"""Log bytes the leader's store made durable per committed slot: its
``store_flushed_bytes`` counter over its ``committed`` gauge, cumulative
over the process (boot, warm-up, window and drain alike: a ratio)."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.store_bytes_per_commit()
