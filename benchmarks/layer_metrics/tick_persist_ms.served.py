"""Median milliseconds the leader's protocol thread spent in ``_persist`` and ``store.flush()``, the fsync included,
per loaded dispatch: the recorder's ``persist_us`` (span ``paxos.tick.persist``).
A median: neither the 2 s of warm-up at the cell's own rate nor the 4
profiled seconds in the ring move it."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("persist_us")
