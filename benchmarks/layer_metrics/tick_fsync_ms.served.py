"""Median milliseconds the leader's protocol thread spent inside ``os.fsync`` in ``StableStore.flush``,
per loaded dispatch: the recorder's ``fsync_us`` (span ``paxos.tick.fsync``).
A median: neither the 2 s of warm-up at the cell's own rate nor the 4
profiled seconds in the ring move it."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("fsync_us")
