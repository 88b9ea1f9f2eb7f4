"""Median milliseconds the leader's protocol thread spent assembling the inbox, choosing fuse and narrow, and in the jitted call,
per loaded dispatch: the recorder's ``enqueue_us`` (span ``paxos.tick.enqueue``).
A median: neither the 2 s of warm-up at the cell's own rate nor the 4
profiled seconds in the ring move it."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("enqueue_us")
