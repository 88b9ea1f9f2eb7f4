"""Median milliseconds the leader's protocol thread spent blocked in ``queue.get``, waiting for a frame,
per loaded dispatch: the recorder's ``wait_us`` (span ``paxos.tick.wait``).
A median: neither the 2 s of warm-up at the cell's own rate nor the 4
profiled seconds in the ring move it."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("wait_us")
