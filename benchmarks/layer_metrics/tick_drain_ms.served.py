"""Median milliseconds the leader's protocol thread spent in the rest of ``_drain``: decode, dedup, registration,
per loaded dispatch: the recorder's ``drain_us`` (span ``paxos.tick.drain``).
A median: neither the 2 s of warm-up at the cell's own rate nor the 4
profiled seconds in the ring move it."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("drain_us")
