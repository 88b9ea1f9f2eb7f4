"""Median milliseconds the leader's protocol thread spent in ``_dispatch``, ``_host_catchup`` and ``transport.flush_all``,
per loaded dispatch: the recorder's ``dispatch_us`` (span ``paxos.tick.egress``).
A median: neither the 2 s of warm-up at the cell's own rate nor the 4
profiled seconds in the ring move it."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("dispatch_us")
