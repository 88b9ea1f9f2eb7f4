"""Median milliseconds the leader's protocol thread spent in
``transport.flush_all()``, per loaded dispatch: the socket writes of
every buffered peer and client frame, after the fsync. The recorder's
``flush_us`` (span ``paxos.tick.egress.flush``, nested in
``paxos.tick.egress``): system calls, so the thread is off the GIL for
most of it. A median over the ring."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("flush_us")
