"""Median milliseconds a sampled command lay decoded in the leader's
queue before the protocol thread drained it: paxtrace, end of ``decode``
to ``drain``. A median over the ring's ~3,000 sampled commands: warm-up
and the profiled seconds do not move it."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.req_queue_wait_ms()
