"""Median milliseconds the leader's protocol thread spent inside the
jitted call alone, per loaded dispatch: the fourteen host-to-device
transfers of the inbox's columns and the jit dispatch, until the call
returns with the outputs still in flight. The recorder's ``call_us``
(span ``paxos.tick.enqueue.call``, nested in ``paxos.tick.enqueue``):
the inbox has one padded shape at every rate, so what grows here with
the rate is a wait, not bytes. A median over the ring."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("call_us")
