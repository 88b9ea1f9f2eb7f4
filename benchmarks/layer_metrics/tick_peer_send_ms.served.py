"""Median milliseconds the leader's protocol thread spent framing the
outbox for its peers, per loaded dispatch: the strided reshapes of the
stacked outbox matrices and ``_dispatch`` (rows per peer into each
connection's buffered writer). The recorder's ``peer_send_us`` (span
``paxos.tick.egress.peers``, nested in ``paxos.tick.egress``); egress
less this and less ``tick_flush_ms.served`` is ``_host_catchup``. A
median over the ring."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("peer_send_us")
