"""Median milliseconds the leader's protocol thread spent on the HOST
half of enqueue, per loaded dispatch: draining the column buffer, the
``MsgBatch`` of fourteen numpy columns, and the fuse / narrow choice
(up to six passes over the rows). The recorder's ``assemble_us`` (span
``paxos.tick.enqueue.assemble``, nested in ``paxos.tick.enqueue``): work
that grows with the rows of the batch. A median over the ring, as
``tick_enqueue_ms.served``."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_median_ms("assemble_us")
