"""Share of the persist phase in which the leader's protocol thread
itself ran: the recorder's ``persist_cpu_us`` (``thread_time_ns`` over
span ``paxos.tick.persist``, the fsync inside it) over ``persist_us``,
both summed over the loaded dispatches with measured CPU times
(``cpu_sampled``, one row in eight; a ratio of sums: that clock moves in
10 ms steps). The fsync is a wait by nature; what else is
missing from 100 is the GIL after the write and fsync calls gave it
up."""

from benchmarks.lib import progcpu


def read(obs):
    return progcpu.phase_cpu_share_pct("persist_us")
