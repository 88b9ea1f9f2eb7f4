"""Share of the enqueue phase in which the leader's protocol thread
itself ran: the recorder's ``enqueue_cpu_us`` (``thread_time_ns`` over
span ``paxos.tick.enqueue``) over ``enqueue_us``, both summed over the
loaded dispatches whose CPU times were measured (``cpu_sampled``, one
row in eight; that clock moves in 10 ms steps on the chip's host, so no
median of rows). Near 100: enqueue is host work the thread does;
far under: it waits there, for the GIL while reader threads decode or
inside the runtime's call."""

from benchmarks.lib import progcpu


def read(obs):
    return progcpu.phase_cpu_share_pct("enqueue_us")
