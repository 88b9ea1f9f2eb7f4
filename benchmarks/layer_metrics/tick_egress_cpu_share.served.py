"""Share of the egress phase in which the leader's protocol thread
itself ran: the recorder's ``dispatch_cpu_us`` (``thread_time_ns`` over
span ``paxos.tick.egress``) over ``dispatch_us``, both summed over the
loaded dispatches with measured CPU times (``cpu_sampled``, one row in
eight; a ratio of sums: that clock moves in 10 ms steps).
What is missing from 100 the thread spent off the CPU: blocked in a
socket write, or waiting to get the GIL back after one."""

from benchmarks.lib import progcpu


def read(obs):
    return progcpu.phase_cpu_share_pct("dispatch_us")
