"""Share of a tick's wall (its wait excluded) in which the leader's
protocol thread itself ran: the recorder's ``cpu_us`` (``thread_time_ns``)
over (wall since the row before - ``wait_us``), both summed over the
loaded dispatches (that clock moves in 10 ms steps, so no median of
rows); the rest is the GIL, the disk and the device. A ratio over some
1,000-1,900 dispatches: the 2 s of warm-up at the cell's own rate hardly
move it, the 4 profiled seconds (an eighth of the rows) by what the
profiler costs the thread."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.tick_cpu_share_pct()
