"""Median milliseconds from a leader dispatch's readback to the slower
follower's first readback at that frontier or past it, over the leader's
loaded dispatches that advanced its frontier: the three recorders'
``frontier`` and ``t_rb_ns``. A median: warm-up and the profiled seconds
do not move it."""

from benchmarks.lib import progobs


def read(obs):
    return progobs.follower_lag_ms()
