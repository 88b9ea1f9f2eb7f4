"""Median milliseconds of host time a dispatch of the pod's resident
loop takes before the call returns: the five host-made device scalars
and ``sharded_run_resident``'s jit dispatch, the interval of span
``paxos.pod.dispatch``, from the ring of the newest pod in
``obs.process_pods()`` (``dispatch_ns``; every dispatch since the
window's ``begin_resident``). The device waits for it between two
dispatches: it is the gap ``device_idle_pct.pod`` sees from outside,
and it is read only where the run traced a device."""

import numpy as np

from benchmarks.lib import progcpu


def read(obs):
    return progcpu.pod_dispatch_ms(obs, np.median)
