"""The LONGEST host interval of one dispatch of the pod's resident loop
in the run, in milliseconds: the largest ``dispatch_ns`` of the newest
pod's ring in ``obs.process_pods()`` (span ``paxos.pod.dispatch``). One
stall of the host (the pod cells' only source of spread) shows here and
explains a run whose ``pod_commits_per_s`` reads low."""

import numpy as np

from benchmarks.lib import progcpu


def read(obs):
    return progcpu.pod_dispatch_ms(obs, np.max)
