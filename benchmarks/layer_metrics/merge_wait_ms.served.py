"""Median milliseconds, over the sampled commands of every replica,
from the readback in which the owner's COMMIT row of the command's OWN
slot left the device (paxtrace ``own_commit``) to the readback whose
MERGED frontier covered the slot (``commit``): how long a command that
its own quorum had settled waited for the other owners' slots below it.
The tail of the ``commit`` stage that ``req_commit_ticks`` counts in
dispatches. A median over the rings' sampled chains: warm-up and the
profiled seconds do not move it. A program without the stage reads
nothing."""

import numpy as np

from benchmarks.lib import progobs


def merge_waits_ms(coll) -> np.ndarray:
    """Every sampled chain's wait, newest registration of each replica."""
    try:
        from minpaxos_tpu.obs.trace import (ST_COMMIT, ST_OWN_COMMIT,
                                            span_chains)
    except ImportError:
        return np.zeros(0)
    waits, seen = [], set()
    for entry in reversed(coll or []):
        if entry["replica"] in seen:
            continue
        seen.add(entry["replica"])
        spans = np.asarray(entry["spans"]["spans"], np.int64).reshape(-1, 5)
        waits += [(c[ST_COMMIT][1] - c[ST_OWN_COMMIT][1]) / 1e6
                  for c in span_chains(spans).values()
                  if ST_OWN_COMMIT in c and ST_COMMIT in c]
    return np.asarray(waits)


def read(obs):
    waits = merge_waits_ms(progobs.collection())
    if len(waits) < progobs.MIN_SAMPLES:
        return None
    return float(np.median(waits))
