"""Control for the served cells: the reference put in the program's
place with ONE stated guarantee broken — fsync of the accepted slots
BEFORE the reply.

Every replica syncs its log once a second instead (the step that would
tempt a later PR: write-behind takes the disk out of every reply): of
each file's fsyncs only the first of every whole second is kept, and
the last, so that nothing is lost for good and logs and tables stay as
the run produced them. Replies that arrived between two syncs were then
acknowledged before they were durable. ``correct`` has to come out
false, by ``acked_before_durable``.
"""

import numpy as np


def apply(evidence: dict) -> dict:
    thinned = []
    for f in evidence["fsyncs"]:
        second = np.floor(f["t_done"])
        keep = np.r_[True, second[1:] != second[:-1]]
        keep[-1] = True
        thinned.append({k: v[keep] for k, v in f.items()})
    return {**evidence, "fsyncs": thinned}
