"""Control for the served cells: the reference put in the program's
place with ONE stated guarantee broken — linearizable reads.

Every GET is answered from the state as it was ``LAG`` committed rows
earlier: what a replica would answer from its local table without
ordering the read in the log (the step that would tempt a later PR: a
read that skips consensus is several ticks faster). Files and tables are
left as the run produced them. ``correct`` has to come out false, by
``wrong_replies``.
"""

import numpy as np

from benchmarks.lib.served_check import (OP_GET, OP_PUT, client_rows,
                                         durable_logs)

LAG = 64  # committed rows a local read lags by: under one exec batch


def apply(evidence: dict) -> dict:
    rows = client_rows(durable_logs(evidence["files"][:1],
                                    evidence["fsyncs"][:1])[0]["rows"])
    ops, keys, vals = (rows[f].tolist() for f in ("op", "key", "val"))
    table: dict[int, int] = {}
    stale = {}
    for i, (op, key) in enumerate(zip(ops, keys)):
        if i >= LAG and ops[i - LAG] == OP_PUT:
            table[keys[i - LAG]] = vals[i - LAG]
        if op == OP_GET:
            stale[rows["cmd_id"][i]] = table.get(key, 0)
    req = dict(evidence["requests"])
    reply = req["reply_val"].copy()
    hit = np.nonzero(np.isin(req["cmd_id"], list(stale)))[0]
    reply[hit] = [stale[c] for c in req["cmd_id"][hit].tolist()]
    req["reply_val"] = reply
    return {**evidence, "requests": req}
