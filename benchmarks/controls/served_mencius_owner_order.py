"""Control for the served Mencius cell: the reference put in the
program's place with ONE stated guarantee broken — every command is
executed, and answered, in the ONE merged order.

Every request is answered from a replay of the log owner after owner
(all of owner 0's slots in slot order, then owner 1's, then owner 2's;
slot ``s`` is owner ``s mod N``'s) in place of slot order: what a
program would answer that executed each owner's commands as they
committed without waiting for the merged frontier to pass them (the
step that would tempt a later PR: ``merge_wait_ms.served`` is the
latency it would save). Under Zipf 0.99 three owners write the same hot
keys, so a GET's latest PUT in owner order is mostly not its latest PUT
in slot order. Files and tables are left as the run produced them.
``correct`` has to come out false, by ``wrong_replies``.
"""

import numpy as np

from benchmarks.lib.served_check import durable_logs, replay
from benchmarks.lib.served_mencius_check import is_client_row


def apply(evidence: dict) -> dict:
    n = len(evidence["files"])
    log = durable_logs(evidence["files"][:1], evidence["fsyncs"][:1])[0]
    rows = log["rows"]
    rows = rows[is_client_row(rows)]
    # stable: within one owner the slots keep their order
    rows = rows[np.argsort(rows["inst"] % n, kind="stable")]
    answers, _ = replay(rows)
    said = dict(zip(rows["cmd_id"].tolist(), answers.tolist()))
    req = dict(evidence["requests"])
    reply = req["reply_val"].copy()
    hit = np.nonzero(np.isin(req["cmd_id"], list(said))
                     & ~np.isnan(req["t_reply"]))[0]
    reply[hit] = [said[c] for c in req["cmd_id"][hit].tolist()]
    req["reply_val"] = reply
    return {**evidence, "requests": req}
