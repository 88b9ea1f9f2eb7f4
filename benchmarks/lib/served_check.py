"""What decides ``correct`` in a served cell: a plain reference of the
configuration's guarantees, held against everything a run produced.

The evidence is what came out of the timed path: every request the
clients sent with the reply each got (``requests``), each replica's
store FILE as it lay on the disk after quiesce, before the servers were
stopped (``files``, parsed by ``lib/storefile.py``), when each fsync of
each file returned and how much of the file it covered (``fsyncs``, from
``lib/fsync_ledger.py``) and each replica's device KV table dumped slot
by slot (``tables``). A replica's log is what its file holds within the
bytes that were fsynced: nothing is taken from the program's memory.
The reference is a Python dict: replay the one committed order into it
and every answer follows. It imports nothing of the program and needs
no table layout, hash or codec of the program's.

Each number is a count of breaches of one stated guarantee, so each has
the limit 0 (an exact comparison):

``never_answered``      requests due in the window with no reply a
                        minute past its close (``in_window`` marks them;
                        the warm-up's requests are history for the
                        replay, and are judged by every other number)
``acked_before_durable`` answered requests whose record was, when
                        the reply reached the client, inside the fsynced
                        bytes of fewer than a quorum's files (fsync
                        before reply, at a majority)
``log_divergence``      rows on which a replica's durable log differs
                        from replica 0's, plus the difference in length
``not_logged_once``     answered requests that are not in the log
                        exactly once, as sent (exactly-once)
``invented_rows``       client rows in the log that no client sent
``wrong_replies``       replies that differ from the replay's: a PUT
                        echoes its value, a GET returns the latest PUT
                        before it in log order (0 when there is none)
``realtime_violations`` requests logged before one whose reply had
                        already arrived when they were first sent
``table_mismatch``      (key, value) pairs by which a device table
                        differs from the replay's final dict, over all
                        replicas — an acknowledged write is on every
                        replica, not a quorum's worth
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import storefile

OP_PUT, OP_GET = 1, 2
LIMITS = {"never_answered": 0, "acked_before_durable": 0,
          "log_divergence": 0, "not_logged_once": 0,
          "invented_rows": 0, "wrong_replies": 0, "realtime_violations": 0,
          "table_mismatch": 0}


def client_rows(log: np.ndarray) -> np.ndarray:
    """The log's client commands, in log order (no-op fills have a
    negative client id)."""
    return log[log["client_id"] >= 0]


def replay(rows: np.ndarray) -> tuple[np.ndarray, dict[int, int]]:
    """The plain reference: apply ``rows`` in order to a dict. Returns
    the value each row's reply must carry and the final dict."""
    table: dict[int, int] = {}
    want = np.zeros(len(rows), np.int64)
    for i, (op, key, val) in enumerate(zip(rows["op"].tolist(),
                                           rows["key"].tolist(),
                                           rows["val"].tolist())):
        if op == OP_PUT:
            table[key] = val
            want[i] = val
        elif op == OP_GET:
            want[i] = table.get(key, 0)
    return want, table


def durable_logs(files: list[bytes], fsyncs: list[dict]) -> list[dict]:
    """Each replica's file parsed within the bytes its last fsync
    covered: what would be there after a power cut."""
    return [storefile.parse(data[:int(f["size"][-1]) if len(f["size"]) else 0]
                            or storefile.MAGIC)
            for data, f in zip(files, fsyncs)]


def acked_before_durable(requests: dict, parsed: list[dict],
                         fsyncs: list[dict], quorum: int) -> int:
    """Answered requests that fewer than ``quorum`` files held durably
    at the instant the reply arrived (one clock: CLOCK_MONOTONIC)."""
    answered = np.nonzero(~np.isnan(requests["t_reply"]))[0]
    cmd, t_reply = requests["cmd_id"][answered], requests["t_reply"][answered]
    holders = np.zeros(len(answered), np.int64)
    for log, f in zip(parsed, fsyncs):
        ids = np.fromiter(log["first_end"], np.int64, len(log["first_end"]))
        ends = np.fromiter(log["first_end"].values(), np.int64, len(ids))
        order = np.argsort(ids)
        ids, ends = ids[order], ends[order]
        at = np.minimum(np.searchsorted(ids, cmd), max(len(ids) - 1, 0))
        found = (ids[at] == cmd) if len(ids) else np.zeros(len(cmd), bool)
        # bytes of this file that were fsynced when the reply arrived
        n_done = np.searchsorted(f["t_done"], t_reply, side="right")
        synced = np.r_[0, f["size"]][n_done]
        holders += found & ((ends[at] if len(ids) else 0) <= synced)
    return int((holders < quorum).sum())


def compare(requests: dict, files: list[bytes], fsyncs: list[dict],
            tables: list[dict[int, int]], quorum: int) -> dict[str, int]:
    """The numbers compared, each against ``LIMITS``."""
    out = dict.fromkeys(LIMITS, 0)
    answered = ~np.isnan(requests["t_reply"])
    out["never_answered"] = int((~answered & requests["in_window"]).sum())
    parsed = durable_logs(files, fsyncs)
    logs = [p["rows"] for p in parsed]
    out["acked_before_durable"] = acked_before_durable(
        requests, parsed, fsyncs, quorum)

    fields = ["op", "key", "val", "cmd_id"]
    base = logs[0]
    for log in logs[1:]:
        n = min(len(base), len(log))
        out["log_divergence"] += abs(len(base) - len(log)) + int(
            (base[fields][:n] != log[fields][:n]).sum())

    rows = client_rows(base)
    want, final = replay(rows)
    # join log rows to requests on the command id
    order = np.argsort(requests["cmd_id"], kind="stable")
    ids = requests["cmd_id"][order]
    at = np.searchsorted(ids, rows["cmd_id"])
    hit = (at < len(ids)) & (ids[np.minimum(at, len(ids) - 1)]
                             == rows["cmd_id"])
    out["invented_rows"] = int((~hit).sum())
    req_of_row = order[np.minimum(at, len(ids) - 1)]  # valid where hit
    times_logged = np.bincount(req_of_row[hit], minlength=len(ids))
    as_sent = np.ones(len(ids), bool)
    r = req_of_row[hit]
    same = ((requests["op"][r] == rows["op"][hit])
            & (requests["key"][r] == rows["key"][hit])
            & (requests["val"][r] == rows["val"][hit]))
    as_sent[r[~same]] = False
    out["not_logged_once"] = int(
        (answered & ((times_logged != 1) | ~as_sent)).sum())

    # first log position and reference answer of every request
    pos = np.full(len(ids), -1, np.int64)
    ref = np.zeros(len(ids), np.int64)
    row_idx = np.nonzero(hit)[0][::-1]  # reversed: the first row wins
    pos[req_of_row[row_idx]] = row_idx
    ref[req_of_row[row_idx]] = want[row_idx]
    judged = answered & (pos >= 0)
    out["wrong_replies"] = int(
        (requests["reply_val"][judged] != ref[judged]).sum())

    # real-time order: whatever was answered before request B was first
    # sent must come before B in the log
    j = np.nonzero(judged)[0]
    by_reply = j[np.argsort(requests["t_reply"][j])]
    latest_pos = np.maximum.accumulate(pos[by_reply])
    n_before = np.searchsorted(requests["t_reply"][by_reply],
                               requests["t_sent"][j], side="left")
    seen = n_before > 0
    out["realtime_violations"] = int(
        (latest_pos[n_before[seen] - 1] > pos[j][seen]).sum())

    want_items = set(final.items())
    for table in tables:
        out["table_mismatch"] += len(want_items ^ set(table.items()))
    return out
