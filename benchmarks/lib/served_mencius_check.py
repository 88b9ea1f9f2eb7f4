"""What decides ``correct`` in a served MENCIUS cell: configuration
``mencius3_durable``'s copy of the plain reference.

The evidence is ``lib/served_check.py``'s — every request and reply,
each replica's store FILE within its fsynced bytes, the fsync ledger,
the three device tables — plus, per replica, the no-op slots the
program says it executed (its ``noop_slots`` counter). The reference is
the same Python dict, and nothing of the program is imported.

What holds for Mencius unchanged is imported, not copied, and
``tests/benchmarks/test_served_mencius_check.py`` proves by planted
faults that it does hold:

* ``lib/storefile.py`` returns the log BY SLOT, whatever order the rows
  reached the file in: three owners' rows arrive interleaved, a slot's
  later row supersedes an earlier one unless its ballot is lower (a
  takeover's no-op is written at a higher ballot than the row it
  replaces), and the log is the contiguous prefix of slots from 0;
* ownership is positional (slot ``s`` is owner ``s mod N``'s), so the
  ONE merged order is slot order and ``served_check.compare``'s eight
  numbers mean here what they mean under one leader: ``log_divergence``
  compares slot by slot, ``wrong_replies`` replays the merged order,
  and ``realtime_violations`` orders requests by merged slot whichever
  owner each was sent to — a GET sent to owner 1 after a PUT's reply
  arrived from owner 2 has to lie after it;
* no-op fills are set apart by their negative client id.

What Mencius adds is ``slots_unaccounted``: in a merged log every slot
under the frontier is exactly one client row (a PUT or a GET with a
client's id) or exactly one no-op (no operation, client id -1) that an
owner ceded or a takeover filled. The number counts, over all replicas,
the slots under the file's recorded frontier that the file does not
hold, the rows that are neither kind, and the difference between the
no-op slots the file holds and the no-op slots the program counted:
that ties the ``noop_slots`` counter (``noop_slot_pct.served``) to the
disk.

Limits, each 0, with the readings they were set from (PERF.md section
4 has the runs): the program read 0 on every number in every chip run
of PR 35; the control ``served_mencius_owner_order`` read
``wrong_replies`` in the thousands at the cell's size.
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import served_check
from benchmarks.lib.served_check import OP_GET, OP_PUT, durable_logs

OP_NONE = 0  # wire.messages.Op.NONE, pinned by a test
LIMITS = {**served_check.LIMITS, "slots_unaccounted": 0}


def is_client_row(rows: np.ndarray) -> np.ndarray:
    return (rows["client_id"] >= 0) & np.isin(rows["op"], (OP_PUT, OP_GET))


def is_noop(rows: np.ndarray) -> np.ndarray:
    return (rows["client_id"] < 0) & (rows["op"] == OP_NONE)


def slots_unaccounted(parsed: list[dict], noops_counted: list[int]) -> int:
    """Over all replicas: slots under the frontier that are missing,
    rows that are neither one client row nor one no-op, and the no-op
    slots by which file and program disagree."""
    bad = 0
    for log, counted in zip(parsed, noops_counted):
        rows = log["rows"]
        under = rows[:log["frontier"] + 1]
        bad += max(log["frontier"] + 1 - len(rows), 0)
        bad += int((~(is_client_row(rows) | is_noop(rows))).sum())
        bad += abs(int(is_noop(under).sum()) - int(counted))
    return bad


def compare(requests: dict, files: list[bytes], fsyncs: list[dict],
            tables: list[dict[int, int]], quorum: int,
            noops_counted: list[int]) -> dict[str, int]:
    """The numbers compared, each against ``LIMITS``."""
    out = served_check.compare(requests, files, fsyncs, tables, quorum)
    out["slots_unaccounted"] = slots_unaccounted(
        durable_logs(files, fsyncs), noops_counted)
    return out
