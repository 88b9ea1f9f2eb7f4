"""The bytes one protocol round of a pod configuration MUST move.

Computed from the configuration's and the cell's numbers only — groups,
replicas, proposals per round, the row widths of the logical state — never from
the program's arrays, so it reads the same work whatever implements the
step. It is the numerator of ``pod_round_hbm_roofline``.

Per group and round, with p proposals, R replicas and 4-byte lanes:

* messages: the leader writes one ACCEPT row per proposal for each of
  its R-1 followers and each follower reads it: 2 * (R-1) * p rows of
  MSG_LANES lanes. Acknowledgements are run-length compressed to a few
  rows a round and are left out.
* log: every replica writes the accepted slot (SLOT_LANES lanes), later
  reads and rewrites its status lane to commit it (2 lanes), and reads
  the whole slot once more to execute it (SLOT_LANES lanes).
* KV table: every replica reads the key's candidate entry and writes
  key, value and the live mark: 2 * KV_LANES lanes.

Nothing else is necessary: scans over the whole window, the inbox's
empty rows and the table's untouched buckets are the implementation's.
"""

from __future__ import annotations

LANE_BYTES = 4
#: one message row: kind, src, ballot, inst, last_committed, op,
#: key hi/lo, val hi/lo, cmd_id, client_id
MSG_LANES = 12
#: one log slot: ballot, status, op, key hi/lo, val hi/lo, cmd_id, client_id
SLOT_LANES = 9
#: one table entry: key hi/lo, val hi/lo, live mark
KV_LANES = 5


def necessary_bytes_per_round(config: dict, proposals: int) -> int:
    """``proposals`` is what the cell offers per group and round."""
    g, r, p = config["groups"], config["n_replicas"], proposals
    messages = 2 * (r - 1) * p * MSG_LANES
    log = r * p * (SLOT_LANES + 2 + SLOT_LANES)
    table = r * p * 2 * KV_LANES
    return g * (messages + log + table) * LANE_BYTES
