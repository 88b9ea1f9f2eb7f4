"""The bytes one state transfer of a pod configuration MUST move.

A replica that has fallen below its leader's log window is healed by a
copy of the leader's executed state: its KV table. Reckoned from the
configuration's numbers alone (the table's capacity and the row width
``lib/necessary_bytes.py`` states), never from the program's arrays:
every entry of the table is read at the donor and written at the
laggard. The window of the log is not part of it (the laggard's is
cleared, the donor's stays) and the cursors are a few words. It is the
numerator of ``state_transfer_mb.pod``.
"""

from __future__ import annotations

from benchmarks.lib.necessary_bytes import KV_LANES, LANE_BYTES


def transfer_bytes_per_install(config: dict) -> int:
    """One install: 2^kv_pow2 entries of KV_LANES lanes, read once and
    written once."""
    return 2 * (1 << config["kv_pow2"]) * KV_LANES * LANE_BYTES
