"""A device KV table read back as a dict, slot by slot."""

from __future__ import annotations

import numpy as np

LIVE = 1  # the table's mark of an occupied slot


def join_i64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """Two int32 lanes -> the int64 they spell (lo is the low word)."""
    return (hi.astype(np.int64) << 32) | (lo.astype(np.int64) & 0xFFFFFFFF)


def dump_table(key_hi, key_lo, val, slot) -> dict[int, int]:
    """Every live entry's 64-bit key and value, from the table's flat
    arrays (``val`` is ``[C, 2]``: high and low lane). It walks the
    slots, so it needs nothing of the table's hashing or placement."""
    live = np.asarray(slot) == LIVE
    v = np.asarray(val)[live]
    keys = join_i64(np.asarray(key_hi)[live], np.asarray(key_lo)[live])
    return dict(zip(keys.tolist(), join_i64(v[:, 0], v[:, 1]).tolist()))
