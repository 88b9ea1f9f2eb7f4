"""The pod's proposal stream, on the host: the yardstick's own copy.

The pod generates its client workload on the device, inside the
resident scan, as a pure function of (seed, round, group, row)
(``minpaxos_tpu/ops/workload.py``: Threefry-2x32 keyed on (seed, round),
countered on (group, row); keys walk a power-of-two key space from a
per-(group, round) base with an odd stride; values are lane 1). This is
that definition again in NumPy, written from the description and
importing nothing of the program, so the reference replays the stream
the configuration states — not whatever the program generated.
"""

from __future__ import annotations

import numpy as np

_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
_PARITY = 0x1BD11BDA
_KEY_STRIDE = 2654435761  # odd: the masked walk is a bijection


def threefry2x32(k0: int, k1: int, c0: np.ndarray, c1: np.ndarray):
    """Threefry-2x32, 20 rounds (Salmon et al., SC'11): key (k0, k1),
    counter (c0, c1) -> two uint32 lanes, elementwise."""
    with np.errstate(over="ignore"):
        k0, k1 = np.uint32(k0 & 0xFFFFFFFF), np.uint32(k1 & 0xFFFFFFFF)
        x0, x1 = np.broadcast_arrays(np.asarray(c0).astype(np.uint32),
                                     np.asarray(c1).astype(np.uint32))
        ks = (k0, k1, k0 ^ k1 ^ np.uint32(_PARITY))
        x0 = x0 + ks[0]
        x1 = x1 + ks[1]
        for i in range(5):
            for r in (_ROT_A if i % 2 == 0 else _ROT_B):
                x0 = x0 + x1
                x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
                x1 = x1 ^ x0
            x0 = x0 + ks[(i + 1) % 3]
            x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def round_rows(seed: int, round_idx: int, groups, rows: int,
               key_space: int) -> tuple[np.ndarray, np.ndarray]:
    """``(keys, vals)`` int32 ``[len(groups), rows]``: what each listed
    group is proposed in ``round_idx``, in row (= log) order."""
    g = np.asarray(groups, np.int32)[:, None]
    col = np.arange(rows, dtype=np.int32)[None, :]
    b0, b1 = threefry2x32(seed, round_idx, g, col)
    with np.errstate(over="ignore"):
        key = ((b0[:, :1] + col.astype(np.uint32) * np.uint32(_KEY_STRIDE))
               & np.uint32(key_space - 1)).astype(np.int32)
    return key, b1.astype(np.int32)


def replay(seed: int, rounds, groups, rows: int,
           key_space: int) -> dict[int, dict[int, int]]:
    """The plain reference: apply every listed round's PUTs, in round
    then row order, into one dict per listed group."""
    tables: dict[int, dict[int, int]] = {int(g): {} for g in groups}
    for r in rounds:
        keys, vals = round_rows(seed, r, groups, rows, key_space)
        for i, g in enumerate(groups):
            tables[int(g)].update(zip(keys[i].tolist(), vals[i].tolist()))
    return tables
