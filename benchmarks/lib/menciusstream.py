"""The Mencius pod's per-owner proposal streams, on the host: the
yardstick's own copy.

Under rotating ownership every one of the R replicas of a group owns
log slots and serves its OWN clients, so the pod generates one stream
per owner on the device (``minpaxos_tpu/ops/workload.py``, the
multi-owner stream): owner o of group g is proposed, in round r, rows
that are a pure function of (seed, r, g, o, row). Threefry-2x32 keyed
on (seed, round), countered on (group * R + owner, row); values are
lane 1; keys walk the owner's own range ``o * keys_per_owner + walk``,
the walk a masked odd-stride sequence from a per-(group, owner, round)
lane-0 base, so a round's keys are distinct and no two owners ever
write one key (the upstream client's default, 0 % conflicts).

That makes the reference exact under ANY interleaving the protocol
picks for the merged log: a key's final value is the last PUT of its
one owner's stream, in that owner's proposal order. This is the
definition again in NumPy, written from the description and importing
nothing of the program (``lib/podstream.py`` has the Threefry).
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib.podstream import _KEY_STRIDE, threefry2x32


def owner_rows(seed: int, round_idx: int, groups, owners: int, rows: int,
               keys_per_owner: int) -> tuple[np.ndarray, np.ndarray]:
    """``(keys, vals)`` int32 ``[len(groups), owners, rows]``: what each
    owner of each listed group is proposed in ``round_idx``, in row
    (= that owner's proposal) order."""
    g = np.asarray(groups, np.int32)[:, None, None]
    own = np.arange(owners, dtype=np.int32)[None, :, None]
    col = np.arange(rows, dtype=np.int32)[None, None, :]
    b0, b1 = threefry2x32(seed, round_idx, g * owners + own, col)
    with np.errstate(over="ignore"):
        walk = ((b0[..., :1] + col.astype(np.uint32) * np.uint32(_KEY_STRIDE))
                & np.uint32(keys_per_owner - 1)).astype(np.int32)
    return own * keys_per_owner + walk, b1.astype(np.int32)


def replay(seed: int, rounds_of_owner, groups, rows: int,
           keys_per_owner: int) -> dict[int, dict[int, int]]:
    """The plain reference: ``rounds_of_owner[o]`` lists the rounds in
    which owner o was offered ``rows`` PUTs; apply each owner's, in
    round then row order, into one dict per listed group. Owners' key
    ranges are disjoint, so the order ACROSS owners cannot matter."""
    offered = [set(rounds) for rounds in rounds_of_owner]
    owners = len(offered)
    tables: dict[int, dict[int, int]] = {int(g): {} for g in groups}
    for r in sorted(set().union(*offered)):
        keys, vals = owner_rows(seed, r, groups, owners, rows,
                                keys_per_owner)
        for o in range(owners):
            if r in offered[o]:
                for i, g in enumerate(groups):
                    tables[int(g)].update(zip(keys[i, o].tolist(),
                                              vals[i, o].tolist()))
    return tables
