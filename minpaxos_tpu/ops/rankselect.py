"""Rank-select over a nondecreasing count, without dependent gathers.

``rank_select(count, want)[k]`` is the first row whose running count
reaches ``want[k]``: "which pooled row is the k-th one destined to this
inbox" (ops/segscatter.py ``plan_slots``), "which inbox row is the q-th
proposal" (models/mencius.py section 1). It is the ``int32`` array
``jnp.searchsorted(count, want)`` returns, element for element (side
left; ``len(count)`` where no row reaches the rank; plateaus, empty
counts and ranks beyond the total included: tests/test_rankselect.py).

Why not ``jnp.searchsorted``: its default is a binary search, 11-14
DEPENDENT element gathers a query, and the TPU gathers elements at some
10 ns apiece. That search was the largest device op of both pod cells
(ledger, PR 28: 45.3 ms of ``pod128_steady``'s 468 ms round, 35.4 +
12.7 ms of ``mencius64k_steady``'s 257 ms). The rank is a COUNT, "how
many rows lie below ``want``", and a count is vector compares and adds,
which the chip does at over a thousand a nanosecond.

Two formulations, chosen from the static length of ``count`` (one
algorithm that adapts; no option selects it):

* up to ``SHORT_ROWS`` rows, compare every row with every rank and sum.
  XLA:TPU fuses the compare into the reduction, so no [ranks, rows]
  plane exists in memory;
* beyond, two levels: compare each rank with the last row of every
  block of ``BLOCK`` rows (the 128 lanes of a vector register) to find
  its block, fetch that block as ONE row of 128 lanes (a row gather,
  one a query, not a chain of element gathers), and compare inside it:
  rows/128 + 128 compares a query instead of rows. Ranks are taken
  ``CHUNK`` at a time so that the fetched blocks stay a bounded working
  set whatever the number of ranks (unchunked, XLA materialises a
  [ranks, 128] plane: 420 MB at ``pod128_steady``'s full tier).

Device time of one call on a v5e, isolated, ms (my chip runs, PR 29;
``tools/scatter_micro.py rankselect``, [G, R, rows] x ranks):

====================================  ======  =====  =======  =======
shape                                   scan   sort  compare  blocked
====================================  ======  =====  =======  =======
pod128 route small [128,5,8645]x512    92.49  49.22     2.18     2.17
pod128 route full [128,5,12485]x1280  239.59  81.60     7.81     5.47
mencius propose [16,5,1216]x4096       44.58   4.01     0.50     2.11
mencius propose, full [16,5,2112]      48.63   4.34     0.86     2.12
mencius route small [16,5,8965]x1152   28.36   5.90     0.63     0.68
mencius route full [16,5,13445]x2048   28.45   8.97     1.75     1.10
====================================  ======  =====  =======  =======

The whole compare grows with rows x ranks (0.25 ms a thousand rows at
[128, 5, .] x 512); the blocked search pays some 6 ns a rank for its
row fetch whatever the rows, so the two cross near 8,500 rows
([128,5,4096]x512: 0.97 against 2.09 ms; [128,5,6144]x512: 1.52
against 2.14; [16,5,4096]x4096: 0.96 against 2.13). Both propose
shapes lie far below (compare wins 4x), both full tiers above (blocked
wins 1.4-1.6x), and the small-tier routes sit on the crossing.
``SHORT_ROWS`` is set at HALF the crossing because XLA:CPU does NOT
fuse the whole compare: there the plane is real memory (98 MB and
75 ms a group at [5, 9280] x 512) and the tier-1 tests' larger shapes
would pay for it, while the blocked search is cheap on both backends.
On the chip that costs a count of 4,097-8,500 rows up to 1.1 ms; no
configuration has one. ``jnp.searchsorted``'s own ``method="sort"``
ranks by a scatter and loses everywhere; an exact one-hot MATMUL in
place of the row fetch read 1.56-4.10 ms but materialises its plane.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["rank_select", "BLOCK", "CHUNK", "SHORT_ROWS"]

BLOCK = 128        # rows a block: the lanes of a vector register
CHUNK = 256        # ranks a step of the blocked search
SHORT_ROWS = 4096  # counts up to this long are compared whole

_I32_MAX = np.iinfo(np.int32).max


def _below(rows: jnp.ndarray, want: jnp.ndarray) -> jnp.ndarray:
    """How many of ``rows[..., :]`` lie below each rank: [Q] int32.
    ``rows`` is [N] (every rank sees the same rows) or [Q, N]."""
    return (rows < want[:, None]).sum(-1, dtype=jnp.int32)


def _blocked(count: jnp.ndarray, want: jnp.ndarray) -> jnp.ndarray:
    n, q = count.shape[0], want.shape[0]
    nb = -(-n // BLOCK)
    # padding never lies below a rank, so the last block counts only
    # its real rows and a rank no row reaches comes out as n
    blocks = jnp.pad(count, (0, nb * BLOCK - n),
                     constant_values=_I32_MAX).reshape(nb, BLOCK)

    def search(w):
        b = jnp.minimum(_below(blocks[:, -1], w), nb - 1)
        return b * BLOCK + _below(blocks[b], w)

    if q <= CHUNK:
        return search(want)
    steps = -(-q // CHUNK)
    chunks = jnp.pad(want, (0, steps * CHUNK - q)).reshape(steps, CHUNK)
    return jax.lax.map(search, chunks).reshape(-1)[:q]


def rank_select(count: jnp.ndarray, want: jnp.ndarray) -> jnp.ndarray:
    """First row of nondecreasing ``count`` [N] (int32) that reaches
    each rank of ``want`` [Q] (int32): ``jnp.searchsorted(count,
    want)`` as int32[Q], N where none does. 1-D; batch with ``vmap``."""
    if count.shape[0] <= SHORT_ROWS:
        return _below(count, want)
    return _blocked(count, want)
