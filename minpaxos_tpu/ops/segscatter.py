"""One-pass segmented routing plans (shared kernels).

The pod-mode routing fabric compacts every replica's addressed outbox
rows into per-destination inboxes. The original fabric
(models/cluster.py ``_route``) vmapped a full masked cumsum + scatter
over the [R·M] pooled rows once PER DESTINATION — O(R²·M) scans and a
per-destination ``slot_winner`` scatter (on the CPU of PR 11 the
scatter was the cost: tools/scatter_micro.py legs e/f).

The segmented plan here does the whole fan-out in one pass:

* each pooled row's destination SEGMENT is computed once (broadcast /
  unicast / client-bound / dead-link, pure [N]-sized masks);
* ONE segment-prefix-sum over the pooled rows (a single cumulative sum
  with the R destination lanes batched — not R independent scans)
  yields every row's offset within its destination inbox; broadcast
  rows expand only in this index arithmetic (dup-free positions, the
  ops/winner.py trick) — the 12 payload columns are NEVER copied per
  destination;
* the winner row for each inbox slot is recovered WITHOUT a scatter
  and without a search: per-destination counts are nondecreasing, so
  slot s's source row is the first whose count reaches s + 1, a
  rank-select done with vector compares (ops/rankselect.py). Until
  PR 29 it was ``jnp.searchsorted``'s binary search, 14 dependent
  element gathers a slot, which the chip measured as the largest
  device op of both pod cells (ledger, PR 28: 45.3 ms of
  ``pod128_steady``'s 468 ms round). The payload lands via 12 dense
  gathers straight into the stacked [R, capacity] inboxes.

Row order per destination is pooled-row order — byte-identical to the
old fabric (tests/test_route_fabric.py pins it, and the golden kernel
fixtures pin it through whole cluster scenarios), including the
overflow-drop-beyond-capacity semantics (legal message loss).

The plan comes in two halves so that a caller can look at the counts
before it chooses how many slots to fill: ``route_counts`` is the
prefix sum (its last column is each destination's row count), and
``plan_slots`` the winner of each of a static number of slots. Filled
slots are a PREFIX of each inbox, so a plan for fewer slots than the
capacity is the capacity's plan cut short: nothing moves, and nothing
drops while every count fits (parallel/sharded.py's two-tier round
chooses the slots on the device each round from exactly that).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from minpaxos_tpu.ops.rankselect import rank_select

__all__ = ["route_counts", "plan_slots", "gather_rows"]


def route_counts(kind_flat: jnp.ndarray, src_rep: jnp.ndarray,
                 fdst: jnp.ndarray, alive: jnp.ndarray) -> jnp.ndarray:
    """The segment-prefix-sum over the pooled outbox rows.

    kind_flat/src_rep/fdst: [N] pooled rows (N = R·M, row i's sender is
    src_rep[i]); fdst semantics: -1 broadcast to all other live
    replicas, 0..R-1 unicast, anything else (e.g. -2 client-bound)
    excluded. alive: bool[R] — dead senders' rows drop, dead
    destinations receive nothing.

    Returns cnt[d, i] = rows destined to d among pooled rows 0..i
    (inclusive): each destined row's inbox offset is its own cnt - 1,
    and cnt[d, -1] is how many rows destination d is sent this round.
    """
    r = alive.shape[0]
    live = (kind_flat != 0) & alive[src_rep]
    isbc = live & (fdst == -1)
    isun = live & (fdst >= 0) & (fdst < r) & (fdst != src_rep)
    dests = jnp.arange(r, dtype=jnp.int32)[:, None]
    # destination plane: row i lands in inbox d iff it broadcasts from
    # another replica or unicasts to d — [R, N] index arithmetic only,
    # never the payload columns
    destined = ((isbc[None, :] & (src_rep[None, :] != dests))
                | (isun[None, :] & (fdst[None, :] == dests))
                ) & alive[:, None]
    return jnp.cumsum(destined.astype(jnp.int32), axis=1)


def plan_slots(cnt: jnp.ndarray,
               slots: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Winner per inbox slot WITHOUT a scatter: cnt[d] is nondecreasing,
    so the row landing at slot s is the first with cnt == s + 1, the
    rank-select of ops/rankselect.py (vector compares; the integers
    ``jnp.searchsorted(cnt[d], s + 1)`` gave, at 2.2 ms where its
    binary search took 45 in ``pod128_steady``'s round).

    Returns (win, hit): win[d, s] = pooled-row index filling slot s of
    destination d's inbox (rows keep pooled order; slots beyond the
    destination's row count are unfilled, rows beyond ``slots`` are
    dropped), hit[d, s] = slot filled.
    """
    want = jnp.arange(1, slots + 1, dtype=jnp.int32)
    win = jax.vmap(rank_select, in_axes=(0, None))(cnt, want)
    return win, win < cnt.shape[1]


def gather_rows(flat_tree, win: jnp.ndarray, hit: jnp.ndarray):
    """Materialize the planned inboxes: 12 dense gathers of the pooled
    columns at the winning rows; unfilled slots are zero (padding)."""
    winc = jnp.where(hit, win, 0)

    def one(col):
        picked = col[winc]
        z = jnp.zeros(win.shape, col.dtype)
        if picked.dtype != col.dtype:  # pragma: no cover - same dtype
            picked = picked.astype(col.dtype)
        return jnp.where(hit, picked, z)

    return jax.tree_util.tree_map(one, flat_tree)
