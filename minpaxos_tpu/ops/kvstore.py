"""Vectorized replicated-KV state machine.

Counterpart of reference src/state/state.go: ``Command.Execute`` applies
PUT/GET/DELETE against an in-memory map (state.go:86-103, backed by
``map[Key]Value`` state.go:33-36). The reference executes commands one
at a time in a polling goroutine (bareminpaxos.go:1066-1098); here a
whole contiguous range of committed log slots is applied in ONE jitted
call while preserving the reference's sequential semantics:

* a GET sees the latest PUT/DELETE to its key among *earlier* slots in
  the same batch, else the pre-batch table state;
* the table ends up as if commands ran one-by-one in slot order;
* PUT returns its own value, GET the read value (NIL=0 when absent),
  DELETE removes — matching Execute's return convention.

Mechanics: rows are sorted by (key, slot) with ``jnp.lexsort``; "the
last write to my key before me" becomes an exclusive segmented
max-scan (ops/scan.py) over the sorted order; final writers per key
(segment maxima) are inserted into a bucketized two-choice hash table
(W ways per bucket, two candidate buckets per key) in a single
LOOP-FREE pass. Everything is fixed-shape and branch-free — no
``while_loop`` anywhere in the KV path — so XLA compiles it once per
batch size and the table arrays never ride a loop carry (the round-4
linear-probing engine made XLA copy all four table arrays through two
while carries per protocol step, ~80MB of pure copy traffic per tick
at kv_pow2=20).

The probe: a batch reads the ``slot``, ``key_hi`` and ``key_lo`` of
each key's 2W candidate ways ONCE, shared by the lookup and the insert
(``_probe``). The TPU gathers elements at some 14 ns apiece whatever
each fetches, so the three columns are stacked as ``int32[3, C]`` and
read by ONE gather of ``[B, 2W]`` indices, each fetching three words;
as three element gathers they were 22 % of ``pod128_steady``'s round
(three ``s32[655360]`` at 9.22 ms each) and 14 % of
``mencius64k_steady``'s (PERF_LEDGER.jsonl). The stack axis is the
MAJOR one: a ``[C, 3]`` row gather pads every fetched row to 128 lanes
(PERF.md section 7 row 21), and under the pod's ``[G, R]`` vmap such a
table leaf would be padded 40x. ``KVState`` keeps its four arrays
(snapshots, the benchmark and the tests read them by position), so the
stack is made on every call: an O(C) copy, 3 x 4 B x C a table (503 MB
a round over ``pod128_steady``'s 640 tables of 2^16, tiled to four
rows, 671 MB; 15.7 MB over ``mencius64k_steady``'s 80 of 2^14; 9.4 MB
over the served cells' three of 2^18). The write side stays O(B): the
four scatters update in place under the steps' donation.

Device time on a TPU v5e, isolated, ms
(``tools/scatter_micro.py kvprobe``; [G, R] tables of 2^p entries, B
rows x 2W = 8 ways; the stack's copy included; ``insert`` is
``_insert_probed`` whole, claims and write scatters with it):

==============================  ========  =======  =======  ======  ======
shape                           elements  barrier  _probe   insert  insert
                                                            parent  _probe
==============================  ========  =======  =======  ======  ======
pod128 [128,5] x 128 <- 2^16       29.89    12.31    12.31   58.37   41.86
mencius64k [16,5] x 320 <- 2^14     8.97     1.82     1.82   13.98    8.14
served3 [3] x 128 <- 2^18          0.086    0.040    0.039   0.508   0.462
==============================  ========  =======  =======  ======  ======

``elements`` is the parent's three element gathers; ``barrier`` the
stacked gather with an ``optimization_barrier`` on the stack, which
compiles to the same program as without one (XLA neither splits the
gather again nor folds the stack into it), so ``_probe`` has none.
Temporaries: 2.9 -> 1,174.5 MB for the probe alone and 172.3 -> 676.0
MB for the insert at pod128 (the stack, tiled ``T(4,128)``), 0.9 -> 0.0
and 17.8 -> 4.4 MB at mencius64k, none at served3; in the whole round
the peak rose by 26.5 MB in ``pod128_steady`` and 3.8 MB in
``mencius64k_steady``. In the round the stacked gather reads 8.29 ms,
0.90 of one of the three it replaced, and the round fell by 14.0 ms,
not the 19.4 those two give: the stack's copy (about 4 ms at pod128,
the isolated ``_probe`` less its gather) is the rest (PERF.md section
6).

Keys are 64-bit on the wire and (hi, lo) i32 lane pairs on device
(ops/packed.py). Values are a ``[*, L]`` i32 lane axis: the engine
(``kv_init`` / ``kv_lookup_lanes`` / ``kv_apply_batch_lanes``) is
generic over L and tested at L=256 — the reference's 1KB build variant
(state.go.1k:15, ``Value [128]int64`` = 256 i32 lanes). The consensus
log and wire schemas instantiate L=2 (one i64 value, statemarsh.go:8-21)
through the ``kv_lookup`` / ``kv_apply_batch`` wrappers below; widening
THOSE is a deployment-wide schema swap, exactly like the reference
swapping state.go for state.go.1k at build time (wire/messages.py
design note), and the seam is these two wrappers plus the ``val``
columns in wire/messages.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from minpaxos_tpu.ops.packed import pair_hash
from minpaxos_tpu.ops.scan import exclusive_segmented_scan_max, segmented_scan_max
from minpaxos_tpu.ops.sections import Sections
from minpaxos_tpu.wire.messages import Op

# Slot states in the table. Buckets have no probe chains to preserve,
# so DELETE frees its slot outright (EMPTY) and churn on a key reuses
# capacity immediately; no tombstone state is needed.
EMPTY, LIVE = 0, 1

# Ways per bucket. A key hashes to two candidate buckets and may live
# in any of their 2*W ways — a fixed 2*W-slot gather replaces the
# round-4 linear-probe while_loop (power-of-two-choices keeps the max
# bucket load near the average, so placement failures are a sizing
# error, not a hashing accident; they are counted in kv.dropped and
# the TCP runtime fail-stops on them). Minimum table: one bucket.
WAYS = 4

# i32 lanes per value on the consensus path: one 8-byte wire value
# (statemarsh.go:8-21). The engine itself is lane-generic — see module
# docstring and kv_init(val_lanes=...).
VAL_LANES = 2


class KVState(NamedTuple):
    """Open-addressing hash table over flat i32 arrays (power-of-2 size)."""

    key_hi: jnp.ndarray  # i32[C]
    key_lo: jnp.ndarray  # i32[C]
    val: jnp.ndarray  # i32[C, L] (lane-major [L, C] was tried and
    # measured SLOWER: the axis-1 scatter it needs lowers far worse
    # than the [C, L] row scatter's two residual copies)
    slot: jnp.ndarray  # i32[C]: EMPTY / LIVE
    dropped: jnp.ndarray  # i32 scalar: inserts lost to a full table


def kv_init(capacity_pow2: int, val_lanes: int = VAL_LANES) -> KVState:
    c = 1 << capacity_pow2
    assert c >= WAYS, "table must hold at least one bucket"
    z = jnp.zeros(c, dtype=jnp.int32)
    return KVState(z, z, jnp.zeros((c, val_lanes), jnp.int32), z,
                   jnp.int32(0))


def _cand_pos(capacity: int, k_hi: jnp.ndarray, k_lo: jnp.ndarray):
    """The 2*W candidate slot positions of each key: i32[B, 2W].

    Bucket 1 from the primary hash; bucket 2 from an independent mix,
    forced distinct from bucket 1 whenever the table has more than one
    bucket (maximum placement flexibility at small tables)."""
    nb = capacity // WAYS
    h1 = pair_hash(k_hi, k_lo)
    b1 = (h1 & jnp.uint32(nb - 1)).astype(jnp.int32)
    if nb > 1:
        h2 = pair_hash(k_lo ^ jnp.int32(0x2545F491), k_hi ^ jnp.int32(0x61C88647))
        b2 = ((b1 + 1 + (h2 % jnp.uint32(nb - 1)).astype(jnp.int32)) % nb)
    else:
        b2 = b1
    w = jnp.arange(WAYS, dtype=jnp.int32)
    return jnp.concatenate(
        [b1[:, None] * WAYS + w[None, :], b2[:, None] * WAYS + w[None, :]],
        axis=1)


def _probe(kv: KVState, pos: jnp.ndarray):
    """The ``slot``, ``key_hi`` and ``key_lo`` of every candidate way:
    three ``i32[B, 2W]`` planes from ONE gather of the three columns
    stacked as ``i32[3, C]`` (module docstring, "The probe")."""
    got = jnp.stack([kv.slot, kv.key_hi, kv.key_lo])[:, pos]
    return got[0], got[1], got[2]


def kv_lookup_lanes(kv: KVState, k_hi: jnp.ndarray, k_lo: jnp.ndarray,
                    valid: jnp.ndarray | None = None):
    """Batched lookup: returns (found bool[B], v i32[B, L]).

    One fixed [B, 2W] probe of the two candidate buckets — loop-free."""
    pos = _cand_pos(kv.key_hi.shape[0], k_hi, k_lo)
    return _lookup_probed(kv, pos, _probe(kv, pos), k_hi, k_lo, valid)


def _lookup_probed(kv, pos, probe, k_hi, k_lo, valid):
    """``kv_lookup_lanes`` on the candidate ways ``pos`` and their
    ``_probe`` planes."""
    lanes = kv.val.shape[1]
    if valid is None:
        valid = jnp.ones(k_hi.shape[0], dtype=bool)
    slot, key_hi, key_lo = probe
    hit = ((slot == LIVE) & (key_hi == k_hi[:, None])
           & (key_lo == k_lo[:, None]) & valid[:, None])
    found = hit.any(axis=1)
    # at most one way holds a key; argmax picks it (0 when absent)
    way = jnp.argmax(hit, axis=1)
    v = jnp.where(found[:, None],
                  kv.val[pos[jnp.arange(pos.shape[0]), way]],
                  jnp.zeros((1, lanes), jnp.int32))
    return found, v


def kv_lookup(kv: KVState, k_hi: jnp.ndarray, k_lo: jnp.ndarray,
              valid: jnp.ndarray | None = None):
    """2-lane (single-i64-value) probe: (found, v_hi, v_lo)."""
    found, v = kv_lookup_lanes(kv, k_hi, k_lo, valid)
    return found, v[:, 0], v[:, 1]


def kv_insert_unique(kv: KVState, k_hi, k_lo, v, delete, valid) -> KVState:
    """Insert/overwrite/delete a batch of rows with DISTINCT keys.

    ``v`` is i32[B, L]. Entirely LOOP-FREE (round-5 redesign): one
    [B, 2W] probe of each key's two candidate buckets resolves every
    row's destination in a single pass, then ONE batch of four
    scatters writes the table. Under the protocol steps' state
    donation the scatters update in place: the round-4 linear-probe
    engine's while carries made XLA copy all four table arrays per
    step and materialize capacity-length claim arrays per probe
    round. What does grow with capacity is the probe's stack of three
    columns, an O(C) copy (module docstring, "The probe").

    Placement:

    * a key already LIVE in a candidate way overwrites in place
      (DELETE frees the slot outright — buckets have no probe chains
      to preserve, so no tombstones);
    * new keys choose the candidate bucket with more free ways
      (power-of-two-choices), and batch-internal contention is solved
      by W statically-unrolled claim rounds: each round, contending
      rows scatter-min their row index into a bucket-count array
      (C/W entries — NOT capacity-length, and never inside a traced
      loop); the round-r winner of a bucket takes its r-th free way.
      Sorts were measured ~0.9 ms per jnp.lexsort at B=4096 on the
      CPU backend, so the rank-by-stable-sort formulation lost to
      this by ~10x;
    * rows whose bucket wins run out of free ways retry their other
      bucket the same way, minus ways the first pass claimed (a
      scatter-or bitmask over buckets);
    * rows that fit in neither bucket are counted in kv.dropped
      (callers should size kv_pow2 comfortably above the distinct-key
      count, as with any bounded table; the TCP runtime fail-stops on
      dropped > 0 — runtime/replica.py)."""
    pos = _cand_pos(kv.key_hi.shape[0], k_hi, k_lo)
    return _insert_probed(kv, pos, _probe(kv, pos), k_hi, k_lo, v,
                          delete, valid)


def _insert_probed(kv, pos, probe, k_hi, k_lo, v, delete, valid):
    """``kv_insert_unique`` on the candidate ways ``pos`` (``[B, 2W]``)
    and their ``_probe`` planes."""
    c = kv.key_hi.shape[0]
    b = k_hi.shape[0]
    nb = c // WAYS
    big = jnp.int32(2**31 - 1)
    rows = jnp.arange(b, dtype=jnp.int32)
    way_ix = jnp.arange(WAYS, dtype=jnp.int32)

    s, key_hi, key_lo = probe
    live_match = ((s == LIVE) & (key_hi == k_hi[:, None])
                  & (key_lo == k_lo[:, None]))
    has_match = live_match.any(axis=1)
    match_pos = pos[rows, jnp.argmax(live_match, axis=1)]

    free = s == EMPTY  # [B, 2W]
    free1, free2 = free[:, :WAYS], free[:, WAYS:]
    bkt1, bkt2 = pos[:, 0] // WAYS, pos[:, WAYS] // WAYS
    pref2 = free2.sum(axis=1) > free1.sum(axis=1)
    place = valid & ~has_match & ~delete  # delete-of-absent is a no-op

    def assign(mask, bkt, fm):
        """W claim rounds: the round-r winner of each bucket (lowest
        contending row index, via scatter-min into an [NB] array)
        takes the bucket's r-th free way."""
        # way_of_rank[i, r]: which way holds the r-th free slot of
        # row i's bucket (and whether rank r exists at all)
        onehot = fm[:, None, :] & (jnp.cumsum(fm, axis=1)[:, None, :] - 1
                                   == way_ix[None, :, None])
        has_rank = onehot.any(axis=2)
        way_of_rank = jnp.argmax(onehot, axis=2)
        dest = jnp.full(b, -1, jnp.int32)
        rem = mask
        for r in range(WAYS):
            claims = jnp.full(nb, big).at[
                jnp.where(rem, bkt, nb)].min(
                jnp.where(rem, rows, big), mode="drop")
            won = rem & (claims[jnp.clip(bkt, 0, nb - 1)] == rows)
            ok = won & has_rank[:, r]
            dest = jnp.where(ok, bkt * WAYS + way_of_rank[:, r], dest)
            # winners leave the contest placed or not: a bucket out of
            # free ways can't place later rounds either
            rem = rem & ~won
        return dest >= 0, dest

    # pass A: the emptier candidate bucket
    tb = jnp.where(pref2, bkt2, bkt1)
    placed_a, pos_a = assign(place, tb,
                             jnp.where(pref2[:, None], free2, free1))
    # pass B: overflow rows retry the other bucket, minus pass-A
    # claims (a scatter-or way bitmask per bucket)
    ob = jnp.where(pref2, bkt1, bkt2)
    cl_bits = jnp.zeros(nb, jnp.int32).at[
        jnp.where(placed_a, pos_a // WAYS, nb)].add(
        jnp.where(placed_a, jnp.int32(1) << (pos_a % WAYS), 0),
        mode="drop")
    taken_b = (cl_bits[jnp.clip(ob, 0, nb - 1)][:, None]
               >> way_ix[None, :]) & 1
    fm_b = jnp.where(pref2[:, None], free1, free2) & (taken_b == 0)
    placed_b, pos_b = assign(place & ~placed_a, ob, fm_b)

    dest = jnp.where(valid & has_match, match_pos,
                     jnp.where(placed_a, pos_a,
                               jnp.where(placed_b, pos_b, -1)))
    wpos = jnp.where(dest >= 0, dest, c)
    new_slot = jnp.where(delete, jnp.int32(EMPTY), jnp.int32(LIVE))
    return kv._replace(
        key_hi=kv.key_hi.at[wpos].set(k_hi, mode="drop"),
        key_lo=kv.key_lo.at[wpos].set(k_lo, mode="drop"),
        val=kv.val.at[wpos].set(v, mode="drop"),
        slot=kv.slot.at[wpos].set(new_slot, mode="drop"),
        dropped=kv.dropped + (place & ~placed_a & ~placed_b).sum(),
    )


def kv_apply_batch_lanes(kv: KVState, op, k_hi, k_lo, v, valid):
    """Apply B commands in slot order; returns (kv', out i32[B, L],
    found bool[B]).

    ``op`` follows wire Op codes; ``v`` is i32[B, L]. Outputs are in
    the original row order: PUT echoes its value, GET returns the value
    visible at its slot (found=False, zeros when absent), DELETE
    returns zeros. RLOCK/WLOCK/NONE are no-ops (the reference parses
    but never implements them, state.go:12-19 vs :86-103).
    """
    with Sections() as sec:
        return _kv_apply_sections(sec, kv, op, k_hi, k_lo, v, valid)


def _kv_apply_sections(sec, kv, op, k_hi, k_lo, v, valid):
    """``kv_apply_batch_lanes``'s body; ``sec(name)`` opens the
    ``px.kv.*`` scope of the section that follows (ops/sections.py)."""
    sec("px.kv.sort")
    b = op.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)
    is_put = valid & (op == Op.PUT)
    is_del = valid & (op == Op.DELETE)
    is_get = valid & (op == Op.GET)
    is_write = is_put | is_del

    # Sort by (key, slot); invalid rows cluster at the end.
    sk_hi = jnp.where(valid, k_hi, jnp.int32(2**31 - 1))
    sk_lo = jnp.where(valid, k_lo, jnp.int32(2**31 - 1))
    order = jnp.lexsort((rows, sk_lo, sk_hi))

    def g(x):
        return x[order]

    s_khi, s_klo, s_valid = g(k_hi), g(k_lo), g(valid)
    s_put, s_del, s_write = g(is_put), g(is_del), g(is_write)
    s_v = v[order]

    pos = jnp.arange(b, dtype=jnp.int32)
    seg_start = (pos == 0) | (s_khi != jnp.roll(s_khi, 1)) | (s_klo != jnp.roll(s_klo, 1)) \
        | (s_valid != jnp.roll(s_valid, 1))

    sec("px.kv.scan")
    # last write before me within my segment (sorted position, -1 if none)
    wpos = jnp.where(s_write, pos, -1)
    prev_w = exclusive_segmented_scan_max(wpos, seg_start, jnp.int32(-1))
    has_prev = prev_w >= 0
    pw = jnp.where(has_prev, prev_w, 0)
    prev_present = has_prev & s_put[pw]
    prev_v = s_v[pw]

    sec("px.kv.insert")
    # ONE probe of the pre-batch table for the lookup and the insert,
    # both at the sorted keys; in the insert's scope, where the pod
    # (which drops the lookup) has always had it
    cand = _cand_pos(kv.key_hi.shape[0], s_khi, s_klo)
    probe = _probe(kv, cand)

    sec("px.kv.lookup")
    # pre-batch table state for rows with no in-batch predecessor
    t_found, t_v = _lookup_probed(kv, cand, probe, s_khi, s_klo,
                                  s_valid & ~has_prev)

    sec("px.kv.output")
    eff_present = jnp.where(has_prev, prev_present, t_found)
    eff_v = jnp.where(has_prev[:, None],
                      jnp.where(prev_present[:, None], prev_v, 0), t_v)

    out_s = jnp.where(g(is_put)[:, None], s_v,
                      jnp.where(g(is_get)[:, None], eff_v, 0))
    found_s = jnp.where(g(is_get), eff_present, g(is_put))

    # scatter back to original row order
    out = jnp.zeros_like(v).at[order].set(out_s)
    found = jnp.zeros(b, bool).at[order].set(found_s)

    sec("px.kv.insert")
    # final writer per key = max write position in segment
    seg_max_w = segmented_scan_max(wpos, seg_start)
    # propagate the segment total (value at last row of segment) backwards:
    # reverse-scan max with reversed segment boundaries
    seg_end = jnp.roll(seg_start, -1).at[b - 1].set(True)
    seg_total = segmented_scan_max(seg_max_w[::-1], seg_end[::-1])[::-1]
    is_final_writer = s_write & (pos == seg_total)

    kv = _insert_probed(kv, cand, probe, s_khi, s_klo, s_v, s_del,
                        is_final_writer)
    return kv, out, found


def kv_apply_batch(kv: KVState, op, k_hi, k_lo, v_hi, v_lo, valid):
    """2-lane (single-i64-value) apply: (kv', out_hi, out_lo, found) —
    the consensus kernels' entry point (models/minpaxos.py step 8,
    models/mencius.py step 11)."""
    v = jnp.stack([v_hi, v_lo], axis=1)
    kv, out, found = kv_apply_batch_lanes(kv, op, k_hi, k_lo, v, valid)
    return kv, out[:, 0], out[:, 1], found


#: ``kv_apply_batch`` as an inlined jit, for the vmapped compositions
#: (``cfg.gate_exec`` off): the program that holds it is what it was,
#: but the body is TRACED, and batched under the pod's two vmaps, once
#: per shape in a process instead of once per kernel variant. Its
#: arguments do not depend on the inbox rows, so the election's kernel
#: and both tiers of a fused dispatch share the one trace, which was
#: 3.3 s of each kernel trace on the chip's host (PERF.md, PR 31).
kv_apply_batch_shared = jax.jit(kv_apply_batch, inline=True)
