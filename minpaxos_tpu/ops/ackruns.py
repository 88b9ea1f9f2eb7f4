"""Run-length ack compression + range vote coverage (shared kernels).

The ack-row explosion fix (round 4): a replica acking p contiguous
ACCEPT rows emits ONE live ACCEPT_REPLY row whose cmd_id carries the
run length (the wire ``count`` — this repo's own wire extension to
AcceptReply, modeled on the reference's CommitShort{Instance, Count}
range message, paxosproto.go:50-54 / minpaxosproto.go AcceptReply
itself has no Count field), and the driving replica consumes the range with
a per-sender difference array + prefix sum instead of one scatter per
slot. Both halves live here so the subtle index arithmetic cannot
drift between the MinPaxos and Mencius kernels — they MUST stay in
lockstep or ack emission desynchronizes from vote consumption.
"""

from __future__ import annotations

import jax.numpy as jnp

from minpaxos_tpu.ops.winner import read_cols


def _shift1(x: jnp.ndarray, fill) -> jnp.ndarray:
    """x shifted right by one row (previous-row view), fill at row 0."""
    return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])


def compress_ack_runs(is_accept: jnp.ndarray, src: jnp.ndarray,
                      inst: jnp.ndarray, ok: jnp.ndarray,
                      ballot: jnp.ndarray | None = None,
                      stride: int = 1):
    """Split ACCEPT rows into maximal stride-``stride`` runs.

    A row starts a new run when the previous row is not an ACCEPT, has
    a different sender or ok flag, is not exactly ``stride`` instances
    later, or (when ``ballot`` is given — Mencius echoes the accept's
    own ballot into its reply, so it is part of the reply row) carries
    a different ballot.

    ``stride`` is a STATIC protocol constant, implicit on the wire:
    MinPaxos/classic drive consecutive slots (stride 1); a Mencius
    replica drives its OWN slots, which stride by R (mencius.go
    instance ownership) — with stride 1 its foreign-accept runs never
    formed and every slot acked as its own row, refilling the inbox
    with (R-1)·p rows per round (round-4 verdict weak #6). Emitter and
    consumer (range_vote_coverage) must agree on the stride.

    Returns (run_start bool[M], run_len i32[M]) where run_len is the
    total run length at EVERY row of the run (callers publish it on the
    start row; other rows become padding).
    """
    m = is_accept.shape[0]
    same_prev = (
        _shift1(is_accept, False)
        & (_shift1(src, jnp.int32(-7)) == src)
        & (_shift1(ok, False) == ok)
        & (_shift1(inst, jnp.int32(-7)) + stride == inst))
    if ballot is not None:
        same_prev = same_prev & (_shift1(ballot, jnp.int32(-7)) == ballot)
    run_start = is_accept & ~same_prev
    rid = jnp.cumsum(run_start.astype(jnp.int32)) - 1
    run_len = jnp.zeros(m + 1, jnp.int32).at[
        jnp.where(is_accept, rid, m)].add(1, mode="drop")
    # each row's run length: one fetch by its run id (ops/winner.py
    # read_cols; an element gather of M rows until PR 36)
    return run_start, read_cols(jnp.clip(rid, 0, m), (run_len,))[0]


def range_vote_coverage(valid: jnp.ndarray, src: jnp.ndarray,
                        inst: jnp.ndarray, count: jnp.ndarray,
                        window_base, window: int, n_replicas: int,
                        stride: int = 1):
    """Per-slot vote coverage from range-ack rows.

    Each valid row acks ``count`` instances starting at ``inst`` and
    spaced ``stride`` apart (stride is the static protocol constant —
    see compress_ack_runs); ranges clip to the resident window
    (partial coverage for ranges straddling a slide — legal: votes are
    facts about slots).

    stride == 1: a per-sender (R, S+1) difference array — +1 at the
    range start, -1 one past its end (column S, the clip ceiling, is
    sliced off after the prefix sum, which is what makes
    end-at-window-edge exact) — then cumsum > 0.

    stride == d > 1: the same difference-array trick in RANK space.
    A stride-d range's covered window-relative slots share one phase
    (rel mod d) and occupy consecutive ranks (rel // d), so per
    (sender, phase) plane the range is contiguous again: diff array
    over (R·d, ranks), cumsum, then gather each slot's
    (sender, rel mod d, rel // d) cell.

    Returns bool[S, R], ready to OR into a votes table.
    """
    s, r = window, n_replicas
    cnt = jnp.maximum(count, 1)  # pre-compression rows carry 0
    src_c = jnp.clip(src, 0, r - 1)
    if stride == 1:
        lo_rel = jnp.clip(inst - window_base, 0, s)
        hi_rel = jnp.clip(inst + cnt - window_base, 0, s)
        vrow = valid & (hi_rel > lo_rel)
        vd = jnp.zeros((r, s + 1), jnp.int32)
        vd = vd.at[jnp.where(vrow, src_c, r),
                   jnp.where(vrow, lo_rel, s)].add(1, mode="drop")
        vd = vd.at[jnp.where(vrow, src_c, r),
                   jnp.where(vrow, hi_rel, s)].add(-1, mode="drop")
        return (jnp.cumsum(vd, axis=1)[:, :s] > 0).T
    d = stride
    nrk = s // d + 2
    rel = inst - window_base
    # first covered candidate at/above the window start ...
    j0 = jnp.where(rel < 0, (-rel + d - 1) // d, 0)  # ceil(-rel / d)
    lo_rel = rel + j0 * d
    phase = jnp.mod(lo_rel, d)
    lo_rank = lo_rel // d
    # ... through the last candidate still below the window end
    rank_hi = jnp.minimum(lo_rank + (cnt - 1 - j0),
                          (s - 1 - phase) // d)
    vrow = valid & (cnt > j0) & (lo_rel < s) & (rank_hi >= lo_rank)
    plane = src_c * d + phase
    np_, nr_ = r * d, nrk + 1
    vd = jnp.zeros((np_, nr_), jnp.int32)
    vd = vd.at[jnp.where(vrow, plane, np_),
               jnp.where(vrow, lo_rank, nrk)].add(1, mode="drop")
    vd = vd.at[jnp.where(vrow, plane, np_),
               jnp.where(vrow, rank_hi + 1, nrk)].add(-1, mode="drop")
    cov = (jnp.cumsum(vd, axis=1)[:, :nrk] > 0).reshape(r, d, nrk)
    rel_ix = jnp.arange(s, dtype=jnp.int32)
    return cov[:, jnp.mod(rel_ix, d), rel_ix // d].T


def pack_vote_bits(cov: jnp.ndarray) -> jnp.ndarray:
    """bool[S, R] -> u16[S] bitmask (bit r = replica r voted).

    Votes/pvotes live as packed u16 per slot — R <= 16 by the ballot
    encoding ((counter << 4) | id, bareminpaxos.go:383-385) — so the
    two densest per-slot arrays cost 2 bytes instead of R bool bytes.
    The bool intermediate here is transient (XLA fuses it); only the
    packed form persists in HBM across steps."""
    r = cov.shape[1]
    w = (jnp.int32(1) << jnp.arange(r, dtype=jnp.int32))[None, :]
    return (cov.astype(jnp.int32) * w).sum(axis=1).astype(jnp.uint16)


def scatter_vote_bits(size: int, idx: jnp.ndarray, src: jnp.ndarray,
                      valid: jnp.ndarray, n_replicas: int) -> jnp.ndarray:
    """OR-delta u16[size]: bit ``src[i]`` set at row ``idx[i]`` for
    every valid i. Safe under duplicates AND multiple senders hitting
    one slot in a batch (a plain scatter-max/add cannot express that):
    scatter booleans into a transient [R, size] plane, then pack."""
    r = n_replicas
    d = jnp.zeros((r, size), bool).at[
        jnp.where(valid, jnp.clip(src, 0, r - 1), r),
        jnp.where(valid, idx, size)].set(True, mode="drop")
    return pack_vote_bits(d.T)
