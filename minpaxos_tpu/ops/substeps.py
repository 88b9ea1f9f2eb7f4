"""Fused multi-tick device steps: k protocol substeps per dispatch.

The TCP runtime's per-tick cost is dominated by the host->device
dispatch floor, not kernel compute (PERF.md round-5 decomposition:
0.3-0.9 ms dispatch x ~3 ticks per serial op). `parallel/sharded.py`
already amortizes that floor k-fold for the fused bench via
``lax.scan``; this module brings the same trick to the real-process
runtime (runtime/replica.py):

* ``scan_ticks`` runs k protocol substeps inside ONE dispatch — the
  real inbox feeds substep 0, the rest step with empty inboxes (their
  work is the follow-up the first substep generated: exec backlog
  drains, catch-up/sweep chunks advance, commits from the first
  substep's acks execute). Per-substep outputs come back STACKED
  ([k, ...] matrices) so the host replays persist/dispatch/reply for
  every substep in order off one device transfer.
* ``pack_outputs`` is the per-tick host-read packing (one outbox
  matrix + one exec matrix + one scalar vector — the round-5
  ~30-reads-to-3 collapse), extended with the scalars the host-side
  fast paths need: ``executed_upto`` (fusion heuristic),
  ``low/high_anchor`` (narrow-view gating) and ``work_pending`` (the
  idle fast path's "may this tick be skipped?" bit).
* ``narrow_view`` / ``merge_view`` carve a compiled-once W-slot
  resident view out of a larger window (``lax.dynamic_slice`` at a
  traced offset), so a server sized ``-window 16384`` can execute
  low-occupancy ticks at small-window cost — the ~4x the dedicated
  W=512 serial cluster measured, without resizing the deployment.

Substep tick accounting: only substep 0 carries ``tick_inc=1``; the
trailing substeps pass 0 so stall/retry/takeover counters stay honest
against wall time (they gate on "ticks of silence", and a fused burst
is one wall tick).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from minpaxos_tpu.models.minpaxos import COMMITTED, MsgBatch

# Scalar-vector layout (one device read per tick; host indexes by
# these names — runtime/replica.py unpacks positionally).
(SCAL_FRONTIER, SCAL_WINDOW_BASE, SCAL_CRT_INST, SCAL_KV_DROPPED,
 SCAL_EXEC_LO, SCAL_EXEC_COUNT, SCAL_LEADER, SCAL_PREPARED,
 SCAL_EXECUTED, SCAL_LOW_ANCHOR, SCAL_HIGH_ANCHOR,
 SCAL_WORK_PENDING) = range(12)
N_SCAL = 12

# positional names for the vector above — the observability layer's
# STATS verb surfaces the whole published vector by name (paxmon,
# OBSERVABILITY.md) without any extra device read
SCAL_NAMES = ("frontier", "window_base", "crt_inst", "kv_dropped",
              "exec_lo", "exec_count", "leader", "prepared", "executed",
              "low_anchor", "high_anchor", "work_pending")
assert len(SCAL_NAMES) == N_SCAL

_BIG = jnp.int32(2 ** 30)


def _anchors(state):
    """(low_anchor, high_anchor, work_pending) for a post-step state.

    ``low_anchor``: the lowest absolute slot the NEXT empty-inbox step
    could read or write (exec cursor, commit frontier, catch-up /
    commit-broadcast cursors, takeover anchor). ``high_anchor``: one
    past the highest (log tip / own-propose cursor). Together they
    bound the narrow resident view. ``work_pending``: whether an
    empty-inbox step would do anything at all — False means the idle
    fast path may skip the dispatch entirely (message arrival always
    forces one).

    Protocol dispatch is structural (MinPaxos-family states carry
    ``leader_id``; Mencius carries ``commit_sent``), resolved at trace
    time.
    """
    exec_edge = state.executed_upto + 1
    frontier = state.committed_upto
    lo = jnp.minimum(exec_edge, frontier + 1)
    backlog = frontier > state.executed_upto
    r = state.peer_commits.shape[0]
    pc = jnp.where(jnp.arange(r) == state.me, _BIG, state.peer_commits)
    pc_min = jnp.min(pc)
    peer_lag = pc_min < frontier
    in_flight = state.crt_inst - 1 > frontier
    if getattr(state, "leader_id", None) is not None:  # minpaxos/classic
        is_leader = state.leader_id == state.me
        serving = is_leader & state.prepared
        lo = jnp.where(serving & peer_lag, jnp.minimum(lo, pc_min + 1), lo)
        hi = state.crt_inst
        behind_gossip = frontier > state.gossip_upto
        pending = (backlog | behind_gossip
                   | (is_leader & (in_flight | ~state.prepared | peer_lag)))
    else:  # mencius: every replica drives its own slots + catch-up
        s = state.status.shape[0]
        lo = jnp.where(peer_lag, jnp.minimum(lo, pc_min + 1), lo)
        lo = jnp.minimum(lo, state.commit_sent + 1)
        lo = jnp.where(state.tk_anchor >= 0,
                       jnp.minimum(lo, state.tk_anchor), lo)
        hi = jnp.maximum(state.crt_inst, state.crt_own)
        # unannounced own commit? The broadcast cursor stops at the
        # first unresolved own slot, so one slot answers the question.
        nxt = state.commit_sent + 1
        nxt = nxt + jnp.mod(state.me - nxt, r)
        rel = nxt - state.window_base
        pending_cb = ((rel >= 0) & (rel < s)
                      & (state.status[jnp.clip(rel, 0, s - 1)] >= COMMITTED))
        pending = backlog | in_flight | peer_lag | pending_cb
    return lo, hi, pending.astype(jnp.int32)


def pack_outputs(state, outbox, execr):
    """Pack everything the host reads per tick into three arrays: one
    [14, M] outbox matrix, one [6, E] exec matrix, one [N_SCAL] scalar
    vector (layout above). Moved here from runtime/replica.py so the
    fused scan can pack per substep."""
    m = outbox.msgs
    # acked is the per-INBOX-row mask ([rows in] <= [rows out] after
    # the kernel appends its sweep/retry rows); zero-pad to outbox
    # length so one matrix carries everything
    ack = outbox.acked.astype(jnp.int32)
    ack = jnp.pad(ack, (0, m.kind.shape[0] - ack.shape[0]))
    out_mat = jnp.stack(
        [getattr(m, c).astype(jnp.int32) for c in MsgBatch._fields]
        + [outbox.dst.astype(jnp.int32), ack])
    exec_mat = jnp.stack([
        execr.val_hi.astype(jnp.int32), execr.val_lo.astype(jnp.int32),
        execr.found.astype(jnp.int32), execr.op.astype(jnp.int32),
        execr.cmd_id.astype(jnp.int32), execr.client_id.astype(jnp.int32)])
    leader = getattr(state, "leader_id", None)
    prepared = getattr(state, "prepared", None)
    low, high, pending = _anchors(state)
    scal = jnp.stack([
        state.committed_upto, state.window_base, state.crt_inst,
        state.kv.dropped.astype(jnp.int32),
        execr.lo.astype(jnp.int32), execr.count.astype(jnp.int32),
        jnp.int32(-1) if leader is None else leader.astype(jnp.int32),
        jnp.int32(1) if prepared is None else prepared.astype(jnp.int32),
        state.executed_upto, low, high, pending,
    ])
    return out_mat, exec_mat, scal


def scan_ticks(cfg, state, inbox, step_impl, k: int):
    """k protocol substeps in one trace: the real inbox feeds substep
    0 (tick_inc=1), substeps 1..k-1 run with empty inboxes
    (tick_inc=0). Returns (state', (out_mats [k, 14, Mout],
    exec_mats [k, 6, E], scals [k, N_SCAL]))."""
    if k == 1:
        state, outbox, execr = step_impl(cfg, state, inbox)
        with jax.named_scope("px.pack"):
            o, e, s = pack_outputs(state, outbox, execr)
            return state, (o[None], e[None], s[None])

    def body(st, x):
        box, inc = x
        st, outbox, execr = step_impl(cfg, st, box, inc)
        with jax.named_scope("px.pack"):
            return st, pack_outputs(st, outbox, execr)

    boxes = jax.tree_util.tree_map(
        lambda col: jnp.concatenate(
            [col[None], jnp.zeros((k - 1,) + col.shape, col.dtype)]),
        inbox)
    incs = jnp.concatenate([jnp.ones(1, jnp.int32),
                            jnp.zeros(k - 1, jnp.int32)])
    return jax.lax.scan(body, state, (boxes, incs))


def _slot_fields(state, window: int) -> tuple[str, ...]:
    """State fields that are per-slot window arrays (the axis the
    narrow view slices). Structural: 1-D leaves of window length at
    the top level of the state NamedTuple (nested KVState and [R]
    vectors don't match)."""
    return tuple(
        name for name, v in state._asdict().items()
        if hasattr(v, "ndim") and v.ndim == 1 and v.shape[0] == window)


def narrow_view(state, off, narrow: int, window: int):
    """Slice a compiled-once ``narrow``-slot resident view out of a
    ``window``-slot state at traced offset ``off`` (absolute base
    window_base + off). Caller guarantees every live slot and every
    slot the step could touch lies inside the view (runtime/replica.py
    derives the guarantee from low/high_anchor + inbox bounds) and
    runs the view with ``slide_window=False`` so the bases stay
    aligned."""
    fields = _slot_fields(state, window)
    upd = {f: jax.lax.dynamic_slice_in_dim(getattr(state, f), off, narrow)
           for f in fields}
    upd["window_base"] = state.window_base + off
    return state._replace(**upd), fields


def merge_view(full, view, off, fields):
    """Write a stepped narrow view back into the full-window state:
    slot arrays via dynamic_update_slice at ``off``; every non-slot
    field (scalars, [R] vectors, the KV table) adopts the view's value.
    window_base keeps the FULL state's value — the view ran with the
    slide disabled, so its shifted base is a view artifact."""
    upd = {f: jax.lax.dynamic_update_slice_in_dim(
        getattr(full, f), getattr(view, f), off, 0) for f in fields}
    for name in view._fields:
        if name not in upd and name != "window_base":
            upd[name] = getattr(view, name)
    return full._replace(**upd)
