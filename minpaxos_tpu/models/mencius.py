"""Mencius — rotating-ownership multi-leader consensus, third protocol.

Counterpart of reference src/mencius/mencius.go (897 LoC; compiled but
never wired into the reference's server binary, server.go:62-65). Core
ideas, mapped to the reference:

* **Rotating ownership** (mencius.go:99, :431-432): replica r owns log
  slots i with i % N == r and serves proposals directly into them —
  every replica is a leader for its own slots; there is no election.
* **SKIP / cede** (:276-304, :449-457, delayed batching :498-501,
  :592-599): a replica that receives an Accept for a slot ahead of its
  own cursor cedes its intervening owned slots as committed no-ops and
  broadcasts ONE Skip row covering the whole range — the reference's
  delayed-skip timer batches skips across events; here a protocol step
  IS the batch, so each step emits at most one Skip row per replica.
* **Explicit commit broadcast** (bcastCommit :606-650): an owner that
  reaches majority on its slot broadcasts COMMIT rows (chunked per
  step) — peers cannot count votes (acks flow owner-only), so commits
  must travel explicitly, like classic paxos.
* **Blocking frontier** (updateBlocking :744-797): the executable
  prefix advances only through slots that are committed or skipped,
  across ALL owners' interleaved slots — here ``commit_frontier`` over
  the merged window.
* **forceCommit takeover** (:244-257, :878-897): when the frontier
  stalls on a dead owner's slot, that owner's successor ((o+1) % N)
  runs per-instance phase 1 (PREPARE_INST at a takeover ballot >
  ballot 0 that ownership implies) over the blocked range and no-op
  fills slots a majority reports empty, majority-audited per slot (the
  same pvotes machinery as models/minpaxos.py step 7d/7e). The
  successor also REVOKES a range of the silent owner's future turns
  (section 7r; Mao et al., OSDI 2008), the reference's NB_INST_TO_SKIP
  bulk skip done by Paxos: a range promise, no-ops accepted by a
  majority above every value it holds, renewed while the owner stays
  silent; its values that every answerer holds commit with them.
* **Conflict-aware out-of-order execution** (:799-876): committed
  slots above the blocking frontier execute early when every earlier
  conflicting slot (same key, >= one PUT — state.go:55-62) inside the
  window is already committed; the sorted-segment scan that proves
  non-conflict shares its machinery with the KV engine's
  sequential-equivalence pass (ops/kvstore.py).

Ballots: slot ownership IS ballot 0 (only the owner may propose there
— the asymmetry that lets an owner accept its own slot without a
prepare). Takeover ballots are make_ballot(counter, successor) > 0,
driven through classic per-instance phase 1.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from minpaxos_tpu.models.minpaxos import (
    ACCEPTED,
    COMMITTED,
    EXECUTED,
    NO_BALLOT,
    NONE,
    ExecResult,
    MinPaxosConfig,
    MsgBatch,
    Outbox,
    SLOT_FIELDS,
    _concat_rows,
    _rel,
    make_ballot,
)
from minpaxos_tpu.ops.ackruns import (
    compress_ack_runs,
    pack_vote_bits,
    range_vote_coverage,
    scatter_vote_bits,
)
from minpaxos_tpu.ops.kvstore import (
    KVState,
    kv_apply_batch,
    kv_apply_batch_shared,
    kv_init,
)
from minpaxos_tpu.ops.rankselect import rank_select
from minpaxos_tpu.ops.scan import commit_frontier, segmented_scan_max
from minpaxos_tpu.ops.sections import Sections
from minpaxos_tpu.ops.winner import (
    gather_cols,
    gather_const,
    read_cols,
    slot_winner,
)
from minpaxos_tpu.wire.messages import MsgKind, Op


#: no revoked range (an rv_lo / rv_from beyond any slot)
REVOKE_NONE = 2 ** 30
#: ``client_id`` of the revocation's own answers and announcements (no
#: client has it): a PREPARE_INST_REPLY that answers a revocation's
#: phase 1 (``key_hi`` 0) or phase 2 (``key_hi`` 1), and a COMMIT that
#: announces what a revocation chose. Its requests are PREPARE_INST
#: rows with ``op`` (the wire's ``to_infinity``) 1 (phase 1: promise
#: the range from ``inst``) or 2 (phase 2: accept no-ops from ``inst``
#: to the promised range's end)
REVOKE_MARK = -2


def revoke_turns(cfg: MinPaxosConfig) -> int:
    """How many of a silent owner's turns one revocation takes: a
    window's worth, ``cfg.window`` turns = R windows of the merged log,
    so a range always reaches past every replica's window. Upstream
    skips NB_INST_TO_SKIP = 100,000 at once (mencius.go); here a range
    is renewed while its owner stays silent (section 7r), and an owner
    that returns resumes after R windows, not after 100,000 turns."""
    return cfg.window


class MenciusState(NamedTuple):
    """One Mencius replica's device state. Field names shared with
    ReplicaState where the host wrappers read them (committed_upto,
    executed_upto, crt_inst, window_base, kv...)."""

    # log window [S]; status/op u8 and votes/pvotes packed u16, as in
    # ReplicaState (the window arrays are the step's dominant HBM
    # traffic)
    ballot: jnp.ndarray  # i32: 0 = owner ballot, >0 takeover
    status: jnp.ndarray  # u8
    op: jnp.ndarray  # u8
    key_hi: jnp.ndarray
    key_lo: jnp.ndarray
    val_hi: jnp.ndarray
    val_lo: jnp.ndarray
    cmd_id: jnp.ndarray
    client_id: jnp.ndarray
    votes: jnp.ndarray  # u16[S] acks for my owned slots
    pvotes: jnp.ndarray  # u16[S] takeover phase-1 answers
    executed: jnp.ndarray  # bool[S] (out-of-order exec tracking)
    # scalars
    me: jnp.ndarray
    window_base: jnp.ndarray
    crt_own: jnp.ndarray  # next owned slot to propose into (== me mod R)
    crt_inst: jnp.ndarray  # max slot seen + 1 (any owner)
    committed_upto: jnp.ndarray  # global blocking frontier
    executed_upto: jnp.ndarray  # contiguous executed prefix
    commit_sent: jnp.ndarray  # own slots <= this had commits broadcast
    takeover_ballot: jnp.ndarray  # my current takeover ballot (or -1)
    tk_anchor: jnp.ndarray  # first slot of my latest takeover span (-1)
    max_recv_ballot: jnp.ndarray
    tick: jnp.ndarray
    stall_ticks: jnp.ndarray
    peer_commits: jnp.ndarray  # i32[R] last frontier reported per peer
    # revocation (section 7r, ``px.revoke``), per OWNER o, as an
    # acceptor: the range [rp_lo, rp_hi] of o's turns it promised at
    # rp_bal (phase 2 is accepted at that ballot alone), the turns
    # [rv_lo, rv_hi] accepted as
    # no-ops at rv_bal, of which [rv_lo, rv_cm] are known chosen; and,
    # as the revoker, the run [rv_from, rv_vend] of o's values that the
    # revocation found held by all its answerers and committed
    rp_lo: jnp.ndarray  # i32[R], REVOKE_NONE when there is no range
    rp_hi: jnp.ndarray  # i32[R], -1
    rp_bal: jnp.ndarray  # i32[R], NO_BALLOT
    rv_lo: jnp.ndarray  # i32[R], REVOKE_NONE
    rv_hi: jnp.ndarray  # i32[R], -1
    rv_cm: jnp.ndarray  # i32[R], -1
    rv_bal: jnp.ndarray  # i32[R], NO_BALLOT
    rv_from: jnp.ndarray  # i32[R], REVOKE_NONE
    rv_vend: jnp.ndarray  # i32[R], -1
    heard: jnp.ndarray  # i32[R] my tick when a row of each peer last came
    # i32[R] steps that brought rows of other peers and none of this
    # one since its last: how long an owner has been silent, counted in
    # its peers' traffic, not in ticks (a live owner of the served path
    # ticks far less often than its successor wakes)
    quiet: jnp.ndarray
    # my revocation in flight: its first turn (-1: none), ballot, phase
    # (1 prepare, 2 accept), the first turn it proposes as no-ops, the
    # answerers of the phase, and what the phase-1 answers reported
    rk_at: jnp.ndarray
    rk_bal: jnp.ndarray
    rk_phase: jnp.ndarray
    rk_t: jnp.ndarray
    rk_acks: jnp.ndarray  # u16
    rk_low: jnp.ndarray  # highest start of an answerer's run of values
    rk_top: jnp.ndarray  # highest value an answerer holds
    rk_topmin: jnp.ndarray  # lowest such top: all hold [rk_low, rk_topmin]
    kv: KVState


def init_mencius(cfg: MinPaxosConfig, me: int) -> MenciusState:
    s, r = cfg.window, cfg.n_replicas

    def zi():
        return jnp.zeros(s, dtype=jnp.int32)

    return MenciusState(
        ballot=jnp.full(s, NO_BALLOT, dtype=jnp.int32),
        status=jnp.zeros(s, dtype=jnp.uint8),
        op=jnp.zeros(s, dtype=jnp.uint8),
        key_hi=zi(),
        key_lo=zi(),
        val_hi=zi(),
        val_lo=zi(),
        cmd_id=zi(),
        client_id=zi(),
        votes=jnp.zeros(s, dtype=jnp.uint16),
        pvotes=jnp.zeros(s, dtype=jnp.uint16),
        executed=jnp.zeros(s, dtype=bool),
        me=jnp.int32(me),
        window_base=jnp.int32(0),
        crt_own=jnp.int32(me),
        crt_inst=jnp.int32(0),
        committed_upto=jnp.int32(-1),
        executed_upto=jnp.int32(-1),
        commit_sent=jnp.int32(-1),
        takeover_ballot=jnp.int32(NO_BALLOT),
        tk_anchor=jnp.int32(-1),
        max_recv_ballot=jnp.int32(0),
        tick=jnp.int32(0),
        stall_ticks=jnp.int32(0),
        peer_commits=jnp.full(r, -1, dtype=jnp.int32),
        rp_lo=jnp.full(r, REVOKE_NONE, dtype=jnp.int32),
        rp_hi=jnp.full(r, -1, dtype=jnp.int32),
        rp_bal=jnp.full(r, NO_BALLOT, dtype=jnp.int32),
        rv_lo=jnp.full(r, REVOKE_NONE, dtype=jnp.int32),
        rv_hi=jnp.full(r, -1, dtype=jnp.int32),
        rv_cm=jnp.full(r, -1, dtype=jnp.int32),
        rv_bal=jnp.full(r, NO_BALLOT, dtype=jnp.int32),
        rv_from=jnp.full(r, REVOKE_NONE, dtype=jnp.int32),
        rv_vend=jnp.full(r, -1, dtype=jnp.int32),
        heard=jnp.zeros(r, dtype=jnp.int32),
        quiet=jnp.zeros(r, dtype=jnp.int32),
        rk_at=jnp.int32(-1),
        rk_bal=jnp.int32(NO_BALLOT),
        rk_phase=jnp.int32(0),
        rk_t=jnp.int32(-1),
        rk_acks=jnp.uint16(0),
        rk_low=jnp.int32(-1),
        rk_top=jnp.int32(-1),
        rk_topmin=jnp.int32(-1),
        kv=kv_init(cfg.kv_pow2),
    )


def _by_owner(arr, owner):
    """``arr[owner]`` for an [R] array and an array of owners in range:
    R selects, no gather."""
    out = jnp.zeros(owner.shape, arr.dtype)
    for r in range(arr.shape[0]):
        out = jnp.where(owner == r, arr[r], out)
    return out


def _owner_max(owner_of, mask, vals, n_owners: int, empty):
    """The largest ``vals`` over each owner's window slots where
    ``mask``: [R], ``empty`` for an owner with none."""
    hit = (owner_of[None, :] == jnp.arange(n_owners)[:, None]) & mask[None, :]
    return jnp.max(jnp.where(hit, vals[None, :], empty), axis=1)


def _merge_range(lo0, hi0, lo, hi, base, stride: int):
    """An owner's range [lo0, hi0] joined with [lo, hi] where the two
    touch (one owner's turns are ``stride`` apart); a range that lies
    wholly below the window (``base``), or none, is replaced. Returns
    (lo', hi', ok); ok is false where the two lie apart and nothing
    changes."""
    stale = hi0 < base
    ok = stale | ((lo <= hi0 + stride) & (hi >= lo0 - stride))
    return (jnp.where(stale, lo, jnp.minimum(lo0, lo)),
            jnp.where(stale, hi, jnp.maximum(hi0, hi)), ok)


def _heard_lately(cfg: MinPaxosConfig, states):
    """Whether a replica heard any peer in the last ``cfg.noop_delay``
    ticks, from its state as it stands (leading axes kept)."""
    r = states.heard.shape[-1]
    peers = jnp.where(jnp.arange(r) == states.me[..., None],
                      jnp.int32(-(2 ** 30)), states.heard)
    # paxlint: disable=wall-honesty -- an elapsed time, no update
    return states.tick - peers.max(axis=-1) < cfg.noop_delay


def mencius_step_impl(
    cfg: MinPaxosConfig, state: MenciusState, inbox: MsgBatch,
    tick_inc=1, steady: bool = False, revoke: bool = True,
) -> tuple[MenciusState, Outbox, ExecResult]:
    """Advance one Mencius replica by one message batch (pure; vmapped
    by the cluster wrapper below).

    ``tick_inc``: wall ticks this step represents (0 for the trailing
    substeps of a fused burst — see models/minpaxos.py
    replica_step_impl); keeps the stall/takeover counters wall-honest
    under the TCP runtime's multi-substep dispatches.

    ``steady`` (static): trace the step without its recovery sections
    (those ``recovery_gates`` names), as models/minpaxos.py
    ``replica_step_impl`` does.

    ``revoke`` (static): whether the step carries the revocation of a
    silent owner's turns at all: sections 7r, 8r and 10r, and section
    10's start of one. A served replica's does not
    (``mencius_served_step_impl``), and so none of its peers begins one."""
    with Sections() as sec:
        return _mencius_step_sections(sec, cfg, state, inbox, tick_inc,
                                      steady, revoke)


#: the step of a served replica (runtime/replica.py). Its stable store
#: keeps no revocation's accepted range: a replica restarted from it
#: would forget a range of no-ops it accepted and could then accept the
#: returning owner's value in a turn where the no-op was chosen. So a
#: served replica steps without the revocation (sections 7r, 8r, 10r:
#: with no peer that begins one there is nothing for them to do), and
#: section 10's sweep is its takeover, as before section 7r. One
#: module-level object: every replica of a process shares
#: ``_packed_step``'s compiled variants.
mencius_served_step_impl = functools.partial(mencius_step_impl,
                                             revoke=False)


#: the takeover's sections and their gates, as models/minpaxos.py
#: ``replica_step_impl.recovery_gates`` has the leader's: each a
#: superset of "some replica's section would write something".
#:
#: * 7a answers PREPARE_INST rows and 7b adopts from
#:   PREPARE_INST_REPLY rows only.
#: * 10 sweeps, fills, re-drives and begins a revocation only where
#:   ``stall_ticks >= cfg.noop_delay`` (the successor's threshold, the
#:   lowest), read after section 8 has set the counter to its old value
#:   plus ``tick_inc`` (1 wherever whole rounds are stepped) or to 0,
#:   and only at a replica that heard a peer lately (``heard`` as it
#:   stood before the step, so a dead replica of the pod, which hears
#:   nobody, keeps it shut). The reset that closes an episode is not
#:   part of it and always runs.
#:
#: A revocation's rows (7r) are PREPARE_INST and PREPARE_INST_REPLY
#: rows and open the first gate; what it chose (8r) and its renewals
#: and announcements (10r) are in every step.
#:
#: SKIP rows (section 4) are the normal path of an uneven load, not
#: recovery: a steady step keeps them.
mencius_step_impl.recovery_gates = {
    "px.takeover_phase1": lambda cfg, states, present: (
        present(MsgKind.PREPARE_INST) | present(MsgKind.PREPARE_INST_REPLY)),
    "px.takeover": lambda cfg, states, present: (
        # paxlint: disable=wall-honesty -- a bound on it, no update
        (states.stall_ticks + 1 >= cfg.noop_delay)
        & _heard_lately(cfg, states)).any(),
}


def _mencius_step_sections(sec, cfg, state, inbox, tick_inc, steady,
                           revoke):
    """``mencius_step_impl``'s body; ``sec(name)`` opens the ``px.*``
    scope of the section that follows (ops/sections.py). Sections that
    MinPaxos has too carry its names."""
    S, R = cfg.window, cfg.n_replicas
    M = inbox.kind.shape[0]
    # flexible quorums (models/minpaxos.py config field note): the
    # takeover phase-1 audits take q1, ACCEPT-vote commit scans q2 —
    # both cfg.majority by default
    quorum1 = cfg.quorum1
    quorum2 = cfg.quorum2
    me = state.me
    k = inbox.kind
    idx = jnp.arange(S, dtype=jnp.int32)
    idx_abs = state.window_base + idx
    owner_of = jnp.mod(idx_abs, R)
    own_mask = owner_of == me

    heard_lately = _heard_lately(cfg, state)
    quiet0 = state.quiet

    is_propose = k == int(MsgKind.PROPOSE)
    is_accept = k == int(MsgKind.ACCEPT)
    is_areply = k == int(MsgKind.ACCEPT_REPLY)
    is_skip = k == int(MsgKind.SKIP)
    is_commit = k == int(MsgKind.COMMIT)
    is_pinst = k == int(MsgKind.PREPARE_INST)
    is_pir = k == int(MsgKind.PREPARE_INST_REPLY)

    out = MsgBatch.empty(M)
    dst = jnp.full(M, -1, jnp.int32)

    sec("px.propose")
    # ---- 1. PROPOSE into my owned slots (handlePropose :429-447) ----
    # the cursor never points at a turn that is no longer mine: past
    # own slots a takeover already filled, and, once it reaches them,
    # past the turns a revocation took (section 7r). Proposing into
    # either would overwrite a chosen no-op
    taken = jnp.max(jnp.where(own_mask & (idx_abs >= state.crt_own)
                              & (state.status != NONE), idx_abs, -1))
    my_lo, my_hi = _by_owner(state.rv_lo, me), _by_owner(state.rv_hi, me)
    in_rv = (my_lo <= state.crt_own) & (state.crt_own <= my_hi)
    past = jnp.maximum(taken, jnp.where(in_rv, my_hi, -1))
    state = state._replace(crt_own=jnp.where(past >= state.crt_own,
                                             past + R, state.crt_own))
    csum_p = jnp.cumsum(is_propose.astype(jnp.int32))
    prefix = csum_p - 1
    slots_p = state.crt_own + R * prefix
    rel_p = slots_p - state.window_base
    fits = (is_propose & (rel_p >= 0) & (rel_p < S)
            & ((slots_p < my_lo) | (slots_p > my_hi)))
    n_fit = fits.sum(dtype=jnp.int32)
    me_bit = (jnp.int32(1) << me).astype(jnp.uint16)
    # one winning row per slot + dense gathers instead of per-column
    # scatters (ops/winner.py rationale) — and the winner itself is
    # recovered WITHOUT a scatter (PR 11): propose targets stride R
    # from crt_own, so window slot s takes propose rank
    # q = (abs - crt_own) / R, and rank q's row is the first whose
    # propose prefix count reaches q + 1: ops/rankselect.py, vector
    # compares. Until PR 29 a jnp.searchsorted per slot, whose 11
    # dependent element gathers were the top device op of
    # mencius64k_steady (35.4 ms of a 257 ms round; ledger, PR 28)
    off_p = idx_abs - state.crt_own
    rank_p = off_p // R
    hit_p = ((off_p >= 0) & (jnp.mod(off_p, R) == 0)
             & (rank_p < n_fit))
    win_p = rank_select(csum_p, jnp.clip(rank_p, 0, M - 1) + 1)
    win_p = jnp.where(hit_p, win_p, -1)
    # a slot's winning row is fetched ONCE a pass, all its columns
    # together (ops/winner.py gather_cols; until PR 34 seven or eight
    # element gathers a section); sections 1, 2, 6 and 7b

    def slot_write(st, win, hit, fields=SLOT_FIELDS):
        """The winning rows' ``fields`` into ``st``'s slots."""
        return st._replace(**dict(zip(fields, gather_cols(
            win, hit, [getattr(inbox, f) for f in fields],
            [getattr(st, f) for f in fields]))))

    # a proposal's ballot is not its row's
    state = slot_write(state, win_p, hit_p, SLOT_FIELDS[1:])._replace(
        ballot=gather_const(hit_p, 0, state.ballot),
        status=gather_const(hit_p, ACCEPTED, state.status),
        votes=gather_const(hit_p, me_bit, state.votes),
    )
    n_prop = n_fit
    state = state._replace(
        crt_own=state.crt_own + R * n_prop,
        crt_inst=jnp.maximum(state.crt_inst,
                             state.crt_own + R * n_prop - R + 1),
    )
    # broadcast ACCEPT rows; rejected (window-full) proposals bounce
    reject = is_propose & ~fits
    out = out._replace(
        kind=jnp.where(fits, int(MsgKind.ACCEPT),
                       jnp.where(reject, int(MsgKind.PROPOSE_REPLY),
                                 out.kind)),
        src=jnp.where(is_propose, me, out.src),
        inst=jnp.where(fits, slots_p, out.inst),
        ballot=jnp.where(fits, 0, jnp.where(reject, me, out.ballot)),
        op=jnp.where(fits, inbox.op, jnp.where(reject, 0, out.op)),
        key_hi=jnp.where(is_propose, inbox.key_hi, out.key_hi),
        key_lo=jnp.where(is_propose, inbox.key_lo, out.key_lo),
        val_hi=jnp.where(is_propose, inbox.val_hi, out.val_hi),
        val_lo=jnp.where(is_propose, inbox.val_lo, out.val_lo),
        cmd_id=jnp.where(is_propose, inbox.cmd_id, out.cmd_id),
        client_id=jnp.where(is_propose, inbox.client_id, out.client_id),
        last_committed=jnp.where(fits, state.committed_upto,
                                 out.last_committed),
    )
    dst = jnp.where(fits, -1, jnp.where(reject, -2, dst))

    sec("px.accept")
    # ---- 2. ACCEPT from other owners (handleAccept :503-590) ----
    rel_a, in_win_a = _rel(state, inbox.inst, S)
    rel_a_safe = jnp.minimum(rel_a, S - 1)
    # only the slot's owner (or a takeover ballot > current) may write
    owner_ok = jnp.mod(inbox.inst, R) == inbox.src
    # a row's slot is read ONCE per version of STATE, all the columns
    # a section tests together (ops/winner.py read_cols; until PR 36 an
    # element gather a column, here and in section 11)
    ballot_a, status_a = read_cols(rel_a_safe, (state.ballot, state.status))
    # a revocation (section 7r): the owner's turns in a range of no-ops
    # I accepted take no value at any ballot (a no-op may have been
    # chosen there: every later proposal must be that no-op). Its
    # phase-1 promise alone blocks nothing: phase 2 is accepted only by
    # an acceptor that holds no value of the range, so a value accepted
    # after the promise and a chosen range never meet, and a revocation
    # abandoned after its phase 1 leaves a live owner unhindered
    o_a = jnp.mod(inbox.inst, R)
    revoked_a = ((inbox.inst >= _by_owner(state.rv_lo, o_a))
                 & (inbox.inst <= _by_owner(state.rv_hi, o_a))
                 & (inbox.op != int(Op.NONE)))
    acc_pre = (
        is_accept & in_win_a
        & (owner_ok | (inbox.ballot > 0))
        & (inbox.ballot >= ballot_a)
        & (status_a < COMMITTED)
        & ~revoked_a
    )
    ab_max = jnp.full(S + 1, NO_BALLOT, jnp.int32).at[
        jnp.where(acc_pre, rel_a, S)].max(inbox.ballot, mode="drop")
    ab_max_a, = read_cols(rel_a_safe, (ab_max[:S],))
    acc_ok = acc_pre & (inbox.ballot == ab_max_a)
    win_a, hit_a = slot_winner(S, rel_a, acc_ok)
    state = slot_write(state, win_a, hit_a)._replace(
        status=gather_const(hit_a, ACCEPTED, state.status),
        # crt_inst ("max slot seen + 1, any owner") advances from ANY
        # owner-plausible ACCEPT — including beyond-window ones a
        # revived laggard can't apply. Without this its in_flight stays
        # False and the takeover sweep below never fires, wedging its
        # own frontier (and its clients) forever while the live cluster
        # runs ahead.
        crt_inst=jnp.maximum(
            state.crt_inst,
            jnp.max(jnp.where(is_accept & (owner_ok | (inbox.ballot > 0)),
                              inbox.inst, -1)) + 1),
        max_recv_ballot=jnp.maximum(
            state.max_recv_ballot,
            jnp.max(jnp.where(is_accept, inbox.ballot, 0))),
    )
    # ack to the sender; a committed slot re-acks ONLY if the accept
    # carries the identical decided content — an owner's stale value-
    # ACCEPT arriving after a takeover committed a no-op here must NACK,
    # or the owner could assemble a majority for a conflicting value
    # (vote-for-the-decided-value rule, as in models/minpaxos.py)
    # after the slot write: status and the seven payload columns
    status_a, *slot_a = read_cols(
        rel_a_safe,
        (state.status,) + tuple(getattr(state, f) for f in SLOT_FIELDS[1:]))
    slot_a = dict(zip(SLOT_FIELDS[1:], slot_a))
    acc_dup_ok = (
        is_accept & in_win_a
        & (status_a >= COMMITTED)
        & (slot_a["op"] == inbox.op)
        & (slot_a["key_hi"] == inbox.key_hi)
        & (slot_a["key_lo"] == inbox.key_lo)
        & (slot_a["val_hi"] == inbox.val_hi)
        & (slot_a["val_lo"] == inbox.val_lo)
        & (slot_a["cmd_id"] == inbox.cmd_id)
        & (slot_a["client_id"] == inbox.client_id)
    )
    # run-length compressed acks (same scheme as models/minpaxos.py
    # step 2; cmd_id = run length -> wire `count`) at the protocol's
    # OWNER STRIDE R: a driving replica's slots stride by R (rotating
    # ownership), so its accept bursts arrive as stride-R sequences —
    # under stride 1 those runs never formed, every foreign accept
    # acked as its own row, and the (R-1)·p per-round ack rows refilled
    # the inbox the compression was built to relieve (round-4 verdict
    # weak #6). Takeover re-drives stride by R too (the dead owner's
    # slots). The echoed ballot joins the run key — unlike MinPaxos's
    # constant default_ballot reply, Mencius echoes the accept's own
    # ballot, which can vary across one inbox.
    ack_ok_row = acc_ok | acc_dup_ok
    run_start, run_len = compress_ack_runs(
        is_accept, inbox.src, inbox.inst, ack_ok_row, ballot=inbox.ballot,
        stride=R)
    out = out._replace(
        kind=jnp.where(is_accept,
                       jnp.where(run_start, int(MsgKind.ACCEPT_REPLY), 0),
                       out.kind),
        src=jnp.where(is_accept, me, out.src),
        inst=jnp.where(is_accept, inbox.inst, out.inst),
        ballot=jnp.where(is_accept, inbox.ballot, out.ballot),
        op=jnp.where(is_accept, ack_ok_row.astype(jnp.int32), out.op),
        cmd_id=jnp.where(is_accept, run_len, out.cmd_id),
        last_committed=jnp.where(is_accept, state.committed_upto,
                                 out.last_committed),
    )
    dst = jnp.where(is_accept, inbox.src, dst)

    sec("px.skip_cede")
    # ---- 3. skip-cede (handleAccept's skip side, :520-556) ----
    # Accepts for slots ahead of my cursor mean peers are running ahead
    # of me: cede my untouched owned slots below the horizon as
    # committed no-ops and tell everyone in ONE Skip row. (The
    # reference batches skips with a 50ms timer + MAX_SKIPS_WAITING=20;
    # one step = one batch here.)
    horizon = jnp.maximum(
        jnp.max(jnp.where(is_accept & acc_ok, inbox.inst, -1)) + 1,
        state.committed_upto + 1)
    cede = (own_mask & (idx_abs >= state.crt_own) & (idx_abs < horizon)
            & (state.status == NONE))
    any_cede = cede.any()
    state = state._replace(
        status=jnp.where(cede, COMMITTED, state.status),
        ballot=jnp.where(cede, 0, state.ballot),
        op=jnp.where(cede, int(Op.NONE), state.op),
        cmd_id=jnp.where(cede, 0, state.cmd_id),
        client_id=jnp.where(cede, -1, state.client_id),
        crt_own=jnp.where(
            any_cede,
            # first owned slot >= horizon
            horizon + jnp.mod(me - horizon, R),
            state.crt_own),
    )
    skip_row = MsgBatch.empty(1)._replace(
        kind=jnp.where(any_cede, int(MsgKind.SKIP), 0)[None].astype(jnp.int32),
        src=jnp.full(1, me, jnp.int32),
        inst=jnp.maximum(state.crt_own - R, 0)[None],  # cede end (own)
        ballot=jnp.zeros(1, jnp.int32),
        # last_committed carries cede start (wire start_inst)
        last_committed=jnp.maximum(
            jnp.min(jnp.where(cede, idx_abs, jnp.int32(2 ** 30))), 0)[None],
    )

    sec("px.skip_rows")
    # ---- 4. SKIP rows from peers (handleSkip :449-501) ----
    # Mark src's owned slots in [start, end] as committed no-ops.
    # Safe against value loss: only the owner proposes VALUES at
    # ballot 0, and an owner never cedes a slot it proposed into, so a
    # skip range can only cover slots whose sole possible content is a
    # no-op (status guard below keeps locally-known content anyway).
    skip_src = jnp.clip(inbox.src, 0, R - 1)
    # per-owner min start / max end across skip rows this batch
    starts = jnp.full(R, jnp.int32(2 ** 30)).at[
        jnp.where(is_skip, skip_src, R)].min(inbox.last_committed,
                                             mode="drop")
    ends = jnp.full(R, jnp.int32(-1)).at[
        jnp.where(is_skip, skip_src, R)].max(inbox.inst, mode="drop")
    skipped = ((idx_abs >= starts[owner_of]) & (idx_abs <= ends[owner_of])
               & (state.status < COMMITTED))
    state = state._replace(
        status=jnp.where(skipped, COMMITTED, state.status),
        ballot=jnp.where(skipped, 0, state.ballot),
        op=jnp.where(skipped, int(Op.NONE), state.op),
        cmd_id=jnp.where(skipped, 0, state.cmd_id),
        client_id=jnp.where(skipped, -1, state.client_id),
        crt_inst=jnp.maximum(state.crt_inst,
                             jnp.max(jnp.where(is_skip, inbox.inst, -1)) + 1),
    )

    sec("px.vote_count")
    # ---- 5. ACCEPT_REPLY vote counting (handleAcceptReply :692-742) --
    # One reply row acks [inst, inst + count) (run-length compression;
    # count in cmd_id). Ranges expand to per-slot coverage via a
    # per-sender difference array + prefix sum, then gate on the slots
    # I'm DRIVING: my owned slots (ballot 0) and takeover slots whose
    # current ballot carries my id in its low bits (make_ballot(counter,
    # me) — successor-driven slots are not owned). The per-slot gate is
    # what keeps a stale ack from ever counting toward a slot another
    # replica is driving.
    ar_ok = is_areply & (inbox.op > 0)
    vote_cov = range_vote_coverage(ar_ok, inbox.src, inbox.inst,
                                   inbox.cmd_id, state.window_base, S, R,
                                   stride=R)
    drv_slot = own_mask | (
        (state.ballot > 0) & (jnp.mod(state.ballot, 16) == me))
    # peer frontier tracking (the minpaxos peer_commits scheme): every
    # accept/ack/commit row carries its SENDER's committed_upto in
    # last_committed. Adopt the batch-max report per peer rather than
    # a running max so a crash-revived peer's LOWER report un-pins
    # catch-up (reports are TCP-ordered within one process lifetime).
    # ANY row of a peer says it is there (a takeover's phase-1 answer
    # among them: the revocation waits to see one, section 10)
    row_from = ((jnp.arange(R)[:, None] == inbox.src[None, :])
                & (k != 0)[None, :]).any(axis=1)
    rep_row = (is_accept | is_areply | is_commit) & (inbox.src >= 0)
    rep_src = jnp.where(rep_row, jnp.clip(inbox.src, 0, R - 1), R)
    pc_seen = jnp.full(R + 1, jnp.int32(-(2 ** 30))).at[rep_src].max(
        inbox.last_committed)
    replied = pc_seen[:R] > -(2 ** 30)
    state = state._replace(
        votes=state.votes | pack_vote_bits(
            vote_cov & drv_slot[:, None]),
        peer_commits=jnp.where(replied, pc_seen[:R], state.peer_commits),
        heard=jnp.where(row_from, state.tick, state.heard),
        quiet=jnp.where(row_from, 0,
                        state.quiet + row_from.any().astype(jnp.int32)))

    sec("px.commit_rows")
    # ---- 6. COMMIT rows (explicit commit transfer, bcastCommit) ----
    rel_c, in_win_c = _rel(state, inbox.inst, S)
    is_revoked = is_commit & (inbox.client_id == REVOKE_MARK)
    com_ok = is_commit & in_win_c & ~is_revoked
    win_c, hit_c = slot_winner(S, rel_c, com_ok)
    state = slot_write(state, win_c, hit_c)._replace(
        status=jnp.where(hit_c, jnp.maximum(state.status, COMMITTED),
                         state.status),
        # any COMMIT row advances crt_inst by both its inst and its
        # piggybacked sender frontier (last_committed): a healing
        # laggard otherwise thinks the log ends at each served chunk,
        # in_flight drops, and its takeover sweep stops one chunk in
        # (a revocation's announcement is no proposal: it leaves
        # crt_inst alone, or idle owners' turns would look in flight)
        crt_inst=jnp.maximum(
            state.crt_inst,
            jnp.max(jnp.where(
                is_commit & ~is_revoked,
                jnp.maximum(inbox.inst, inbox.last_committed), -1)) + 1),
    )

    # the revocation's helpers (sections 7r, 8r, 10r)
    L = revoke_turns(cfg)
    # the takeover sweep's span (section 10) in whole turns of R slots
    sweep_span = R * -(-cfg.recovery_rows // R)

    def held_run(st):
        """Per owner: the highest window slot that holds one of its
        values (-1: none) and where the unbroken run of its held turns
        that ends there begins."""
        held = (st.status >= ACCEPTED) & (st.op != int(Op.NONE))
        top = _owner_max(owner_of, held, idx_abs, R, -1)
        below = ~held & (idx_abs < _by_owner(top, owner_of))
        gap = _owner_max(owner_of, below, idx_abs, R, -1)
        return top, jnp.where(gap >= 0, gap + R, st.window_base)

    def begin_revoke(st, go, owner, lo, bal):
        """Start my revocation of ``owner``'s turns from ``lo`` at
        ``bal`` where ``go`` and the range touches any I promised: my
        own phase-1 answer is in it at once."""
        top, low = held_run(st)
        mp_lo, mp_hi, mp_ok = _merge_range(st.rp_lo, st.rp_hi, lo,
                                           lo + R * (L - 1), st.window_base,
                                           R)
        at = (jnp.arange(R) == owner) & go & mp_ok
        go = at.any()
        return st._replace(
            rp_lo=jnp.where(at, mp_lo, st.rp_lo),
            rp_hi=jnp.where(at, mp_hi, st.rp_hi),
            rp_bal=jnp.where(at, jnp.maximum(st.rp_bal, bal), st.rp_bal),
            rk_at=jnp.where(go, lo, st.rk_at),
            rk_bal=jnp.where(go, bal, st.rk_bal),
            rk_phase=jnp.where(go, 1, st.rk_phase),
            rk_acks=jnp.where(go, me_bit, st.rk_acks),
            rk_low=jnp.where(go, _by_owner(low, owner), st.rk_low),
            rk_top=jnp.where(go, _by_owner(top, owner), st.rk_top),
            rk_topmin=jnp.where(go, _by_owner(top, owner), st.rk_topmin),
            max_recv_ballot=jnp.maximum(st.max_recv_ballot,
                                        jnp.where(go, bal, 0)),
        )

    sec("px.takeover_phase1")
    # ---- 7. takeover phase 1 (forceCommit :244-257, :878-897) ----
    # 7a. answer PREPARE_INST: my slot contents or explicit empty; a
    # promise here blocks my own future ballot-0 writes only if the
    # slot was still NONE (owner priority is forfeited once a takeover
    # ballot touches the slot — tracked via ballot bump below).
    # a steady step has no such row: no answer, no promise, no adoption
    # paxlint: disable=trace-hazard -- `steady` is a static bool
    if not steady:
        rq = is_pinst & (inbox.op > 0)
        # paxlint: disable=trace-hazard -- `revoke` is a static bool
        if revoke:
            # ---- 7r. revocation (Mao et al., OSDI 2008: a suspected
            # server's future turns revoked by Paxos at a higher ballot;
            # upstream's forceCommit skip, mencius.go:878-897) ----
            # Phase 1 (a PREPARE_INST with op 1) asks every acceptor to
            # promise owner o's turns [inst, inst + R (L - 1)] to the
            # ballot, and to say which of o's values it holds: the highest
            # (cmd_id) and where the unbroken run of them ending there
            # begins (key_lo). Phase 2 (op 2) proposes no-ops from the
            # first turn above every answerer's highest value to the end of
            # the promise, under that very promise. Values all answerers
            # hold were accepted by a q2 majority, so are chosen: they
            # commit with their values. An acceptor refuses phase 2 where
            # it holds a value at or above its first turn; refusing is
            # always safe.
            o_q = jnp.mod(inbox.inst, R)
            top_o, low_o = held_run(state)
            at_q = (o_q[None, :] == jnp.arange(R)[:, None]) & rq[None, :]
            q_bal = jnp.where(at_q, inbox.ballot[None, :],
                              NO_BALLOT).max(axis=1)
            win_q = rq & (inbox.ballot == _by_owner(q_bal, o_q))
            at_w = at_q & win_q[None, :]
            q_at = jnp.where(at_w, inbox.inst[None, :], -1).max(axis=1)
            q_ph = jnp.where(at_w, inbox.op[None, :], 0).max(axis=1)
            has_q = q_bal > NO_BALLOT
            p_lo, p_hi, p_ok = _merge_range(state.rp_lo, state.rp_hi, q_at,
                                            q_at + R * (L - 1),
                                            state.window_base, R)
            ok1 = has_q & (q_ph == 1) & (q_bal >= state.rp_bal) & p_ok
            a_lo, a_hi, a_ok = _merge_range(state.rv_lo, state.rv_hi, q_at,
                                            state.rp_hi, state.window_base, R)
            ok2 = (has_q & (q_ph == 2) & (q_bal == state.rp_bal)
                   & (q_at >= state.rp_lo) & (q_at <= state.rp_hi)
                   & (top_o < q_at) & a_ok)
            state = state._replace(
                rp_lo=jnp.where(ok1, p_lo, state.rp_lo),
                rp_hi=jnp.where(ok1, p_hi, state.rp_hi),
                rp_bal=jnp.where(ok1, q_bal, state.rp_bal),
                rv_lo=jnp.where(ok2, a_lo, state.rv_lo),
                rv_hi=jnp.where(ok2, a_hi, state.rv_hi),
                rv_cm=jnp.where(ok2 & (state.rv_hi < state.window_base), -1,
                                state.rv_cm),
                rv_bal=jnp.where(ok2, q_bal, state.rv_bal),
                max_recv_ballot=jnp.maximum(
                    state.max_recv_ballot,
                    jnp.max(jnp.where(rq, inbox.ballot, 0))),
            )
            ok_row = win_q & _by_owner(jnp.where(q_ph == 1, ok1, ok2), o_q)
            out = out._replace(
                kind=jnp.where(rq, int(MsgKind.PREPARE_INST_REPLY), out.kind),
                src=jnp.where(rq, me, out.src),
                inst=jnp.where(rq, inbox.inst, out.inst),
                ballot=jnp.where(rq, _by_owner(state.rp_bal, o_q), out.ballot),
                last_committed=jnp.where(rq, inbox.ballot, out.last_committed),
                op=jnp.where(rq, ok_row.astype(jnp.int32), out.op),
                cmd_id=jnp.where(rq, _by_owner(top_o, o_q), out.cmd_id),
                key_lo=jnp.where(rq, _by_owner(low_o, o_q), out.key_lo),
                key_hi=jnp.where(rq, inbox.op - 1, out.key_hi),
                val_hi=jnp.where(rq, 0, out.val_hi),
                val_lo=jnp.where(rq, 0, out.val_lo),
                client_id=jnp.where(rq, REVOKE_MARK, out.client_id),
            )
            dst = jnp.where(rq, inbox.src, dst)
            # my revocation's answers
            o_k = jnp.mod(jnp.maximum(state.rk_at, 0), R)
            ph1 = state.rk_phase == 1
            ans = (is_pir & (inbox.client_id == REVOKE_MARK)
                   & (state.rk_at >= 0)
                   & (inbox.last_committed == state.rk_bal)
                   & (inbox.key_hi == state.rk_phase - 1)
                   & (inbox.inst == jnp.where(ph1, state.rk_at, state.rk_t)))
            yes = ans & (inbox.op == 1)
            said = (yes[:, None]
                    & (inbox.src[:, None] == jnp.arange(R))).any(0)
            acks = state.rk_acks | jnp.sum(
                jnp.where(said, jnp.left_shift(1, jnp.arange(R)), 0)
            ).astype(jnp.uint16)
            n_ack = jax.lax.population_count(acks).astype(jnp.int32)
            low = jnp.maximum(state.rk_low,
                              jnp.max(jnp.where(yes, inbox.key_lo, -1)))
            top = jnp.maximum(state.rk_top,
                              jnp.max(jnp.where(yes, inbox.cmd_id, -1)))
            topmin = jnp.minimum(state.rk_topmin, jnp.min(
                jnp.where(yes, inbox.cmd_id, REVOKE_NONE)))
            abandon = (ans & (inbox.op == 0)).any()
            done1 = ~abandon & ph1 & (n_ack >= max(quorum1, quorum2))
            done2 = ~abandon & (state.rk_phase == 2) & (n_ack >= quorum2)
            # the no-ops start above every answerer's value and above the
            # span the episode's sweep re-drives at the SAME ballot (10):
            # one ballot, one value a slot
            base = jnp.maximum(state.rk_at + sweep_span, top + R)
            t2 = base + jnp.mod(o_k - base, R)
            vals = (done1 & (owner_of == o_k) & (idx_abs >= low)
                    & (idx_abs <= topmin) & (state.status == ACCEPTED)
                    & (state.op != int(Op.NONE)))
            at_k = jnp.arange(R) == o_k
            m_lo, m_hi, m_ok = _merge_range(state.rv_lo, state.rv_hi, t2,
                                            state.rp_hi, state.window_base, R)
            stale_k = state.rv_hi < state.window_base
            # phase 2 only where its no-ops join the range of this owner I
            # already accepted (or that range lies below the window): an
            # owner that came back, proposed past the range and fell silent
            # again has values between the two, which one range announced
            # from its first turn would cover
            joins = _by_owner(m_ok, o_k)
            to2 = done1 & joins
            stop = abandon | done2 | (done1 & ~joins)
            got_vals = to2 & (topmin >= low)
            state = state._replace(
                status=jnp.where(vals, COMMITTED, state.status),
                # phase 1 done: my own acceptance of phase 2
                rv_lo=jnp.where(at_k & to2, m_lo, state.rv_lo),
                rv_hi=jnp.where(at_k & to2, m_hi, state.rv_hi),
                rv_bal=jnp.where(at_k & to2, state.rk_bal, state.rv_bal),
                rv_cm=jnp.where(
                    at_k & to2,
                    jnp.maximum(jnp.where(stale_k, -1, state.rv_cm), t2 - R),
                    jnp.where(at_k & done2,
                              jnp.maximum(state.rv_cm, state.rv_hi),
                              state.rv_cm)),
                rv_from=jnp.where(at_k & got_vals, low, state.rv_from),
                rv_vend=jnp.where(at_k & got_vals, topmin, state.rv_vend),
                rk_at=jnp.where(stop, -1, state.rk_at),
                rk_phase=jnp.where(stop, 0, jnp.where(to2, 2, state.rk_phase)),
                rk_t=jnp.where(to2, t2, state.rk_t),
                rk_acks=jnp.where(to2, me_bit, acks),
                rk_low=low, rk_top=top, rk_topmin=topmin,
                max_recv_ballot=jnp.maximum(
                    state.max_recv_ballot,
                    jnp.max(jnp.where(ans, inbox.ballot, 0))),
            )

        rel_pi, in_win_pi = _rel(state, inbox.inst, S)
        rel_pi_safe = jnp.minimum(rel_pi, S - 1)
        pi_answer = (is_pinst & ~rq
                     & (in_win_pi | (inbox.inst >= state.crt_inst)))
        pi_com = pi_answer & in_win_pi & (state.status[rel_pi_safe] >= COMMITTED)
        pi_occ = (pi_answer & ~pi_com & in_win_pi
                  & (state.status[rel_pi_safe] >= ACCEPTED))
        pi_val = pi_com | pi_occ
        # an empty slot in a range of no-ops I accepted (7r) answers as
        # that no-op at its ballot
        o_pi = jnp.mod(inbox.inst, R)
        pi_rv = (pi_answer & ~pi_val & in_win_pi
                 & (inbox.inst >= _by_owner(state.rv_lo, o_pi))
                 & (inbox.inst <= _by_owner(state.rv_hi, o_pi)))
        # promise: bump slot ballot so ballot-0 owner writes lose from here
        prom = pi_answer & ~pi_com & in_win_pi & (
            inbox.ballot > state.ballot[rel_pi_safe])
        state = state._replace(
            ballot=state.ballot.at[jnp.where(prom, rel_pi, S)].max(
                inbox.ballot, mode="drop"))
        out = out._replace(
            kind=jnp.where(pi_com, int(MsgKind.COMMIT),
                           jnp.where(pi_answer & ~pi_com,
                                     int(MsgKind.PREPARE_INST_REPLY), out.kind)),
            src=jnp.where(pi_answer, me, out.src),
            inst=jnp.where(pi_answer, inbox.inst, out.inst),
            ballot=jnp.where(pi_val, state.ballot[rel_pi_safe],
                             jnp.where(pi_rv, _by_owner(state.rv_bal, o_pi),
                                       jnp.where(pi_answer, NO_BALLOT,
                                                 out.ballot))),
            # COMMIT answers carry my real frontier (it feeds receivers'
            # peer_commits, 9d, and crt_inst, section 6 — echoing the
            # sweep ballot there poisoned catch-up targeting); PIR answers
            # echo the sweep ballot as the 7b context tag, as in
            # models/minpaxos.py 2b
            last_committed=jnp.where(pi_com, state.committed_upto,
                                     jnp.where(pi_answer, inbox.ballot,
                                               out.last_committed)),
            op=jnp.where(pi_val, state.op[rel_pi_safe],
                         jnp.where(pi_answer, 0, out.op)),
            key_hi=jnp.where(pi_val, state.key_hi[rel_pi_safe], out.key_hi),
            key_lo=jnp.where(pi_val, state.key_lo[rel_pi_safe], out.key_lo),
            val_hi=jnp.where(pi_val, state.val_hi[rel_pi_safe], out.val_hi),
            val_lo=jnp.where(pi_val, state.val_lo[rel_pi_safe], out.val_lo),
            cmd_id=jnp.where(pi_val, state.cmd_id[rel_pi_safe], out.cmd_id),
            client_id=jnp.where(pi_val, state.client_id[rel_pi_safe],
                                jnp.where(pi_rv, -1, out.client_id)),
        )
        dst = jnp.where(pi_answer, inbox.src, dst)

        # 7b. collect PREPARE_INST_REPLY answers (mine): pvotes + adoption
        rel_v, in_win_v = _rel(state, inbox.inst, S)
        rel_v_safe = jnp.minimum(rel_v, S - 1)
        pv_ok = (is_pir & (inbox.client_id != REVOKE_MARK)
                 & (inbox.last_committed == state.takeover_ballot)
                 & in_win_v)
        state = state._replace(
            pvotes=state.pvotes | scatter_vote_bits(S, rel_v, inbox.src,
                                                    pv_ok, R))
        pir_ok = (pv_ok & (state.status[rel_v_safe] < COMMITTED)
                  & (inbox.ballot > NO_BALLOT)
                  & (inbox.ballot > state.ballot[rel_v_safe]))
        vb_max = jnp.full(S + 1, NO_BALLOT, jnp.int32).at[
            jnp.where(pir_ok, rel_v, S)].max(inbox.ballot, mode="drop")
        pir_win = pir_ok & (inbox.ballot == vb_max[rel_v_safe])
        win_v, hit_v = slot_winner(S, rel_v, pir_win)
        state = slot_write(state, win_v, hit_v)._replace(
            status=gather_const(hit_v, ACCEPTED, state.status),
            votes=gather_const(hit_v, me_bit, state.votes),
        )

    # paxlint: disable=trace-hazard -- `revoke` is a static bool
    if revoke:
        sec("px.revoke")
        # ---- 8r. what a revocation chose (section 7r), in every step ----
        # A revoker re-sends, every step, ONE COMMIT row with REVOKE_MARK
        # per owner it revoked: the run [cmd_id, val_lo] of the owner's
        # values its phase 1 found held by all its answerers (chosen), the
        # no-ops it proposed from inst to key_hi at ballot, of which those
        # to key_lo are chosen. Turns of a chosen range that enter the
        # window become committed no-ops here, in the steady step too, so
        # the frontier never waits for a turn nobody will propose; before
        # the commit scan, so my own revocation's count in this step.
        # per owner over the announcing rows: [R, M] compares and a
        # reduction, not a scatter (a scatter costs its updates: the
        # inbox's rows, every step)
        at_r = ((jnp.mod(inbox.inst, R)[None, :] == jnp.arange(R)[:, None])
                & is_revoked[None, :])

        def rows_by_owner(how, init, col):
            pick = jnp.where(at_r, col[None, :], init)
            return pick.min(axis=1) if how == "min" else pick.max(axis=1)

        rd_t = rows_by_owner("min", REVOKE_NONE, inbox.inst)
        rd_cm = rows_by_owner("max", -1, inbox.key_lo)
        rd_hi = rows_by_owner("max", -1, inbox.key_hi)
        rd_from = rows_by_owner("min", REVOKE_NONE, inbox.cmd_id)
        rd_vend = rows_by_owner("max", -1, inbox.val_lo)
        rd_bal = rows_by_owner("max", NO_BALLOT, inbox.ballot)
        p_lo, p_hi, p_ok = _merge_range(state.rp_lo, state.rp_hi, rd_t, rd_hi,
                                        state.window_base, R)
        a_lo, a_hi, a_ok = _merge_range(state.rv_lo, state.rv_hi, rd_t, rd_hi,
                                        state.window_base, R)
        take = (rd_hi >= 0) & p_ok & a_ok
        state = state._replace(
            rp_lo=jnp.where(take, p_lo, state.rp_lo),
            rp_hi=jnp.where(take, p_hi, state.rp_hi),
            rp_bal=jnp.where(take, jnp.maximum(state.rp_bal, rd_bal),
                             state.rp_bal),
            rv_lo=jnp.where(take, a_lo, state.rv_lo),
            rv_hi=jnp.where(take, a_hi, state.rv_hi),
            rv_bal=jnp.where(take, jnp.maximum(state.rv_bal, rd_bal),
                             state.rv_bal),
            rv_cm=jnp.where(take, jnp.maximum(
                jnp.where(state.rv_hi < state.window_base, -1, state.rv_cm),
                rd_cm), state.rv_cm),
            max_recv_ballot=jnp.maximum(
                state.max_recv_ballot,
                jnp.max(jnp.where(take, rd_bal, 0))),
        )
        noop_cm = ((idx_abs >= _by_owner(state.rv_lo, owner_of))
                   & (idx_abs <= _by_owner(state.rv_cm, owner_of))
                   & (state.status < COMMITTED))
        val_cm = ((idx_abs >= _by_owner(jnp.where(take, rd_from, REVOKE_NONE),
                                         owner_of))
                  & (idx_abs <= _by_owner(rd_vend, owner_of))
                  & (state.status == ACCEPTED)
                  & (state.op != int(Op.NONE)))
        state = state._replace(
            status=jnp.where(noop_cm | val_cm, COMMITTED, state.status),
            ballot=jnp.where(noop_cm, _by_owner(state.rv_bal, owner_of),
                             state.ballot),
            op=jnp.where(noop_cm, int(Op.NONE), state.op),
            cmd_id=jnp.where(noop_cm, 0, state.cmd_id),
            client_id=jnp.where(noop_cm, -1, state.client_id),
        )

    sec("px.commit_scan")
    # ---- 8. commit scan: my owned slots at majority, frontier ----
    n_votes = jax.lax.population_count(state.votes).astype(jnp.int32)
    driven_by_me = own_mask | (
        (state.ballot > 0) & (jnp.mod(state.ballot, 16) == me))
    my_commit = (driven_by_me & (state.status == ACCEPTED)
                 & (n_votes >= quorum2))
    state = state._replace(
        status=jnp.where(my_commit, COMMITTED, state.status))
    old_upto = state.committed_upto
    start_rel = state.committed_upto + 1 - state.window_base
    frontier_rel = commit_frontier(state.status >= COMMITTED, start_rel)
    state = state._replace(
        committed_upto=jnp.maximum(state.committed_upto,
                                   frontier_rel + state.window_base))
    advanced = state.committed_upto > old_upto
    in_flight = state.crt_inst - 1 > state.committed_upto
    state = state._replace(
        tick=state.tick + tick_inc,
        stall_ticks=jnp.where(in_flight & ~advanced,
                              state.stall_ticks + tick_inc, 0))

    sec("px.commit_bcast")
    # ---- 9. chunked COMMIT broadcast for my newly committed slots ----
    # Strides over MY OWN slots (me, me+R, ...): a window over raw log
    # slots would contain only 1/R own slots, capping the announce rate
    # at catchup_rows/R per step — below the proposal rate, so the
    # cluster frontier (which needs every owner's commits) would lag
    # unboundedly. commit_sent is the last own slot announced; foreign
    # commits are their owners' jobs (takeover commits: see 9b).
    K = cfg.catchup_rows

    def slots_at(rel_safe, *more):
        """status, the slot's ``SLOT_FIELDS`` (op widened to the wire's
        int32) and ``more`` columns at the window indices ``rel_safe``,
        in one fetch (read_cols): what 9, 9b, 9c and 9d each send of
        the slots they announce."""
        status, *cols = read_cols(
            rel_safe, (state.status,)
            + tuple(getattr(state, f) for f in SLOT_FIELDS) + more)
        slot = dict(zip(SLOT_FIELDS, cols))
        slot["op"] = slot["op"].astype(jnp.int32)
        return status, slot, cols[len(SLOT_FIELDS):]

    # never let the cursor fall below the window (slid-out slots were
    # executed everywhere; pinning there would wedge the broadcast)
    state = state._replace(
        commit_sent=jnp.maximum(state.commit_sent, state.window_base - 1))
    cb0 = state.commit_sent + 1
    cb0 = cb0 + jnp.mod(me - cb0, R)  # first own slot > commit_sent
    cb_slots = cb0 + R * jnp.arange(K, dtype=jnp.int32)
    cb_rel = cb_slots - state.window_base
    cb_rel_safe = jnp.clip(cb_rel, 0, S - 1)
    # no-op commits (ceded slots) broadcast too: harmless duplicate of
    # their SKIP; receivers' status guards make both idempotent.
    cb_status, cb_slot, _ = slots_at(cb_rel_safe)
    cb_ok = (cb_rel >= 0) & (cb_rel < S) & (cb_status >= COMMITTED)
    cb = MsgBatch(
        kind=jnp.where(cb_ok, int(MsgKind.COMMIT), 0).astype(jnp.int32),
        src=jnp.full(K, me, jnp.int32),
        inst=cb_slots,
        last_committed=jnp.full(K, state.committed_upto, jnp.int32),
        **cb_slot,
    )
    # advance through the committed prefix of my own-slot stride
    resolved = cb_ok
    pending_first = jnp.argmin(resolved.astype(jnp.int32))
    n_resolved = jnp.where(resolved.all(), K, pending_first)
    state = state._replace(
        commit_sent=jnp.maximum(
            state.commit_sent, cb0 + R * n_resolved - R) )
    # 9b. takeover-commit announce: slots I committed at a takeover
    # ballot are NOT ≡ me (mod R) so the stride broadcast misses them,
    # and my own frontier jumps past them the moment they commit — so
    # the window is anchored at the EPISODE's blocking slot (tk_anchor,
    # set in step 10) and keeps re-announcing until the slots slide out
    # or a new episode moves the anchor (bounded duplicates; self-
    # healing against commit-row loss).
    K2b = cfg.recovery_rows
    ta_slots = state.tk_anchor + jnp.arange(K2b, dtype=jnp.int32)
    ta_rel = ta_slots - state.window_base
    ta_rel_safe = jnp.clip(ta_rel, 0, S - 1)
    ta_status, ta_slot, _ = slots_at(ta_rel_safe)
    ta_own = jnp.mod(ta_slots, R)
    ta_ok = ((state.tk_anchor >= 0) & (ta_rel >= 0) & (ta_rel < S)
             & (ta_status >= COMMITTED)
             & (ta_slot["ballot"] > 0)
             & (jnp.mod(ta_slot["ballot"], 16) == me)
             # a revocation's no-ops every replica learns from 8r
             & ~((ta_slots >= _by_owner(state.rv_lo, ta_own))
                 & (ta_slots <= _by_owner(state.rv_cm, ta_own))))
    ta = MsgBatch(
        kind=jnp.where(ta_ok, int(MsgKind.COMMIT), 0).astype(jnp.int32),
        src=jnp.full(K2b, me, jnp.int32),
        inst=ta_slots,
        last_committed=jnp.full(K2b, state.committed_upto, jnp.int32),
        **ta_slot,
    )

    # 9c. own-slot accept RETRY (mirror of models/minpaxos.py 7d).
    # Without it, a lost ACCEPT or ack waits for the TAKEOVER sweep —
    # the protocol's only other rescuer — so under load-induced inbox
    # overflow the rr TCP bench ran at takeover cadence with constant
    # ballot-bump/re-drive churn (round-5 repro: raising noop_delay
    # alone collapsed throughput 1474 -> 1.4 ops/s). After 4 stalled
    # steps, rebroadcast my still-unacked driven slots in the blocked
    # range at their CURRENT ballot: no bump, no churn — peers dedupe
    # re-accepts and re-ack committed content (section 2 acc_ok /
    # acc_dup_ok), like the reference's leader re-sending accepts on
    # its own clock rather than escalating (bareminpaxos.go analog;
    # mencius.go relies on TCP never dropping, which the bounded inbox
    # here does not guarantee).
    K3 = cfg.catchup_rows
    rt_slots = state.committed_upto + 1 + jnp.arange(K3, dtype=jnp.int32)
    rt_rel = rt_slots - state.window_base
    rt_rel_safe = jnp.clip(rt_rel, 0, S - 1)
    rt_status, rt_slot, (rt_driven, rt_votes) = slots_at(
        rt_rel_safe, driven_by_me, n_votes)
    rt_ok = ((state.stall_ticks >= 4) & (rt_rel >= 0) & (rt_rel < S)
             & (rt_slots < state.crt_inst)
             & rt_driven
             & (rt_status == ACCEPTED)
             & (rt_votes < quorum2))
    rt = MsgBatch(
        kind=jnp.where(rt_ok, int(MsgKind.ACCEPT), 0).astype(jnp.int32),
        src=jnp.full(K3, me, jnp.int32),
        inst=rt_slots,
        last_committed=jnp.full(K3, state.committed_upto, jnp.int32),
        **rt_slot,
    )

    # 9d. frontier catch-up (the minpaxos 7c scheme, which mencius
    # lacked entirely): commit_sent announces each own committed slot
    # ONCE, so a peer whose inbox overflowed during a burst loses those
    # COMMIT rows forever, its frontier (and exec, and client replies)
    # then advances only at the pace of whatever traffic it happens to
    # re-learn from — observed as a replica trailing the others by 10k
    # slots while "advancing" just enough that the stall-gated takeover
    # never fired, flat-lining the rr bench. Cure: every step, re-serve
    # up to catchup_rows committed slots to one lagging peer (worst /
    # round-robin alternation as in models/minpaxos.py 7c), unicast.
    pc_masked = jnp.where(jnp.arange(R) == me, jnp.int32(2 ** 30),
                          state.peer_commits)
    worst = jnp.argmin(pc_masked).astype(jnp.int32)
    rr_peer = jnp.mod(state.tick // 2, R)
    cu_peer = jnp.where(jnp.mod(state.tick, 2) == 0, worst, rr_peer)
    cu_lag = state.peer_commits[cu_peer] < state.committed_upto
    do_cu = (cu_peer != me) & cu_lag
    K4 = cfg.catchup_rows
    cu_slots = state.peer_commits[cu_peer] + 1 + jnp.arange(
        K4, dtype=jnp.int32)
    cu_rel = cu_slots - state.window_base
    cu_rel_safe = jnp.clip(cu_rel, 0, S - 1)
    cu_status, cu_slot, _ = slots_at(cu_rel_safe)
    cu_ok = (do_cu & (cu_slots <= state.committed_upto)
             & (cu_rel >= 0) & (cu_rel < S)
             & (cu_status >= COMMITTED))
    cu = MsgBatch(
        kind=jnp.where(cu_ok, int(MsgKind.COMMIT), 0).astype(jnp.int32),
        src=jnp.full(K4, me, jnp.int32),
        inst=cu_slots,
        last_committed=jnp.full(K4, state.committed_upto, jnp.int32),
        **cu_slot,
    )

    sec("px.takeover")
    # ---- 10. takeover driver: successor sweeps the blocked range ----
    K2 = cfg.recovery_rows
    # paxlint: disable=trace-hazard -- `steady` is a static bool
    if steady:
        # no stall counter at cfg.noop_delay: no ballot is drawn, no
        # slot swept, filled or re-driven, and no row is live
        tk = rd = MsgBatch.empty(K2)
    else:
        blocking = state.committed_upto + 1
        blk_owner = jnp.mod(blocking, R)
        i_am_successor = jnp.mod(blk_owner + 1, R) == me
        # successor-priority avoids ballot duels, but a revived laggard's
        # frontier view is private — the blocking owner's successor (a live
        # replica, far ahead) will never sweep FOR it. After a long stall
        # any stuck replica sweeps its own blocked range, with the
        # threshold staggered by replica id so that under a global stall
        # competing sweepers start serialized instead of dueling ballots
        # on the same tick (the reference staggers forceCommit the same
        # way, mencius.go:878-886 "50+Id").
        # ... and only a replica that heard some peer in the last
        # noop_delay ticks before this step: one that hears nobody (a
        # dead replica of the pod, an isolated server) has no one to ask
        do_tk = (in_flight & heard_lately
                 & ((i_am_successor & (state.stall_ticks >= cfg.noop_delay))
                    | (state.stall_ticks >= (4 + me) * cfg.noop_delay)))
        # fresh takeover ballot when starting a new takeover episode
        new_tb = make_ballot(state.max_recv_ballot // 16 + 1, me)
        tb = jnp.where(do_tk & (state.takeover_ballot < 0), new_tb,
                       state.takeover_ballot)
        fresh = do_tk & (state.takeover_ballot < 0)
        state = state._replace(
            takeover_ballot=tb,
            max_recv_ballot=jnp.maximum(state.max_recv_ballot, tb),
            pvotes=jnp.where(fresh, jnp.uint16(0), state.pvotes),
            tk_anchor=jnp.where(fresh, blocking, state.tk_anchor),
        )
        tk_slots = blocking + jnp.arange(K2, dtype=jnp.int32)
        tk_rel = tk_slots - state.window_base
        tk_rel_safe = jnp.clip(tk_rel, 0, S - 1)
        tk_ok = (do_tk & (tk_slots < state.crt_inst) & (tk_rel >= 0)
                 & (tk_rel < S))
        tk = MsgBatch.empty(K2)._replace(
            kind=jnp.where(tk_ok, int(MsgKind.PREPARE_INST), 0).astype(jnp.int32),
            src=jnp.full(K2, me, jnp.int32),
            ballot=jnp.full(K2, tb, jnp.int32),
            inst=tk_slots,
        )
        tk_row = idx - tk_rel[0]
        state = state._replace(
            # tk_rel is a contiguous range: slot s's source row is
            # s - tk_rel[0], so the OR-delta is a dense select (no scatter)
            pvotes=state.pvotes | jnp.where(
                (tk_row >= 0) & (tk_row < K2)
                & tk_ok[jnp.clip(tk_row, 0, K2 - 1)],
                me_bit, jnp.uint16(0)))
        # no-op fill empties with a phase-1 majority; re-drive adopted
        # values; both as ACCEPTs at the takeover ballot
        pv_cnt = jax.lax.population_count(state.pvotes).astype(jnp.int32)
        in_tk_span = (idx_abs >= blocking) & (
            idx_abs < blocking + K2) & (idx_abs < state.crt_inst)
        fill = (do_tk & in_tk_span & (state.status == NONE)
                & (pv_cnt >= quorum1))
        state = state._replace(
            status=jnp.where(fill, ACCEPTED, state.status),
            ballot=jnp.where(fill, tb, state.ballot),
            op=jnp.where(fill, int(Op.NONE), state.op),
            cmd_id=jnp.where(fill, 0, state.cmd_id),
            client_id=jnp.where(fill, -1, state.client_id),
            votes=jnp.where(fill, me_bit, state.votes),
        )
        # never a value into turns a revocation proposes as no-ops: the
        # two share the episode's ballot (7r)
        revoked_here = ((idx_abs >= _by_owner(state.rv_lo, owner_of))
                        & (idx_abs <= _by_owner(state.rv_hi, owner_of)))
        redrive = (do_tk & in_tk_span & (state.status == ACCEPTED)
                   & ((state.ballot == tb) | (pv_cnt >= quorum1))
                   & ~revoked_here)
        bump = redrive & (state.ballot != tb)
        state = state._replace(
            ballot=jnp.where(bump, tb, state.ballot),
            votes=jnp.where(bump, me_bit, state.votes),
        )
        rd_slots = blocking + jnp.arange(K2, dtype=jnp.int32)
        rd_rel_safe = jnp.clip(rd_slots - state.window_base, 0, S - 1)
        rd_ok = tk_ok & redrive[rd_rel_safe]
        rd = MsgBatch(
            kind=jnp.where(rd_ok, int(MsgKind.ACCEPT), 0).astype(jnp.int32),
            src=jnp.full(K2, me, jnp.int32),
            ballot=jnp.full(K2, tb, jnp.int32),
            inst=rd_slots,
            last_committed=jnp.full(K2, state.committed_upto, jnp.int32),
            op=state.op[rd_rel_safe].astype(jnp.int32),
            key_hi=state.key_hi[rd_rel_safe],
            key_lo=state.key_lo[rd_rel_safe],
            val_hi=state.val_hi[rd_rel_safe],
            val_lo=state.val_lo[rd_rel_safe],
            cmd_id=state.cmd_id[rd_rel_safe],
            client_id=state.client_id[rd_rel_safe],
        )
        # the successor proper (not a laggard sweeping for itself, nor
        # one whose peers report frontiers past its own: its view of
        # the owner is stale) also revokes the blocking owner's turns
        # from the blocking slot on, at the sweep's ballot (section 7r;
        # its no-ops start above the sweep's span, one value a slot at
        # one ballot)
        peers_ahead = jnp.max(jnp.where(jnp.arange(R) == me, -1,
                                        state.peer_commits))
        # It waits two ticks into the episode, time for the owner to
        # answer the sweep's PREPARE_INST if it is there (a live owner
        # that only looked silent is not revoked), and once the
        # revocation covers the blocking turn it is not begun again.
        covered = ((_by_owner(state.rp_lo, blk_owner) <= blocking)
                   & (blocking <= _by_owner(state.rp_hi, blk_owner)))
        # paxlint: disable=trace-hazard -- `revoke` is a static bool
        if revoke:
            state = begin_revoke(
                state,
                do_tk & i_am_successor
                & (state.stall_ticks >= cfg.noop_delay + 2)
                & (state.rk_at < 0) & (blk_owner != me) & ~covered
                & (peers_ahead <= state.committed_upto)
                & (_by_owner(quiet0, blk_owner) >= cfg.noop_delay),
                blk_owner, blocking, tb)
    # takeover episode ends when the frontier moves again
    state = state._replace(
        takeover_ballot=jnp.where(advanced, jnp.int32(NO_BALLOT),
                                  state.takeover_ballot))

    # paxlint: disable=trace-hazard -- `revoke` is a static bool
    if revoke:
        sec("px.revoke_send")
        # ---- 10r. my revocation's rows, in every step (section 7r) ----
        # While it is in flight, its request (phase 1 or 2) every step.
        # While an owner I revoked stays silent (``quiet``) and the chosen
        # no-ops come within a window of my frontier, the next range, at
        # the same ballot, with no stall first. And the chosen
        # part of each range I revoked, while still in the window, every
        # step.
        mine = (state.rv_bal > NO_BALLOT) & (jnp.mod(state.rv_bal, 16) == me)
        due = (mine & (state.rv_cm >= state.window_base)
               & (state.rv_cm - state.committed_upto < S)
               & (state.quiet >= cfg.noop_delay)
               & (jnp.arange(R) != me) & (state.rk_at < 0))
        o_n = jnp.argmax(due).astype(jnp.int32)
        # (its phase 1 starts a sweep span early, over turns already
        # chosen, so that its no-ops start right after them)
        state = begin_revoke(state, due.any(), o_n,
                             _by_owner(state.rv_cm, o_n) + R - sweep_span,
                             _by_owner(state.rv_bal, o_n))
        rk_row = MsgBatch.empty(1)._replace(
            kind=jnp.where(state.rk_at >= 0, int(MsgKind.PREPARE_INST),
                           0)[None].astype(jnp.int32),
            src=jnp.full(1, me, jnp.int32),
            inst=jnp.where(state.rk_phase == 1, state.rk_at, state.rk_t)[None],
            ballot=state.rk_bal[None],
            op=state.rk_phase[None],
        )
        shown = mine & (jnp.maximum(state.rv_cm, state.rv_vend)
                        >= state.window_base)
        o_s = jnp.argmax(shown).astype(jnp.int32)
        rv_row = MsgBatch.empty(1)._replace(
            kind=jnp.where(shown.any(), int(MsgKind.COMMIT),
                           0)[None].astype(jnp.int32),
            src=jnp.full(1, me, jnp.int32),
            inst=_by_owner(state.rv_lo, o_s)[None],
            ballot=_by_owner(state.rv_bal, o_s)[None],
            last_committed=state.committed_upto[None],
            key_hi=_by_owner(state.rv_hi, o_s)[None],
            key_lo=_by_owner(state.rv_cm, o_s)[None],
            val_lo=_by_owner(state.rv_vend, o_s)[None],
            cmd_id=_by_owner(state.rv_from, o_s)[None],
            client_id=jnp.full(1, REVOKE_MARK, jnp.int32),
        )

    sec("px.outbox")
    out = _concat_rows(_concat_rows(_concat_rows(_concat_rows(_concat_rows(
        _concat_rows(_concat_rows(out, skip_row), cb), ta), rt), cu), tk), rd)
    # paxlint: disable=trace-hazard -- `revoke` is a static bool
    if revoke:
        out = _concat_rows(_concat_rows(out, rk_row), rv_row)
    dst = jnp.concatenate([
        dst,
        jnp.full(1, -1, jnp.int32),    # skip broadcast
        jnp.full(K, -1, jnp.int32),    # own-commit broadcast
        jnp.full(K2b, -1, jnp.int32),  # takeover-commit announce
        jnp.full(K3, -1, jnp.int32),   # own-accept retry broadcast
        jnp.full(K4, cu_peer, jnp.int32),  # catch-up -> lagging peer
        jnp.full(K2, -1, jnp.int32),   # takeover sweep
        jnp.full(K2, -1, jnp.int32),   # takeover re-drive
    ] + [jnp.full(2, -1, jnp.int32)] * revoke)  # revocation request, announce

    sec("px.exec")
    # ---- 11. conflict-aware out-of-order execution (:799-876) ----
    # A committed, unexecuted slot executes this step iff every EARLIER
    # window slot that conflicts with it (same key, at least one PUT —
    # state.go:55-62) is already executed-or-being-executed. We take
    # the contiguous executable prefix [executed_upto+1, frontier] AND
    # any committed slot above the frontier whose conflicts are all
    # committed below it with no uncommitted conflicting predecessor.
    E = cfg.exec_batch
    exec_lo = state.executed_upto + 1
    rel_e0 = exec_lo - state.window_base

    # The whole sort/scan/KV pipeline runs under lax.cond only when a
    # committed-unexecuted slot exists (status == COMMITTED exactly:
    # execution moves slots to EXECUTED). Idle and accept-only ticks —
    # most ticks of a serial op's path — skip the window lexsort and
    # the KV probe entirely (the same gating models/minpaxos.py step 8
    # got this round: 2.36 -> sub-1 ms idle mencius step on the host).
    def _exec_pipeline(st):
        # in-order part
        avail = st.committed_upto - st.executed_upto
        n_inorder = jnp.clip(avail, 0, E)
        in_prefix = (idx >= rel_e0) & (idx < rel_e0 + n_inorder)
        # out-of-order part: committed slots above the frontier with no
        # uncommitted conflicting predecessor in the window. Sort by
        # (key, slot); an uncommitted write "poisons" every later slot
        # of the same key via a segmented running max.
        rows_w = jnp.arange(S, dtype=jnp.int32)
        order = jnp.lexsort((rows_w, st.key_lo, st.key_hi))
        # the window in execution order, six columns in one fetch
        # (read_cols): the order is a permutation of the window, S x S
        # pairs, which at the pod's and the served window of 4,096 is
        # exactly ONEHOT_PAIRS and so inside the bound
        (s_status, s_op, s_key_hi, s_key_lo, s_executed,
         s_in_prefix) = read_cols(order, (st.status, st.op, st.key_hi,
                                          st.key_lo, st.executed,
                                          in_prefix))
        pos = jnp.arange(S, dtype=jnp.int32)
        seg_start = (pos == 0) | (s_key_hi != jnp.roll(s_key_hi, 1)) | (
            s_key_lo != jnp.roll(s_key_lo, 1))
        live = (s_status >= ACCEPTED) & (s_status < EXECUTED)
        uncommitted_write = ((s_status == ACCEPTED)
                             & ((s_op == int(Op.PUT))
                                | (s_op == int(Op.DELETE))))
        # also: ANY unexecuted write below blocks a GET; any unexecuted
        # slot of same key blocks a WRITE (sequential-equivalence); use
        # conservative rule: blocked if any same-key slot with smaller
        # slot number is not yet executed and not in this step's
        # in-order prefix
        not_done = live & ~s_executed & ~s_in_prefix
        poison = jnp.where(not_done | uncommitted_write, pos, -1)
        last_poison = segmented_scan_max(poison, seg_start)
        # slot is clear if no poison strictly before it in its segment
        prev_poison = jnp.where(seg_start, -1,
                                jnp.concatenate([jnp.array([-1]),
                                                 last_poison[:-1]]))
        clear_sorted = prev_poison < 0
        clear = jnp.zeros(S, bool).at[order].set(clear_sorted)
        # gap barrier: a NONE slot above the frontier has UNKNOWN
        # future content (its key can't be consulted), so nothing
        # beyond the first such gap may execute early — otherwise a
        # later-committed PUT in the gap would be serialized after a
        # GET that should have seen it
        first_gap = jnp.min(jnp.where(
            (idx_abs > st.committed_upto) & (st.status == NONE),
            idx_abs, jnp.int32(2 ** 30)))
        ooo = ((st.status == COMMITTED) & ~st.executed & ~in_prefix
               & (idx_abs > st.committed_upto) & (idx_abs < first_gap)
               & clear)
        # compact: in-order prefix first (slot order), then OOO slots
        # up to the E budget; slots already executed out-of-order must
        # not run again when the in-order prefix sweeps past them
        want = (in_prefix & ~st.executed) | ooo
        exec_rank = jnp.cumsum(want.astype(jnp.int32)) - 1
        take = want & (exec_rank < E)
        slot_of = jnp.full(E, S, jnp.int32).at[
            jnp.where(take, exec_rank, E)].min(idx, mode="drop")
        evalid = slot_of < S
        slot_of_safe = jnp.clip(slot_of, 0, S - 1)
        # the batch's seven payload columns in one fetch (the apply
        # writes none of them)
        (op_s, key_hi_e, key_lo_e, val_hi_e, val_lo_e, cmd_id_e,
         client_id_e) = read_cols(
            slot_of_safe, tuple(getattr(st, f) for f in SLOT_FIELDS[1:]))
        op_e = jnp.where(evalid, op_s.astype(jnp.int32), 0)
        # the vmapped compositions share one trace of the apply
        apply = kv_apply_batch if cfg.gate_exec else kv_apply_batch_shared
        kv, o_hi, o_lo, o_found = apply(
            st.kv, op_e, key_hi_e, key_lo_e, val_hi_e, val_lo_e, evalid)
        newly_exec = jnp.zeros(S, bool).at[
            jnp.where(evalid, slot_of, S)].set(True, mode="drop")
        return (kv, newly_exec, evalid, op_e, o_hi, o_lo, o_found,
                jnp.where(evalid, cmd_id_e, 0),
                jnp.where(evalid, client_id_e, 0))

    def _no_exec(st):
        z = jnp.zeros(E, jnp.int32)
        return (st.kv, jnp.zeros(S, bool), jnp.zeros(E, bool), z, z, z,
                jnp.zeros(E, bool), z, z)

    if cfg.gate_exec:
        (kv, newly_exec, evalid, op_e, o_hi, o_lo, o_found, cmd_id_e,
         client_id_e) = jax.lax.cond(
            (state.status == COMMITTED).any(), _exec_pipeline, _no_exec,
            state)
    else:  # vmapped composition: cond would run both branches anyway
        (kv, newly_exec, evalid, op_e, o_hi, o_lo, o_found, cmd_id_e,
         client_id_e) = _exec_pipeline(state)
    state = state._replace(
        kv=kv,
        executed=state.executed | newly_exec,
        status=jnp.where(newly_exec, EXECUTED, state.status),
    )
    # executed_upto advances through the contiguous executed prefix
    ex_rel = commit_frontier(state.executed | (state.status >= EXECUTED),
                             state.executed_upto + 1 - state.window_base)
    state = state._replace(
        executed_upto=jnp.maximum(state.executed_upto,
                                  ex_rel + state.window_base))
    execr = ExecResult(
        lo=exec_lo, count=evalid.sum(),
        val_hi=o_hi, val_lo=o_lo, found=o_found,
        op=op_e,
        cmd_id=cmd_id_e,
        client_id=client_id_e,
    )

    sec("px.window_slide")
    # ---- 12. window slide (same scheme as minpaxos step 9) ----
    if cfg.slide_window:
        retention = cfg.retention if cfg.retention >= 0 else S // 2
        exec_edge = state.executed_upto + 1
        target = exec_edge - retention
        shift = jnp.clip(target - state.window_base, 0, S)
        gone = idx >= (S - shift)

        def slide(a, fill):
            rolled = jnp.roll(a, -shift, axis=0)
            m = gone if a.ndim == 1 else gone[:, None]
            return jnp.where(m, fill, rolled)

        state = state._replace(
            ballot=slide(state.ballot, NO_BALLOT),
            status=slide(state.status, NONE),
            op=slide(state.op, 0),
            key_hi=slide(state.key_hi, 0),
            key_lo=slide(state.key_lo, 0),
            val_hi=slide(state.val_hi, 0),
            val_lo=slide(state.val_lo, 0),
            cmd_id=slide(state.cmd_id, 0),
            client_id=slide(state.client_id, 0),
            votes=slide(state.votes, 0),
            pvotes=slide(state.pvotes, 0),
            executed=slide(state.executed, False),
            window_base=state.window_base + shift,
        )
    return state, Outbox(msgs=out, dst=dst, acked=ack_ok_row), execr


mencius_step = jax.jit(mencius_step_impl, static_argnums=0,
                       donate_argnums=1)


class MenciusCluster:
    """Pod-mode Mencius harness: N multi-leader replicas on device,
    messages routed as array ops (the Mencius analogue of
    models/cluster.py's Cluster — there is no elect(): every replica
    serves proposals into its owned slots from boot)."""

    def __init__(self, cfg: MinPaxosConfig, ext_rows: int = 1024):
        from minpaxos_tpu.models.cluster import ClusterState, cluster_step
        from minpaxos_tpu.verify.quorum import validate_config_quorums

        validate_config_quorums(cfg)
        self.cfg = cfg
        self.ext_rows = ext_rows
        self._cluster_step = cluster_step
        states = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[init_mencius(cfg, i) for i in range(cfg.n_replicas)])
        self.cs = ClusterState(
            states=states,
            pending=jax.tree_util.tree_map(
                lambda x: jnp.zeros((cfg.n_replicas,) + x.shape, x.dtype),
                MsgBatch.empty(cfg.inbox)),
            alive=jnp.ones(cfg.n_replicas, dtype=bool),
        )
        self._ext_queue: list[tuple[int, object]] = []
        self.replies: dict[tuple[int, int], dict] = {}
        self.reply_log: list[dict] = []
        self._proposed_at: dict[tuple[int, int], int] = {}
        self._prop_keys: dict[int, object] = {}  # rep -> cluster.KeyBuf

    def kill(self, replica: int) -> None:
        self.cs = self.cs._replace(alive=self.cs.alive.at[replica].set(False))

    def revive(self, replica: int) -> None:
        self.cs = self.cs._replace(alive=self.cs.alive.at[replica].set(True))

    def propose(self, ops, keys, vals, cmd_ids, client_id: int, to: int):
        """Queue PROPOSE rows for owner ``to`` — ANY replica serves
        proposals in Mencius (multi-leader); no leader discovery."""
        from minpaxos_tpu.ops.packed import split_i64

        ops = np.asarray(ops, dtype=np.int32)
        k_hi, k_lo = split_i64(np.asarray(keys))
        v_hi, v_lo = split_i64(np.asarray(vals))
        n = len(ops)
        row = dict(
            kind=np.full(n, int(MsgKind.PROPOSE), np.int32),
            src=np.full(n, -1, np.int32),
            ballot=np.zeros(n, np.int32),
            inst=np.zeros(n, np.int32),
            last_committed=np.zeros(n, np.int32),
            op=ops,
            key_hi=k_hi.astype(np.int32), key_lo=k_lo.astype(np.int32),
            val_hi=v_hi.astype(np.int32), val_lo=v_lo.astype(np.int32),
            cmd_id=np.asarray(cmd_ids, dtype=np.int32),
            client_id=np.full(n, client_id, np.int32),
        )
        for mid in np.asarray(cmd_ids, dtype=np.int64):
            self._proposed_at[(client_id, int(mid))] = to
        from minpaxos_tpu.models.cluster import KeyBuf, pack_reply_key

        self._prop_keys.setdefault(to, KeyBuf()).append(
            pack_reply_key(client_id, cmd_ids))
        batch = MsgBatch(**{f: row[f] for f in MsgBatch._fields})
        for lo in range(0, n, self.ext_rows):
            self._ext_queue.append((to, jax.tree_util.tree_map(
                lambda x: x[lo: lo + self.ext_rows], batch)))

    def _drain_ext(self) -> MsgBatch:
        r, m = self.cfg.n_replicas, self.ext_rows
        cols = {f: np.zeros((r, m), np.int32) for f in MsgBatch._fields}
        fill = [0] * r
        rest = []
        for to, rows in self._ext_queue:
            arrs = rows._asdict() if isinstance(rows, MsgBatch) else rows
            n = np.atleast_1d(arrs["kind"]).shape[0]
            if fill[to] + n > m:
                rest.append((to, rows))
                continue
            sl = slice(fill[to], fill[to] + n)
            for f in MsgBatch._fields:
                cols[f][to, sl] = arrs[f]
            fill[to] += n
        self._ext_queue = rest
        return MsgBatch(**{f: jnp.asarray(cols[f]) for f in MsgBatch._fields})

    def step(self) -> None:
        ext = self._drain_ext()
        self.cs, execr, _, _ = self._cluster_step(
            self.cfg, self.cs, ext, mencius_step_impl)
        self._collect_exec(execr)

    def run(self, n: int) -> None:
        for _ in range(n):
            self.step()

    def _collect_exec(self, execr: ExecResult) -> None:
        from minpaxos_tpu.models.cluster import collect_exec_replies

        # drop_skip_fills: Mencius SKIP fills execute as (op=0, mid=0)
        # rows that no client ever proposed; no per-slot inst is
        # recorded because out-of-order execution makes the contiguous
        # exec_lo+i numbering of the MinPaxos collector meaningless
        collect_exec_replies(self, execr, drop_skip_fills=True,
                             record_inst=False)
