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
  fills slots a majority reports empty — the reference's
  NB_INST_TO_SKIP bulk skip, but majority-audited per slot (the same
  pvotes machinery as models/minpaxos.py step 7d/7e).
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
        kv=kv_init(cfg.kv_pow2),
    )


def mencius_step_impl(
    cfg: MinPaxosConfig, state: MenciusState, inbox: MsgBatch,
    tick_inc=1, steady: bool = False,
) -> tuple[MenciusState, Outbox, ExecResult]:
    """Advance one Mencius replica by one message batch (pure; vmapped
    by the cluster wrapper below).

    ``tick_inc``: wall ticks this step represents (0 for the trailing
    substeps of a fused burst — see models/minpaxos.py
    replica_step_impl); keeps the stall/takeover counters wall-honest
    under the TCP runtime's multi-substep dispatches.

    ``steady`` (static): trace the step without its recovery sections
    (those ``recovery_gates`` names), as models/minpaxos.py
    ``replica_step_impl`` does."""
    with Sections() as sec:
        return _mencius_step_sections(sec, cfg, state, inbox, tick_inc,
                                      steady)


#: the takeover's sections and their gates, as models/minpaxos.py
#: ``replica_step_impl.recovery_gates`` has the leader's: each a
#: superset of "some replica's section would write something".
#:
#: * 7a answers PREPARE_INST rows and 7b adopts from
#:   PREPARE_INST_REPLY rows only.
#: * 10 sweeps, fills and re-drives only where ``stall_ticks >=
#:   cfg.noop_delay`` (the successor's threshold, the lowest), read
#:   after section 8 has set the counter to its old value plus
#:   ``tick_inc`` (1 wherever whole rounds are stepped) or to 0. The
#:   reset that closes an episode is not part of it and always runs.
#:
#: SKIP rows (section 4) are the normal path of an uneven load, not
#: recovery: a steady step keeps them.
mencius_step_impl.recovery_gates = {
    "px.takeover_phase1": lambda cfg, states, present: (
        present(MsgKind.PREPARE_INST) | present(MsgKind.PREPARE_INST_REPLY)),
    "px.takeover": lambda cfg, states, present: (
        # paxlint: disable=wall-honesty -- a bound on it, no update
        states.stall_ticks + 1 >= cfg.noop_delay).any(),
}


def _mencius_step_sections(sec, cfg, state, inbox, tick_inc, steady):
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
    own_mask = jnp.mod(idx_abs, R) == me

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
    csum_p = jnp.cumsum(is_propose.astype(jnp.int32))
    prefix = csum_p - 1
    slots_p = state.crt_own + R * prefix
    rel_p = slots_p - state.window_base
    fits = is_propose & (rel_p >= 0) & (rel_p < S)
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
             & (rank_p < csum_p[-1]))
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
    n_prop = jnp.where(fits, 1, 0).sum()
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
    acc_pre = (
        is_accept & in_win_a
        & (owner_ok | (inbox.ballot > 0))
        & (inbox.ballot >= ballot_a)
        & (status_a < COMMITTED)
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
    owner_of = jnp.mod(idx_abs, R)
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
    rep_row = (is_accept | is_areply | is_commit) & (inbox.src >= 0)
    rep_src = jnp.where(rep_row, jnp.clip(inbox.src, 0, R - 1), R)
    pc_seen = jnp.full(R + 1, jnp.int32(-(2 ** 30))).at[rep_src].max(
        inbox.last_committed)
    replied = pc_seen[:R] > -(2 ** 30)
    state = state._replace(
        votes=state.votes | pack_vote_bits(
            vote_cov & drv_slot[:, None]),
        peer_commits=jnp.where(replied, pc_seen[:R], state.peer_commits))

    sec("px.commit_rows")
    # ---- 6. COMMIT rows (explicit commit transfer, bcastCommit) ----
    rel_c, in_win_c = _rel(state, inbox.inst, S)
    com_ok = is_commit & in_win_c
    win_c, hit_c = slot_winner(S, rel_c, com_ok)
    state = slot_write(state, win_c, hit_c)._replace(
        status=jnp.where(hit_c, jnp.maximum(state.status, COMMITTED),
                         state.status),
        # any COMMIT row advances crt_inst by both its inst and its
        # piggybacked sender frontier (last_committed): a healing
        # laggard otherwise thinks the log ends at each served chunk,
        # in_flight drops, and its takeover sweep stops one chunk in
        crt_inst=jnp.maximum(
            state.crt_inst,
            jnp.max(jnp.where(
                is_commit,
                jnp.maximum(inbox.inst, inbox.last_committed), -1)) + 1),
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
        rel_pi, in_win_pi = _rel(state, inbox.inst, S)
        rel_pi_safe = jnp.minimum(rel_pi, S - 1)
        pi_answer = is_pinst & (in_win_pi | (inbox.inst >= state.crt_inst))
        pi_com = pi_answer & in_win_pi & (state.status[rel_pi_safe] >= COMMITTED)
        pi_occ = (pi_answer & ~pi_com & in_win_pi
                  & (state.status[rel_pi_safe] >= ACCEPTED))
        pi_val = pi_com | pi_occ
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
                             jnp.where(pi_answer, NO_BALLOT, out.ballot)),
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
                                out.client_id),
        )
        dst = jnp.where(pi_answer, inbox.src, dst)

        # 7b. collect PREPARE_INST_REPLY answers (mine): pvotes + adoption
        rel_v, in_win_v = _rel(state, inbox.inst, S)
        rel_v_safe = jnp.minimum(rel_v, S - 1)
        pv_ok = (is_pir & (inbox.last_committed == state.takeover_ballot)
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
    ta_ok = ((state.tk_anchor >= 0) & (ta_rel >= 0) & (ta_rel < S)
             & (ta_status >= COMMITTED)
             & (ta_slot["ballot"] > 0)
             & (jnp.mod(ta_slot["ballot"], 16) == me))
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
        do_tk = (in_flight
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
        redrive = (do_tk & in_tk_span & (state.status == ACCEPTED)
                   & ((state.ballot == tb) | (pv_cnt >= quorum1)))
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
    # takeover episode ends when the frontier moves again
    state = state._replace(
        takeover_ballot=jnp.where(advanced, jnp.int32(NO_BALLOT),
                                  state.takeover_ballot))

    sec("px.outbox")
    out = _concat_rows(_concat_rows(_concat_rows(_concat_rows(_concat_rows(
        _concat_rows(_concat_rows(out, skip_row), cb), ta), rt), cu), tk), rd)
    dst = jnp.concatenate([
        dst,
        jnp.full(1, -1, jnp.int32),    # skip broadcast
        jnp.full(K, -1, jnp.int32),    # own-commit broadcast
        jnp.full(K2b, -1, jnp.int32),  # takeover-commit announce
        jnp.full(K3, -1, jnp.int32),   # own-accept retry broadcast
        jnp.full(K4, cu_peer, jnp.int32),  # catch-up -> lagging peer
        jnp.full(K2, -1, jnp.int32),   # takeover sweep
        jnp.full(K2, -1, jnp.int32),   # takeover re-drive
    ])

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
