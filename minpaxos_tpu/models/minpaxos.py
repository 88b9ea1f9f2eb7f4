"""MinPaxos (global-ballot stable-leader Multi-Paxos) as a batched
array state machine.

Counterpart of reference src/bareminpaxos/bareminpaxos.go — the thesis
protocol: ONE global ballot covers every instance (one Prepare round
elects a leader for the whole log, bareminpaxos.go:394-446), Accepts
piggyback the leader's commit frontier (``LastCommitted``) so there is
no Commit broadcast on the hot path (SURVEY.md section 3.2), and a
follower that falls behind is healed with explicit catch-up rows.

The reference advances one instance per goroutine event
(bareminpaxos.go:292-381). Here one jitted ``replica_step`` consumes a
fixed-capacity batch of messages (any mix of kinds) and advances the
whole log window with branch-free masked array ops:

* propose handling = prefix-sum slot assignment + scatter
  (vs handlePropose bareminpaxos.go:617-710);
* accept handling = masked ballot-compare + scatter + per-row acks
  (vs handleAccept :753-806);
* vote counting = boolean scatter into a [S, R] vote table
  (vs handleAcceptReply :1014-1064);
* commit frontier = one cumulative scan (vs updateCommittedUpTo
  :387-392);
* execution = the parallel KV engine applying a committed range
  (vs executeCommands :1066-1098).

Message routing, durability, and ragged catch-up stay on the host
(runtime/) or in the pod-mode cluster composition (models/cluster.py):
the reference's cold paths deliberately stay off the device
(SURVEY.md section 7.4).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

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
from minpaxos_tpu.ops.scan import commit_frontier
from minpaxos_tpu.ops.sections import Sections
from minpaxos_tpu.ops.winner import gather_cols, read_cols
from minpaxos_tpu.wire.messages import MsgKind

# Log-slot statuses (reference minpaxosproto.go:8-15 plus EXECUTED,
# which the reference tracks implicitly via the exec cursor).
NONE, PREPARING, PREPARED, ACCEPTED, COMMITTED, EXECUTED = range(6)

NO_BALLOT = -1


def make_ballot(counter, replica_id):
    """(counter << 4) | id — reference bareminpaxos.go:383-385; caps
    replicas at 16, like the reference."""
    return counter * 16 + replica_id


class MinPaxosConfig(NamedTuple):
    """Static (compile-time) protocol parameters."""

    n_replicas: int = 3
    window: int = 1 << 16  # log slots resident on device (ref: 15M preallocated)
    inbox: int = 4096  # message rows per step
    exec_batch: int = 4096  # max slots executed per step
    kv_pow2: int = 16  # KV table capacity 2**kv_pow2
    catchup_rows: int = 64  # catch-up ACCEPT rows per step (CatchUpLog batch)
    recovery_rows: int = 256  # uncommitted-suffix rows shipped per PREPARE
    noop_delay: int = 8  # stalled steps before a gap slot is no-op filled
    # Slide the window past the executed prefix each step, making the
    # log unbounded like the reference's 15M preallocation
    # (bareminpaxos.go:95) without unbounded device memory. Every
    # replica retains up to `retention` executed slots so whoever is
    # (or becomes) leader can heal laggards from resident state
    # (CatchUpLog). LIMIT of catch-up rows: a replica lagging beyond
    # `retention` is out of their reach and is healed by a transfer of
    # executed state instead. The served path ships it from the durable
    # log (runtime/ stable store: COMMIT frames, or the retained
    # snapshot once the log is truncated; the reference's replay,
    # bareminpaxos.go:122-161); the fused pod dispatches ship the
    # leader's KV table on the device (`state_transfer` below,
    # parallel/sharded.py `transfer_round`). Only a path with neither
    # (a pod stepped round by round through `sharded_step`, Mencius)
    # leaves such a laggard frozen, and it must not be elected leader
    # (the master elects the highest-frontier replica for this reason).
    # Retention covers the outages that are healed row by row; longer
    # ones cost one table copy.
    slide_window: bool = True
    retention: int = -1  # executed slots retained per replica; -1 = window//2
    # Gate the execute pipeline (sort/lookup/KV insert) behind
    # ``lax.cond`` so idle/accept-only ticks skip it. Right for the
    # event-driven TCP runtime (one replica per process, most ticks of
    # a serial op's path have nothing to execute: 1.75 -> 0.83 ms
    # minpaxos, 2.36 -> 0.98 ms mencius idle steps). WRONG under
    # ``vmap`` (pod/sharded composition): batched ``cond`` lowers to
    # ``select`` which evaluates BOTH branches, so the gate only adds
    # overhead there — cluster_step_impl (the choke point every
    # pod/sharded composition routes through) strips it at trace time
    # via ``cfg._replace(gate_exec=False)``; a new composition that
    # vmaps a *_step_impl directly must do the same.
    gate_exec: bool = True
    # Frontier-gossip cadence in ticks. 1 = gossip immediately on every
    # advance (right for the lock-step pod composition, where rounds
    # are synchronous and a gossip row costs nothing extra). The
    # event-driven TCP runtime sets ~4: there every gossip row WAKES
    # idle peers, and per-commit gossip cascaded each serial op into
    # ~4 extra process wakeups that serialized into commit latency on
    # small hosts (round-5 trace; cli/server.py -gossipticks).
    gossip_ticks: int = 1
    # Routing-fabric selector (static): "segmented" = the one-pass
    # segmented scatter (ops/segscatter.py — one segment-prefix-sum
    # over the pooled outbox rows, winner via rank-select
    # (ops/rankselect.py: vector compares since PR 29, after the chip
    # measured searchsorted's dependent gathers as the top device op
    # of both pod cells), 12 dense gathers; PR 11); "dense" = the
    # original per-destination vmap-over-R masked cumsum (kept for the
    # byte-equality pin).
    # Both produce byte-identical inboxes (tests/test_route_fabric.py).
    route_fabric: str = "segmented"
    # Protocol selector: False = MinPaxos (global ballot, commits learned
    # from the LastCommitted piggyback on Accepts — bareminpaxos.go hot
    # path, SURVEY.md 3.2); True = classic per-instance Multi-Paxos
    # (models/paxos.py): followers commit ONLY on explicit
    # Commit/CommitShort broadcasts (paxos.go:336-386, :522-575) and the
    # leader commits at each instance's own ballot (per-instance
    # bookkeeping, paxos.go:57-70). Static, so XLA specializes the
    # kernel per protocol.
    explicit_commit: bool = False
    # Flexible quorums (Flexible Paxos, PAPERS.md 1608.06696): phase-1
    # (prepare/leader-change + no-op-fill audits) and phase-2 (ACCEPT-
    # vote commit scans) quorum sizes. 0 = the majority default, so a
    # default-constructed config compiles the exact same thresholds as
    # before (byte-identical kernels, tests/test_kernel_golden.py).
    # Safety needs only q1 + q2 > n_replicas — certified at
    # construction by verify/quorum.py via the Cluster/server hosts
    # (the kernel itself never validates: verify/mc.py plants
    # non-intersecting mutants through these very fields).
    q1: int = 0
    q2: int = 0
    # Fast path (Fast Flexible Paxos, PAPERS.md 2008.02671): followers
    # accept client PROPOSEs directly (1 delivery before the leader's
    # ACCEPT broadcast) and fast-ack the leader, which counts a fast
    # ack only when its own slot assignment carries the same command
    # (value-fingerprint match) — mismatches fall back to the classic
    # path for free because the leader still broadcasts ACCEPTs and
    # same-ballot overwrite converges followers to the leader's value.
    # While fast_path is on, EVERY commit takes quorum_fast votes: the
    # leader-change sweep (7e) adopts same-ballot values by max-vballot
    # with an index tiebreak, so divergent same-ballot rows must never
    # coexist with a commit — unanimity (q_fast = n) guarantees the
    # committed value is on every replica any phase-1 quorum can see.
    # That trades liveness under failure (one dead replica stalls
    # commits until healed) for the 1-RTT happy path; classic (q1, q2)
    # configs remain the production shape.
    fast_path: bool = False
    q_fast: int = 0  # 0 = n_replicas (the only kernel-safe size here)

    @property
    def majority(self) -> int:
        return self.n_replicas // 2 + 1

    @property
    def quorum1(self) -> int:
        """Phase-1 threshold actually compiled into the kernels."""
        return self.q1 or self.n_replicas // 2 + 1

    @property
    def quorum2(self) -> int:
        """Phase-2 (commit) threshold actually compiled into the
        kernels (quorum_fast supersedes it while fast_path is on)."""
        return self.q2 or self.n_replicas // 2 + 1

    @property
    def quorum_fast(self) -> int:
        """Fast-path commit threshold; see the fast_path field note
        for why the kernel-safe size is n_replicas."""
        return self.q_fast or self.n_replicas


class MsgBatch(NamedTuple):
    """Fixed-capacity struct-of-arrays message batch (device side).

    kind==0 rows are padding. One row touches one log slot; wire frames
    map rows 1:1 (wire/messages.py design note #2).
    """

    kind: jnp.ndarray  # i32[M]
    src: jnp.ndarray  # i32[M] sender replica (-1 for clients)
    ballot: jnp.ndarray  # i32[M]
    inst: jnp.ndarray  # i32[M] absolute instance number
    last_committed: jnp.ndarray  # i32[M]
    op: jnp.ndarray  # i32[M]
    key_hi: jnp.ndarray
    key_lo: jnp.ndarray
    val_hi: jnp.ndarray
    val_lo: jnp.ndarray
    cmd_id: jnp.ndarray
    client_id: jnp.ndarray

    @staticmethod
    def empty(m: int) -> "MsgBatch":
        z = jnp.zeros(m, dtype=jnp.int32)
        return MsgBatch(*([z] * 12))


# What a slot write copies from its winning inbox row into the window:
# the columns a message and the replica's state share by name.
SLOT_FIELDS = ("ballot", "op", "key_hi", "key_lo", "val_hi", "val_lo",
               "cmd_id", "client_id")


class Outbox(NamedTuple):
    """Per-input-row responses: out row i is derived from inbox row i.

    dst == -1 means broadcast to all peers; otherwise a replica id.
    PROPOSE_REPLY rows are addressed to clients (host resolves the
    connection from client_id).

    ACCEPT_REPLY rows are run-length compressed: only the first row of
    each maximal contiguous (sender, ok, consecutive inst) run is live,
    with cmd_id carrying the run length (the wire ``count`` — this
    repo's extension to AcceptReply, minpaxosproto.go:75-80, modeled on
    CommitShort's Instance+Count range, paxosproto.go:50-54); the
    other rows of the run are padding.
    ``acked`` therefore exists as the durability hook: bool per INBOX
    row, True where an inbox ACCEPT row was accepted (or re-acked as
    identical-committed) this step — the host's _persist reads it
    instead of matching outbox rows 1:1 (runtime/replica.py).
    """

    msgs: MsgBatch
    dst: jnp.ndarray  # i32[M]
    acked: jnp.ndarray  # bool[M_in] over inbox rows


class ExecResult(NamedTuple):
    """Newly executed slots this step (for -dreply replies and reads)."""

    lo: jnp.ndarray  # i32: first executed absolute slot
    count: jnp.ndarray  # i32
    val_hi: jnp.ndarray  # i32[E]
    val_lo: jnp.ndarray  # i32[E]
    found: jnp.ndarray  # bool[E]
    op: jnp.ndarray  # i32[E] command op per executed slot
    cmd_id: jnp.ndarray  # i32[E]
    client_id: jnp.ndarray  # i32[E]


class ReplicaState(NamedTuple):
    """Everything one replica owns, as device arrays."""

    # log window [S]. Width matters: these arrays are the dominant
    # HBM traffic of a step (PERF.md), so status/op are u8 (values
    # 0..5) and votes/pvotes are packed u16 bitmasks (R <= 16 by the
    # ballot encoding) instead of i32 / bool[S, R].
    ballot: jnp.ndarray  # i32: accepted ballot per slot
    status: jnp.ndarray  # u8
    op: jnp.ndarray  # u8
    key_hi: jnp.ndarray
    key_lo: jnp.ndarray
    val_hi: jnp.ndarray
    val_lo: jnp.ndarray
    cmd_id: jnp.ndarray
    client_id: jnp.ndarray
    votes: jnp.ndarray  # u16[S]: bit r = replica r acked this slot
    # scalars
    me: jnp.ndarray  # i32
    window_base: jnp.ndarray  # i32 absolute slot of window index 0
    crt_inst: jnp.ndarray  # i32 next unassigned absolute slot
    committed_upto: jnp.ndarray  # i32 absolute, -1 before any commit
    executed_upto: jnp.ndarray  # i32
    default_ballot: jnp.ndarray  # i32 promised/current global ballot
    max_recv_ballot: jnp.ndarray  # i32
    leader_id: jnp.ndarray  # i32 (-1 unknown)
    prepared: jnp.ndarray  # bool: leader has prepare majority
    prepare_oks: jnp.ndarray  # bool[R]
    # leader's knowledge of each peer's commit frontier, fed by the
    # last_committed piggyback on replies (reference peerCommits,
    # bareminpaxos.go:80, :1050) — drives catch-up targeting
    peer_commits: jnp.ndarray  # i32[R]
    tick: jnp.ndarray  # i32 step counter (round-robin catch-up target)
    stall_ticks: jnp.ndarray  # i32 consecutive steps the frontier stalled
    # new-leader value discovery (per-instance phase 1): which replicas
    # answered PREPARE_INST for each slot at the CURRENT ballot. A gap
    # slot may be no-op filled ONLY once a majority has answered "no
    # value" — the safety condition the reference approximates with its
    # full CatchUpLog shipping (bareminpaxos.go:488-513, :912-966)
    pvotes: jnp.ndarray  # u16[S]: bit r = replica r answered phase 1
    rec_cursor: jnp.ndarray  # i32 next slot the leader's sweep requests
    # log tip at the moment this leader's prepare quorum completed:
    # slots at/above it were created by THIS tenure's own proposals and
    # never need phase-1 discovery — without the bound, every new
    # proposal re-armed the sweep for its own in-flight slot and each
    # serial op shipped pointless PREPARE_INST broadcasts (round-5
    # trace). Tracks crt_inst while unprepared (so election-time
    # discovery keeps extending it), freezes once prepared; the
    # stalled-frontier rescan ignores it (full-range safety net).
    tenure_start: jnp.ndarray  # i32
    gossip_upto: jnp.ndarray  # i32 frontier as of the last gossip row
    kv: KVState

    @property
    def is_leader(self):
        return self.leader_id == self.me


def init_replica(cfg: MinPaxosConfig, me: int) -> ReplicaState:
    s, r = cfg.window, cfg.n_replicas

    def zi():
        # distinct buffers per field: donation (replica_step
        # donate_argnums) rejects the same buffer appearing twice
        return jnp.zeros(s, dtype=jnp.int32)

    return ReplicaState(
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
        me=jnp.int32(me),
        window_base=jnp.int32(0),
        crt_inst=jnp.int32(0),
        committed_upto=jnp.int32(-1),
        executed_upto=jnp.int32(-1),
        default_ballot=jnp.int32(NO_BALLOT),
        max_recv_ballot=jnp.int32(NO_BALLOT),
        leader_id=jnp.int32(-1),
        prepared=jnp.asarray(False),
        prepare_oks=jnp.zeros(r, dtype=bool),
        peer_commits=jnp.full(r, -1, dtype=jnp.int32),
        tick=jnp.int32(0),
        stall_ticks=jnp.int32(0),
        pvotes=jnp.zeros(s, dtype=jnp.uint16),
        rec_cursor=jnp.int32(0),
        tenure_start=jnp.int32(0),
        gossip_upto=jnp.int32(-1),
        kv=kv_init(cfg.kv_pow2),
    )


def become_leader(cfg: MinPaxosConfig, state: ReplicaState) -> tuple[ReplicaState, MsgBatch]:
    """Start an election: bump to a fresh unique ballot and emit a
    broadcast PREPARE row.

    Counterpart of bcastPrepare (bareminpaxos.go:394-446) triggered by
    initial boot (:286-290) or the master's BeTheLeader RPC (:220-223).
    Unlike the reference's BeTheLeader (which flips the flag without
    re-preparing — SURVEY.md section 3.4 note), this always runs a real
    Prepare round; `prepared` gates proposals until majority.
    """
    counter = state.max_recv_ballot // 16 + 1
    new_ballot = make_ballot(counter, state.me)
    state = state._replace(
        default_ballot=new_ballot,
        max_recv_ballot=jnp.maximum(state.max_recv_ballot, new_ballot),
        # .copy(): leader_id must not alias the me buffer — the runtime
        # donates the state to the jitted step, which rejects one
        # buffer appearing twice
        leader_id=state.me.copy(),
        prepared=jnp.asarray(False),
        prepare_oks=jnp.zeros(cfg.n_replicas, dtype=bool).at[state.me].set(True),
        # fresh ballot -> stale phase-1 answers must not count; restart
        # the per-instance discovery sweep at our commit frontier
        pvotes=jnp.zeros(cfg.window, dtype=jnp.uint16),
        rec_cursor=state.committed_upto + 1,
        # fresh tenure: re-track the tip until the new prepare quorum
        tenure_start=state.crt_inst + 0,
    )
    out = MsgBatch.empty(1)
    out = out._replace(
        kind=jnp.full(1, int(MsgKind.PREPARE), jnp.int32),
        src=jnp.full(1, state.me, jnp.int32),
        ballot=jnp.full(1, new_ballot, jnp.int32),
        last_committed=jnp.full(1, state.committed_upto, jnp.int32),
    )
    return state, out


def _concat_rows(a: MsgBatch, b: MsgBatch) -> MsgBatch:
    return jax.tree_util.tree_map(
        lambda x, y: jnp.concatenate([x, y], axis=-1), a, b)


def _rel(state: ReplicaState, inst, window: int):
    """Absolute instance -> window index; out-of-window -> `window`
    (a drop sentinel for scatter mode='drop')."""
    rel = inst - state.window_base
    ok = (rel >= 0) & (rel < window)
    return jnp.where(ok, rel, window), ok


def replica_step_impl(
    cfg: MinPaxosConfig, state: ReplicaState, inbox: MsgBatch,
    tick_inc=1, gates: dict | None = None,
) -> tuple[ReplicaState, Outbox, ExecResult]:
    """Advance one replica by one batch of messages (pure, unjitted —
    models/cluster.py vmaps this over the replica axis).

    Handles every message kind in one fused, branch-free pass; see
    module docstring for the reference-call mapping.

    ``tick_inc``: wall-clock ticks this step represents. The TCP
    runtime's fused burst path (runtime/replica.py) runs k protocol
    substeps inside ONE host tick; crediting each substep a full tick
    would make the stall/retry counters reach their thresholds k times
    faster than wall time — exactly the duplicate-accept churn the
    round-5 threshold tuning removed. The fused path passes 1 for the
    first substep and 0 for the rest; every other caller uses the
    default 1.

    ``gates``: this round's ``recovery_gates`` (below), by section, as
    bool scalars that are NOT batched by the vmaps that batch the step
    (parallel/sharded.py ``sharded_round`` hands them in): a section
    whose gate is shut is skipped by a ``lax.cond``, which on a
    per-replica predicate would lower to a select and run both sides.
    Every other caller passes none and runs every section in line.
    """
    with Sections() as sec:
        return _replica_step_sections(sec, cfg, state, inbox, tick_inc,
                                      gates)


#: stalled ticks after which the leader re-sends its in-flight slots
#: (7d, whose comment says why 4)
RETRY_STALL_TICKS = 4

#: the recovery sections of the step that a caller can gate, by
#: ``px.*`` scope, each with its GATE: ``gate(cfg, states, present)``
#: says, from what can be seen before a step, whether the section can
#: have work in it, as one bool scalar over ALL the replicas of
#: ``states`` (any leading axes); ``present(kind)``: a row of that kind
#: is in some inbox. A gate is a SUPERSET of "some replica's section
#: would write something": it may be open needlessly and is never shut
#: on a section that matters, so the bytes are the same either way
#: (tests/test_route_fabric.py holds it to that).
#:
#: 7d acts only where ``stall_ticks >= RETRY_STALL_TICKS``, read AFTER
#: 7b has set the counter to its old value plus ``tick_inc`` (1
#: wherever whole rounds are stepped) or to 0: no counter one short of
#: the threshold before the step, none at it inside. (1c and 2b, whose
#: gates would be a PREPARE_INST_REPLY / PREPARE_INST row present, and
#: 7e are not gated: PERF.md section 7 rows 14 and 16.)
replica_step_impl.recovery_gates = {
    "px.retry": lambda cfg, states, present: (
        # paxlint: disable=wall-honesty -- a bound on it, no update
        states.stall_ticks + 1 >= RETRY_STALL_TICKS).any(),
}
#: the step skips them itself, by a conditional on ``gates``
replica_step_impl.takes_gates = True


def _replica_step_sections(sec, cfg, state, inbox, tick_inc, gates):
    """``replica_step_impl``'s body; ``sec(name)`` opens the ``px.*``
    scope of the section that follows (ops/sections.py)."""
    S, R = cfg.window, cfg.n_replicas
    M = inbox.kind.shape[0]  # actual batch rows (pending + ext concat)
    # flexible quorums (config field note): phase-1 sites take q1,
    # commit scans take q2 — both equal cfg.majority by default; the
    # fast path commits at quorum_fast (unanimous by default)
    quorum1 = cfg.quorum1
    quorum2 = cfg.quorum_fast if cfg.fast_path else cfg.quorum2
    k = inbox.kind
    is_prep = k == int(MsgKind.PREPARE)
    is_prep_reply = k == int(MsgKind.PREPARE_REPLY)
    is_accept = k == int(MsgKind.ACCEPT)
    is_accept_reply = k == int(MsgKind.ACCEPT_REPLY)
    is_commit = k == int(MsgKind.COMMIT)
    is_cshort = k == int(MsgKind.COMMIT_SHORT)
    is_propose = k == int(MsgKind.PROPOSE)

    out = MsgBatch.empty(M)
    dst = jnp.full(M, -1, jnp.int32)

    sec("px.prepare")
    # ---- 1. PREPARE (handlePrepare bareminpaxos.go:712-751) ----
    # Adopt the highest proposed ballot if it beats our promise.
    prep_ballot = jnp.max(jnp.where(is_prep, inbox.ballot, NO_BALLOT))
    any_prep = is_prep.any()
    prep_src = inbox.src[jnp.argmax(jnp.where(is_prep, inbox.ballot, NO_BALLOT))]
    adopt = any_prep & (prep_ballot > state.default_ballot)
    new_default = jnp.where(adopt, prep_ballot, state.default_ballot)
    new_leader = jnp.where(adopt, prep_src, state.leader_id)
    prepared = jnp.where(adopt, False, state.prepared)
    state = state._replace(
        default_ballot=new_default,
        leader_id=new_leader,
        prepared=prepared,
        max_recv_ballot=jnp.maximum(state.max_recv_ballot, prep_ballot),
    )
    # reply per PREPARE row (ok iff its ballot is the adopted one)
    prep_ok = is_prep & (inbox.ballot >= state.default_ballot)
    out = out._replace(
        kind=jnp.where(is_prep, int(MsgKind.PREPARE_REPLY), out.kind),
        src=jnp.where(is_prep, state.me, out.src),
        ballot=jnp.where(is_prep, state.default_ballot, out.ballot),
        # inst carries our highest known instance (for leader catch-up)
        inst=jnp.where(is_prep, state.crt_inst, out.inst),
        last_committed=jnp.where(is_prep, state.committed_upto, out.last_committed),
        op=jnp.where(is_prep, prep_ok.astype(jnp.int32), out.op),  # op = ok flag
    )
    dst = jnp.where(is_prep, inbox.src, dst)

    sec("px.phase1_reply")
    # ---- 1c. PREPARE_INST_REPLY: phase-1 answers for the leader's
    # per-instance discovery sweep (see 1e/7e). Two effects:
    # * value adoption — the highest-vballot reported value is adopted
    #   (handlePrepareReply's log-suffix merge, bareminpaxos.go:934-947,
    #   and classic paxos.go:577-612 semantics);
    # * pvotes — EVERY current-ballot answer (value or "empty") counts
    #   toward the majority that gates no-op gap fill (7d).
    # PR 11: the PIR and ACCEPT sections' slot WRITES are fused into
    # one keyed winner pass (write A below) — the predicates here stay
    # verbatim, and the ACCEPT section reads PIR's would-be writes
    # through closed forms (ballot1) instead of a materialized store,
    # so the fused kernel is byte-identical to the sequential one
    # (golden fixtures pin it). ----
    is_pir = k == int(MsgKind.PREPARE_INST_REPLY)
    # packed-bitmask identities for this replica / per-row senders
    me_bit = (jnp.int32(1) << state.me).astype(jnp.uint16)
    rows_m = jnp.arange(M, dtype=jnp.int32)
    # every inst-addressed section (1c/2/2b/3) shares one window
    # translation of inbox.inst — computed once
    rel_i, in_win_i = _rel(state, inbox.inst, S)
    rel_i_safe = jnp.minimum(rel_i, S - 1)
    pv_ok = (
        is_pir
        & state.is_leader
        & (inbox.last_committed == state.default_ballot)  # context tag
        & in_win_i
    )
    state = state._replace(
        pvotes=state.pvotes | scatter_vote_bits(S, rel_i, inbox.src,
                                                pv_ok, R))
    # a row's slot is read ONCE per version of STATE, all the columns
    # a section compares together (ops/winner.py read_cols; until PR 36
    # an element gather a column: 1c, 2, 2b, 7c, 7e and 8). Before
    # write A: status and ballot, which 1c and 2 both test
    status0_i, ballot0_i = read_cols(rel_i_safe,
                                     (state.status, state.ballot))
    pir_ok = (
        pv_ok
        & (status0_i < COMMITTED)
        & (inbox.ballot > ballot0_i)
    )
    # max-vballot wins per slot within the batch
    vb_max = jnp.full(S + 1, NO_BALLOT, jnp.int32).at[
        jnp.where(pir_ok, rel_i, S)].max(inbox.ballot, mode="drop")
    vb_max_i, = read_cols(rel_i_safe, (vb_max[:S],))
    pir_win = pir_ok & (inbox.ballot == vb_max_i)
    # PIR's would-be ballot write as a closed form: a hit slot's new
    # ballot IS vb_max (pir_win requires equality), and pir_ok requires
    # inbox.ballot > state.ballot[rel] >= NO_BALLOT, so vb_max >
    # NO_BALLOT detects hits exactly — no winner scatter needed for
    # the view the ACCEPT predicates read, and no further read: a
    # row's view of its slot follows from the two it already holds
    ballot1_i = jnp.where(vb_max_i > NO_BALLOT, vb_max_i, ballot0_i)

    sec("px.accept")
    # ---- 2. ACCEPT (handleAccept :753-806) ----
    # Seeing a higher ballot in an ACCEPT also deposes us: a leader
    # that missed the new leader's PREPARE must stop serving, or two
    # leaders could emit conflicting ACCEPTs at the same ballot.
    acc_max_ballot = jnp.max(jnp.where(is_accept, inbox.ballot, NO_BALLOT))
    deposed = acc_max_ballot > state.default_ballot
    acc_max_src = inbox.src[
        jnp.argmax(jnp.where(is_accept, inbox.ballot, NO_BALLOT))]
    state = state._replace(
        leader_id=jnp.where(deposed, acc_max_src, state.leader_id),
        prepared=jnp.where(deposed, False, state.prepared),
    )
    acc_pre = (
        is_accept
        & in_win_i
        & (inbox.ballot >= state.default_ballot)
        & (inbox.ballot >= ballot1_i)  # post-PIR ballot view
        & (status0_i < COMMITTED)
    )
    # duplicate rows for one slot (old + new leader in one pooled
    # inbox): only the max-ballot row may write, or per-field scatter
    # could tear the slot (ballot from one row, value from another)
    ab_max = jnp.full(S + 1, NO_BALLOT, jnp.int32).at[
        jnp.where(acc_pre, rel_i, S)].max(inbox.ballot, mode="drop")
    ab_max_i, = read_cols(rel_i_safe, (ab_max[:S],))
    acc_ok = acc_pre & (inbox.ballot == ab_max_i)

    sec("px.slot_write_a")
    # ---- fused slot write A (PIR + ACCEPT) ----
    # One keyed winner scatter replaces the two sections' slot_winner
    # passes and 2x9 column writes: key = section*M + row, so an
    # ACCEPT row beats any PIR row on its slot (the sequential code's
    # overwrite order) and the max row index wins within a section
    # (slot_winner's tie-break). Each inbox row belongs to at most one
    # section (kind-exclusive), so the key decodes unambiguously.
    okA = pir_win | acc_ok
    keyA = jnp.full(S + 1, -1, jnp.int32).at[
        jnp.where(okA, rel_i, S)].max(
        jnp.where(acc_ok, M + rows_m, rows_m), mode="drop")[:S]
    hitA = keyA >= 0
    secA_acc = keyA >= M  # winner came from the ACCEPT section
    rowA = jnp.mod(keyA, M)  # valid index even for keyA == -1 (masked)
    # the winning row is fetched ONCE a pass, all its columns together
    # (ops/winner.py gather_cols; until PR 34 nine element gathers by
    # rowA here and eight by rowB in write B). The ninth column is the
    # sender's bit, fetched into votes
    slot_cols = [getattr(inbox, f) for f in SLOT_FIELDS]
    src_bit = jnp.int32(1) << jnp.clip(inbox.src, 0, R - 1)
    *colsA, votesA = gather_cols(
        rowA, hitA, slot_cols + [src_bit],
        [getattr(state, f) for f in SLOT_FIELDS] + [state.votes])
    state = state._replace(
        **dict(zip(SLOT_FIELDS, colsA)),
        status=jnp.where(hitA, jnp.uint8(ACCEPTED), state.status),
        # PIR adoption votes for itself; accepting a newer ballot
        # supersedes any older votes with the sender's bit
        votes=jnp.where(hitA & ~secA_acc, me_bit, votesA),
        default_ballot=jnp.maximum(state.default_ballot, acc_max_ballot),
        max_recv_ballot=jnp.maximum(state.max_recv_ballot, acc_max_ballot),
        # followers track the log extent so a later election starts
        # assigning after everything they've seen (the reference keeps
        # crtInstance on followers the same way)
        crt_inst=jnp.maximum(
            state.crt_inst,
            jnp.maximum(jnp.max(jnp.where(pir_ok, inbox.inst, -1)),
                        jnp.max(jnp.where(acc_ok, inbox.inst, -1))) + 1),
    )
    sec("px.accept_ack")
    # A re-ACCEPT of a slot we already hold COMMITTED is acked (not
    # NACKed) iff it carries the identical decided value: commitment is
    # final, so voting for the decided value again is always safe, and
    # a new leader re-driving slots it learned from a partial quorum
    # needs these votes to reach majority (second half of the
    # elected-laggard livelock fix; value mismatch still NACKs).
    # after write A: the nine columns this section compares and 2b
    # echoes, fetched once for both (no slot is written in between)
    status_i, *slot_i = read_cols(
        rel_i_safe,
        (state.status,) + tuple(getattr(state, f) for f in SLOT_FIELDS))
    slot_i = dict(zip(SLOT_FIELDS, slot_i))
    acc_com_match = (
        is_accept & in_win_i
        & (status_i >= COMMITTED)
        & (slot_i["op"] == inbox.op)
        & (slot_i["key_hi"] == inbox.key_hi)
        & (slot_i["key_lo"] == inbox.key_lo)
        & (slot_i["val_hi"] == inbox.val_hi)
        & (slot_i["val_lo"] == inbox.val_lo)
        & (slot_i["cmd_id"] == inbox.cmd_id)
        & (slot_i["client_id"] == inbox.client_id)
    )
    # ack every ACCEPT row (ok=0 NACK carries our promised ballot),
    # run-length compressed: one reply row per maximal contiguous
    # (sender, ok, consecutive inst) run instead of one per slot, with
    # cmd_id = run length (wire `count` — our AcceptReply extension,
    # modeled on CommitShort's range form, paxosproto.go:50-54). The
    # leader consumes the range in step 6. This kills the round-3
    # ack-row explosion — (R-1)*p per-slot ack rows per round through
    # the routing fabric collapse to ~1 per follower, which is what
    # lets the inbox capacity (and every [M]-shaped computation in this
    # kernel) be sized to ~p instead of ~R*p.
    ack_ok_row = acc_ok | acc_com_match
    run_start, run_len = compress_ack_runs(
        is_accept, inbox.src, inbox.inst, ack_ok_row)
    out = out._replace(
        kind=jnp.where(is_accept,
                       jnp.where(run_start, int(MsgKind.ACCEPT_REPLY), 0),
                       out.kind),
        src=jnp.where(is_accept, state.me, out.src),
        inst=jnp.where(is_accept, inbox.inst, out.inst),
        ballot=jnp.where(is_accept, state.default_ballot, out.ballot),
        op=jnp.where(is_accept, ack_ok_row.astype(jnp.int32),
                     out.op),  # op = ok flag
        cmd_id=jnp.where(is_accept, run_len, out.cmd_id),  # run length
        last_committed=jnp.where(is_accept, state.committed_upto, out.last_committed),
    )
    dst = jnp.where(is_accept, inbox.src, dst)

    # follower commit frontier from piggybacked LastCommitted
    # (bareminpaxos.go:856-910 semantics without a Commit broadcast).
    # Only rows at our current global ballot count: after a leader
    # change, slots accepted under an older ballot must be re-confirmed
    # by the new leader's catch-up before they may commit (the
    # reference gets this implicitly from its single-leader stream
    # ordering; with batched mixed-kind inboxes it must be explicit).
    # COMMIT_SHORT rows carry the frontier in last_committed (the
    # leader's explicit frontier broadcast, see step 9).
    # Classic mode (explicit_commit): the ACCEPT piggyback is NOT a
    # commit signal — followers learn commitment only from explicit
    # Commit/CommitShort (paxos.go:522-575); MinPaxos's defining trick
    # (bareminpaxos's LastCommitted-on-Accept) is exactly what classic
    # paxos doesn't do.
    committish = ((is_commit | is_cshort) if cfg.explicit_commit
                  else (is_accept | is_commit | is_cshort))
    lc = jnp.max(jnp.where(committish
                           & (inbox.ballot >= state.default_ballot),
                           inbox.last_committed, -1))

    sec("px.prepare_inst")
    # ---- 2b. PREPARE_INST (classic per-instance phase 1; the pull
    # side of new-leader value discovery — see 7e) ----
    # Answer ONLY truthfully: slots in our window answer with contents
    # (vballot + value) or an explicit "empty" marker (vballot ==
    # NO_BALLOT); slots at/beyond crt_inst are provably empty here;
    # slots below window_base were EXECUTED and slid out — we refuse to
    # answer (claiming "empty" for a slot we committed could let the
    # sweep no-op fill an acked slot). The promise is the global
    # default_ballot, already raised by steps 1-2.
    is_pinst = k == int(MsgKind.PREPARE_INST)
    in_win_pi = in_win_i  # shared inst->window translation
    pi_answer = is_pinst & (inbox.ballot >= state.default_ballot) & (
        in_win_pi | (inbox.inst >= state.crt_inst))
    # Slots we already hold COMMITTED answer with a COMMIT row instead
    # of a phase-1 reply: this is committed-state transfer TO a behind
    # leader — the reference's CatchUpLog-in-PrepareReply wholesale
    # adoption (bareminpaxos.go:488-513, :912-966). Without it, an
    # elected laggard adopts peer values as ACCEPTED, re-broadcasts
    # ACCEPTs, and the committed peers NACK every one (acc_pre requires
    # status < COMMITTED) — a permanent livelock at frontier -1.
    # the slot's contents: section 2's read after write A (status_i,
    # slot_i), the same index and the same version of STATE
    pi_com = pi_answer & in_win_pi & (status_i >= COMMITTED)
    pi_occ = pi_answer & ~pi_com & in_win_pi & (status_i >= ACCEPTED)
    pi_val = pi_com | pi_occ
    out = out._replace(
        kind=jnp.where(pi_com, int(MsgKind.COMMIT),
                       jnp.where(pi_answer & ~pi_com,
                                 int(MsgKind.PREPARE_INST_REPLY), out.kind)),
        src=jnp.where(pi_answer, state.me, out.src),
        inst=jnp.where(pi_answer, inbox.inst, out.inst),
        ballot=jnp.where(pi_val, slot_i["ballot"],
                         jnp.where(pi_answer, NO_BALLOT, out.ballot)),
        last_committed=jnp.where(pi_com, state.committed_upto,
                                 jnp.where(pi_answer, inbox.ballot,
                                           out.last_committed)),
        op=jnp.where(pi_val, slot_i["op"],
                     jnp.where(pi_answer, 0, out.op)),
        key_hi=jnp.where(pi_val, slot_i["key_hi"], out.key_hi),
        key_lo=jnp.where(pi_val, slot_i["key_lo"], out.key_lo),
        val_hi=jnp.where(pi_val, slot_i["val_hi"], out.val_hi),
        val_lo=jnp.where(pi_val, slot_i["val_lo"], out.val_lo),
        cmd_id=jnp.where(pi_val, slot_i["cmd_id"], out.cmd_id),
        client_id=jnp.where(pi_val, slot_i["client_id"], out.client_id),
    )
    dst = jnp.where(pi_answer, inbox.src, dst)
    # track the sweep's extent so a later election here starts after it
    state = state._replace(
        crt_inst=jnp.maximum(
            state.crt_inst,
            jnp.max(jnp.where(is_pinst, inbox.inst, -1)) + 1))

    sec("px.commit_rows")
    # ---- 3. COMMIT rows (explicit per-slot commit, cold path) ----
    # A replica with no known leader (revived with an empty store into
    # a quiescent cluster) adopts the committer as its leader hint, so
    # the frontier-report gossip (7b) has a destination and host-side
    # catch-up can make progress instead of livelocking.
    com_any = (is_commit | is_cshort).any()
    com_bal = jnp.max(jnp.where(is_commit | is_cshort, inbox.ballot, NO_BALLOT))
    com_src = inbox.src[
        jnp.argmax(jnp.where(is_commit | is_cshort, inbox.ballot, NO_BALLOT))]
    adopt_com = com_any & (state.leader_id < 0) & (
        com_bal >= state.default_ballot)
    state = state._replace(
        leader_id=jnp.where(adopt_com, com_src, state.leader_id))
    com_ok = is_commit & in_win_i
    # slot writes DEFERRED into fused write B (after 5 — commit and
    # propose target provably disjoint slots this batch, see below);
    # the log-extent update must happen NOW, before 5 assigns slots
    state = state._replace(
        crt_inst=jnp.maximum(
            state.crt_inst, jnp.max(jnp.where(com_ok, inbox.inst, -1)) + 1),
    )

    sec("px.prepare_reply")
    # ---- 4. PREPARE_REPLY (handlePrepareReply :912-966) ----
    pr_ok = (
        is_prep_reply
        & (inbox.ballot == state.default_ballot)
        & (inbox.op > 0)
        & state.is_leader
    )
    state = state._replace(
        prepare_oks=state.prepare_oks.at[jnp.where(pr_ok, inbox.src, R)].set(
            True, mode="drop"),
        max_recv_ballot=jnp.maximum(
            state.max_recv_ballot,
            jnp.max(jnp.where(is_prep_reply, inbox.ballot, NO_BALLOT))),
        # learn how far peers' logs extend so new proposals don't collide
        crt_inst=jnp.maximum(
            state.crt_inst, jnp.max(jnp.where(pr_ok, inbox.inst, -1))),
    )
    state = state._replace(
        # track the discovered log tip through phase 1, freeze at the
        # prepare quorum: slots above this are our own tenure's
        # proposals (see tenure_start field note; ordered before the
        # prepared update so the quorum-forming step still captures
        # this step's discovery)
        tenure_start=jnp.where(state.prepared, state.tenure_start,
                               state.crt_inst))
    state = state._replace(
        prepared=state.prepared
        | (state.is_leader & (state.prepare_oks.sum() >= quorum1)),
    )

    sec("px.propose")
    # ---- 5. PROPOSE (handlePropose :617-710) ----
    can_serve = state.is_leader & state.prepared
    if cfg.fast_path:
        # 5-fast (Fast Flexible Paxos, config field note): a follower
        # that already follows a leader's ballot accepts broadcast
        # client PROPOSEs straight into its own next slots — sharing
        # section 5's cumsum assignment and fused slot write B — and
        # fast-acks the leader (out-row rewrite below) instead of
        # redirecting the client. The leader keeps its classic path.
        can_fast = ((~state.is_leader) & (state.leader_id >= 0)
                    & (state.default_ballot > NO_BALLOT))
        prop = is_propose & (can_serve | can_fast)
    else:
        prop = is_propose & can_serve
    # slot assignment: prefix count over propose rows
    slot_off = jnp.cumsum(prop.astype(jnp.int32)) - 1
    slots = state.crt_inst + slot_off
    rel_p = slots - state.window_base
    fits = prop & (rel_p >= 0) & (rel_p < S)

    sec("px.slot_write_b")
    # ---- fused slot write B (COMMIT + PROPOSE) ----
    # The two sections' targets are disjoint within one batch: every
    # com_ok row bumped crt_inst past its inst (section 3, above), and
    # propose slots start at the post-bump crt_inst — so one keyed
    # winner pass applies both (key = section*M + row; propose targets
    # are unique by the cumsum, commit rows tie-break by max row index
    # exactly as slot_winner did).
    okB = com_ok | fits
    keyB = jnp.full(S + 1, -1, jnp.int32).at[
        jnp.where(okB, jnp.where(fits, rel_p, rel_i), S)].max(
        jnp.where(fits, M + rows_m, rows_m), mode="drop")[:S]
    hitB = keyB >= 0
    secB_prop = keyB >= M  # winner came from the PROPOSE section
    rowB = jnp.mod(keyB, M)
    colsB = gather_cols(rowB, hitB, slot_cols,
                        [getattr(state, f) for f in SLOT_FIELDS])
    state = state._replace(
        **dict(zip(SLOT_FIELDS[1:], colsB[1:])),
        # propose stamps the serving ballot; commit keeps the row's
        ballot=jnp.where(hitB & secB_prop, state.default_ballot, colsB[0]),
        # commit never downgrades (max with COMMITTED); propose accepts
        status=jnp.where(
            hitB, jnp.where(secB_prop, jnp.uint8(ACCEPTED),
                            jnp.maximum(state.status,
                                        jnp.uint8(COMMITTED))),
            state.status),
        # only propose seeds votes (the leader votes for itself)
        votes=jnp.where(hitB & secB_prop, me_bit, state.votes),
        crt_inst=state.crt_inst + jnp.where(fits, 1, 0).sum(),
    )
    # broadcast ACCEPT rows for accepted proposals; rejection replies
    # (ProposeReplyTS{FALSE, Leader} :618-625) for the rest
    reject = is_propose & ~fits
    out = out._replace(
        kind=jnp.where(fits, int(MsgKind.ACCEPT),
                       jnp.where(reject, int(MsgKind.PROPOSE_REPLY), out.kind)),
        src=jnp.where(is_propose, state.me, out.src),
        inst=jnp.where(fits, slots, out.inst),
        ballot=jnp.where(fits, state.default_ballot,
                         jnp.where(reject, state.leader_id, out.ballot)),
        last_committed=jnp.where(fits, state.committed_upto, out.last_committed),
        op=jnp.where(fits, inbox.op, jnp.where(reject, 0, out.op)),
        key_hi=jnp.where(is_propose, inbox.key_hi, out.key_hi),
        key_lo=jnp.where(is_propose, inbox.key_lo, out.key_lo),
        val_hi=jnp.where(is_propose, inbox.val_hi, out.val_hi),
        val_lo=jnp.where(is_propose, inbox.val_lo, out.val_lo),
        cmd_id=jnp.where(is_propose, inbox.cmd_id, out.cmd_id),
        client_id=jnp.where(is_propose, inbox.client_id, out.client_id),
    )
    dst = jnp.where(fits, -1, jnp.where(reject, -2, dst))  # -2 = to client
    if cfg.fast_path:
        # 5-fast out rows: a follower's accepted PROPOSE becomes an
        # ACCEPT_REPLY to the leader, op=2 marking it a FAST ack whose
        # vote only counts under the leader's fingerprint check (6),
        # with the command identity in (client_id, val_lo) and the
        # run length 1 in cmd_id (range_vote_coverage contract)
        fastrow = fits & ~state.is_leader
        out = out._replace(
            kind=jnp.where(fastrow, int(MsgKind.ACCEPT_REPLY), out.kind),
            op=jnp.where(fastrow, 2, out.op),
            cmd_id=jnp.where(fastrow, 1, out.cmd_id),
            val_hi=jnp.where(fastrow, 0, out.val_hi),
            val_lo=jnp.where(fastrow, inbox.cmd_id, out.val_lo),
        )
        dst = jnp.where(fastrow, state.leader_id, dst)

    sec("px.vote_count")
    # ---- 6. ACCEPT_REPLY (handleAcceptReply :1014-1064) ----
    # One reply row acks the RANGE [inst, inst + count) (count in
    # cmd_id — the run-length compression emitted by step 2 / carried
    # by the wire `count` field). The range becomes per-slot votes via
    # a per-sender difference array + prefix sum: +1 at the range
    # start, -1 past its end, cumsum > 0 = covered. Rows predating
    # compression (cmd_id == 0) count as single-slot acks. Ranges
    # clipped to the window contribute their resident part.
    ar_ok = is_accept_reply & (inbox.op > 0) & state.is_leader \
        & (inbox.ballot == state.default_ballot)
    if cfg.fast_path:
        # a FAST ack (op == 2) votes only if this leader's own slot
        # holds the very same command (value fingerprint) at the
        # serving ballot: a divergent fast assignment must not count
        # toward a quorum for the leader's value — it converges later
        # when the classic ACCEPT broadcast overwrites it (section 2
        # same-ballot overwrite), whose classic re-ack then counts
        ar_rel = inbox.inst - state.window_base
        ar_safe = jnp.clip(ar_rel, 0, S - 1)
        fast_match = ((ar_rel >= 0) & (ar_rel < S)
                      & (state.status[ar_safe] >= ACCEPTED)
                      & (state.ballot[ar_safe] == state.default_ballot)
                      & (state.cmd_id[ar_safe] == inbox.val_lo)
                      & (state.client_id[ar_safe] == inbox.client_id))
        ar_ok = ar_ok & ((inbox.op != 2) | fast_match)
    vote_cov = range_vote_coverage(ar_ok, inbox.src, inbox.inst,
                                   inbox.cmd_id, state.window_base, S, R)
    reply_src = jnp.where(is_accept_reply | is_prep_reply,
                          jnp.clip(inbox.src, 0, R - 1), R)
    # peer_commits ADOPTS the batch-max report per peer rather than
    # taking a running max: a crash-revived peer reports a frontier
    # LOWER than what we remember, and a monotone max would pin
    # catch-up past its real gap forever. Reports are monotone per
    # source within one process lifetime (TCP-ordered), so adoption
    # only regresses across a real crash — exactly when it must.
    pc_seen = jnp.full(R + 1, jnp.int32(-(2 ** 30))).at[reply_src].max(
        inbox.last_committed)
    replied = pc_seen[:R] > -(2 ** 30)
    state = state._replace(
        votes=state.votes | pack_vote_bits(vote_cov),
        max_recv_ballot=jnp.maximum(
            state.max_recv_ballot,
            jnp.max(jnp.where(is_accept_reply, inbox.ballot, NO_BALLOT))),
        peer_commits=jnp.where(replied, pc_seen[:R], state.peer_commits),
    )

    sec("px.commit_scan")
    # ---- 7. commit scan ----
    idx_abs = state.window_base + jnp.arange(S, dtype=jnp.int32)
    n_votes = jax.lax.population_count(state.votes).astype(jnp.int32)
    if cfg.explicit_commit:
        # classic: each instance commits at its OWN ballot (votes are
        # reset whenever a slot's ballot changes, so n_votes counts
        # acks for exactly the (slot, ballot) pair — per-instance
        # bookkeeping, paxos.go:57-70, :631-660)
        leader_commit = state.is_leader & (state.status == ACCEPTED) & (
            n_votes >= quorum2)
    else:
        leader_commit = state.is_leader & (state.status == ACCEPTED) & (
            n_votes >= quorum2) & (state.ballot == state.default_ballot)
    follower_commit = (state.status == ACCEPTED) & (idx_abs <= lc) & (
        state.ballot == state.default_ballot)
    state = state._replace(
        status=jnp.where(leader_commit | follower_commit,
                         COMMITTED, state.status))
    start_rel = state.committed_upto + 1 - state.window_base
    frontier_rel = commit_frontier(state.status >= COMMITTED, start_rel)
    old_upto = state.committed_upto
    state = state._replace(
        committed_upto=jnp.maximum(state.committed_upto,
                                   frontier_rel + state.window_base))

    sec("px.gossip")
    # ---- 7b. frontier gossip + stall tracking ----
    # The reference's followers only learn commitment from the NEXT
    # Accept's piggyback (SURVEY.md section 3.2), stalling their exec
    # cursor when traffic pauses. Here ONE appended row closes the loop
    # in both directions:
    # * leader: broadcast COMMIT_SHORT whenever its frontier advances;
    # * follower: an ACCEPT_REPLY frontier report to the leader when
    #   its frontier advances OR it received commit-ish traffic without
    #   advancing. The second clause is load-bearing: a revived replica
    #   being healed by host-side COMMIT rows (runtime _host_catchup)
    #   would otherwise never ack, the leader's peer_commits would
    #   never leave -1, and catch-up would re-serve the same prefix
    #   forever (peer_commits only updates from reply rows).
    advanced = state.committed_upto > old_upto
    in_flight = state.crt_inst - 1 > state.committed_upto
    state = state._replace(
        tick=state.tick + tick_inc,
        stall_ticks=jnp.where(
            state.is_leader & state.prepared & in_flight & ~advanced,
            state.stall_ticks + tick_inc, 0))
    # classic mode broadcasts the frontier EVERY step (one row): with
    # the Accept piggyback inert, an idle leader's followers would
    # otherwise never learn the last commits (the reference instead
    # bcasts per-instance Commits inline, paxos.go:661).
    # non-classic gossip runs on a 4-tick cadence with a watermark
    # (gossip_upto): per-commit gossip made every serial op cascade
    # into ~4 extra ticks across the cluster (leader commit ->
    # COMMIT_SHORT wakes both followers -> their exec + frontier
    # reports -> one more leader tick), which on a single-core host
    # directly serialized into commit latency (round-5 trace). The
    # watermark keeps it edge-triggered — an advance just before an
    # idle stretch still gossips on the next cadence tick. Accept
    # piggybacking carries the frontier under load anyway; the cadence
    # only delays IDLE followers' exec by <=4 ticks.
    if cfg.gossip_ticks > 1:
        cadence = (state.tick % cfg.gossip_ticks) == 0
    else:
        cadence = jnp.asarray(True)
    behind = state.committed_upto > state.gossip_upto
    # a follower reports its frontier whenever this step processed
    # inbound consensus traffic (got_committy): the report rides the
    # reply frame that traffic generates anyway, and the lossy
    # pod-mode fabric (fixed-row inboxes drop overflow) depends on
    # prompt reports to aim the leader's catch-up — gating these to
    # the cadence starved healing and wedged saturated fused runs. A
    # QUIET follower reports only on the cadence: that standalone
    # report is exactly the wakeup cascade the cadence suppresses
    # (an always-eager variant fed back into a permanent tick storm
    # under closed-loop serial load — round-5 trace).
    if cfg.explicit_commit:
        lead_adv = state.is_leader & state.prepared & (
            state.committed_upto >= 0)
    else:
        lead_adv = state.is_leader & state.prepared & cadence & behind
    got_committy = (is_accept | is_commit | is_cshort | is_pir).any()
    fol_report = (~state.is_leader) & (state.leader_id >= 0) & (
        got_committy | (cadence & behind))
    state = state._replace(
        gossip_upto=jnp.where(lead_adv | fol_report, state.committed_upto,
                              state.gossip_upto))
    fb = MsgBatch.empty(1)
    fb = fb._replace(
        kind=jnp.where(lead_adv, int(MsgKind.COMMIT_SHORT),
                       jnp.where(fol_report, int(MsgKind.ACCEPT_REPLY),
                                 0))[None].astype(jnp.int32),
        src=jnp.full(1, state.me, jnp.int32),
        ballot=jnp.full(1, state.default_ballot, jnp.int32),
        inst=jnp.maximum(state.committed_upto, 0)[None],
        # op=0: the report must NOT read as an accept ack — op>0 would
        # register a phantom vote at the leader for a slot this replica
        # never accepted (peer_commits adoption ignores op; only the
        # vote path checks it)
        op=jnp.zeros(1, jnp.int32),
        last_committed=jnp.full(1, state.committed_upto, jnp.int32),
    )
    fb_dst = jnp.where(lead_adv, jnp.int32(-1),
                       jnp.clip(state.leader_id, 0, R - 1))[None]

    sec("px.catchup")
    # ---- 7c. catch-up (CatchUpLog, bareminpaxos.go:488-513) ----
    # One peer per step: if its known frontier trails ours, append up
    # to `catchup_rows` committed slots as ACCEPT rows at the current
    # ballot; the piggybacked frontier commits them on arrival. Peer
    # choice alternates between the MOST-lagging peer (so a revived
    # replica heals at catchup_rows/2 per round instead of
    # catchup_rows/R — the difference between healing under load and
    # never catching up) and round-robin (so one permanently dead peer,
    # whose frontier report never arrives, cannot starve a second
    # laggard).
    K = cfg.catchup_rows
    pc_masked = jnp.where(jnp.arange(R) == state.me, jnp.int32(2 ** 30),
                          state.peer_commits)
    worst = jnp.argmin(pc_masked).astype(jnp.int32)
    # tick//2 so the round-robin half cycles ALL residues: tick % R on
    # odd ticks only visits odd residues when R is even, which would
    # starve even-indexed laggards whenever a dead peer pins `worst`
    rr = jnp.mod(state.tick // 2, R)
    peer = jnp.where(jnp.mod(state.tick, 2) == 0, worst, rr)
    lagging = state.peer_commits[peer] < state.committed_upto
    do_cu = state.is_leader & state.prepared & (peer != state.me) & lagging
    cu_slots = state.peer_commits[peer] + 1 + jnp.arange(K, dtype=jnp.int32)
    cu_rel = cu_slots - state.window_base
    cu_ok = do_cu & (cu_slots <= state.committed_upto) & (cu_rel >= 0) & (
        cu_rel < S)
    cu_rel_safe = jnp.clip(cu_rel, 0, S - 1)
    # the run's seven payload columns in one fetch (read_cols; the
    # index is a clipped run of slots, read like any other)
    cu_op, *cu_payload = read_cols(
        cu_rel_safe, tuple(getattr(state, f) for f in SLOT_FIELDS[1:]))
    cu = MsgBatch(
        kind=jnp.where(cu_ok, int(MsgKind.ACCEPT), 0).astype(jnp.int32),
        src=jnp.full(K, state.me, jnp.int32),
        ballot=jnp.full(K, state.default_ballot, jnp.int32),
        inst=cu_slots,
        last_committed=jnp.full(K, state.committed_upto, jnp.int32),
        op=cu_op.astype(jnp.int32),
        **dict(zip(SLOT_FIELDS[2:], cu_payload)),
    )

    sec("px.retry")
    # ---- 7d. in-flight retry + gap no-op fill ----
    # When the frontier stalls (lost accepts, leader change), rebroad-
    # cast the first `catchup_rows` uncommitted slots at the current
    # ballot. Slots still EMPTY after `noop_delay` stalled steps (no
    # live replica reported a value during recovery) are filled with
    # no-ops — the classic new-leader gap fill; the reference's
    # equivalent half-finished path is flagged in SURVEY.md section
    # 7.4.
    # >= 4, not >= 1: a leader awaiting acks keeps ticking at tick_s
    # (it is not idle), so the stall counter reaches 2-3 within one
    # normal ack round-trip and a low threshold rebroadcast every
    # in-flight accept once per op — pure duplicate traffic that the
    # followers then re-ack (round-5 trace). Genuinely lost accepts
    # still retry within ~4 ticks (milliseconds).
    def retry():
        do_rt = state.is_leader & state.prepared & (
            state.stall_ticks >= RETRY_STALL_TICKS)
        rt_slots = state.committed_upto + 1 + jnp.arange(K, dtype=jnp.int32)
        rt_rel = rt_slots - state.window_base
        rt_rel_safe = jnp.clip(rt_rel, 0, S - 1)
        rt_in = do_rt & (rt_slots < state.crt_inst) & (rt_rel >= 0) & (
            rt_rel < S)
        rt_empty = rt_in & (state.status[rt_rel_safe] == NONE)
        # A gap slot may be no-op filled ONLY when a majority (self
        # included) answered the current-ballot per-instance phase 1
        # with "no value" (pvotes, fed by the 7e sweep). This is the
        # Paxos phase-1 safety condition; the old time-based heuristic
        # (stall_ticks >= noop_delay) could fill a slot whose committed
        # value simply hadn't been transferred yet.
        pv_cnt = jax.lax.population_count(
            state.pvotes[rt_rel_safe]).astype(jnp.int32)
        noop_fill = rt_empty & (pv_cnt >= quorum1)
        # A slot holding a value adopted from phase-1 answers (ballot
        # != default_ballot) may be re-driven at the current ballot
        # ONLY after a majority answered the per-instance phase 1: the
        # adopted value is then the max-vballot value over a majority —
        # the classic Paxos phase-2 precondition. Re-driving off a
        # single early answer could push a superseded value over a
        # committed one (the superseding higher-vballot answer lands
        # via 1c only later). Slots already at the current ballot were
        # driven by this leader (safe); committed slots carry the
        # decided value (safe).
        own_ballot = state.ballot[rt_rel_safe] == state.default_ballot
        settled = (pv_cnt >= quorum1) | (
            state.status[rt_rel_safe] >= COMMITTED)
        rt_ok = rt_in & (
            ((state.status[rt_rel_safe] >= ACCEPTED)
             & (own_ballot | settled))
            | noop_fill)
        # bump retried slots to the current ballot (resetting votes
        # when the ballot actually changes), so follower acks count
        bump = rt_ok & (state.ballot[rt_rel_safe] != state.default_ballot)
        # rt_rel is the contiguous range [rt_rel[0], rt_rel[0]+K): each
        # slot's source row is arithmetic (slot - rt_rel[0]) — the
        # masked writes become dense gathers with NO scatter
        # (ops/winner.py)
        sidx = jnp.arange(S, dtype=jnp.int32)
        rt_row = sidx - rt_rel[0]
        rt_row_safe = jnp.clip(rt_row, 0, K - 1)
        in_rt = (rt_row >= 0) & (rt_row < K)
        hit_b = in_rt & bump[rt_row_safe]
        hit_n = in_rt & noop_fill[rt_row_safe]
        ballot = jnp.where(hit_b, state.default_ballot, state.ballot)
        status = jnp.where(hit_n, jnp.asarray(ACCEPTED, state.status.dtype),
                           state.status)
        op = jnp.where(hit_n, jnp.uint8(0), state.op)
        cmd_id = jnp.where(hit_n, 0, state.cmd_id)
        client_id = jnp.where(hit_n, -1, state.client_id)
        votes = jnp.where(hit_b, me_bit, state.votes)
        rt = MsgBatch(
            kind=jnp.where(rt_ok, int(MsgKind.ACCEPT), 0).astype(jnp.int32),
            src=jnp.full(K, state.me, jnp.int32),
            ballot=jnp.full(K, state.default_ballot, jnp.int32),
            inst=rt_slots,
            last_committed=jnp.full(K, state.committed_upto, jnp.int32),
            op=op[rt_rel_safe].astype(jnp.int32),
            key_hi=state.key_hi[rt_rel_safe],
            key_lo=state.key_lo[rt_rel_safe],
            val_hi=state.val_hi[rt_rel_safe],
            val_lo=state.val_lo[rt_rel_safe],
            cmd_id=cmd_id[rt_rel_safe],
            client_id=client_id[rt_rel_safe],
        )
        return (ballot, status, op, cmd_id, client_id, votes), rt, sidx

    def no_retry():
        # no stall counter at RETRY_STALL_TICKS: do_rt is false in
        # every replica, so no slot is written and no row is live (the
        # fabric never counts or copies a row of kind 0)
        return ((state.ballot, state.status, state.op, state.cmd_id,
                 state.client_id, state.votes), MsgBatch.empty(K),
                jnp.arange(S, dtype=jnp.int32))

    # sidx: the window's slot index, which 7e and 8 read too; it is
    # made inside the section so that the step without gates stays the
    # program it was, equation for equation
    (ballot, status, op, cmd_id, client_id, votes), rt, sidx = (
        retry() if gates is None
        else jax.lax.cond(gates["px.retry"], retry, no_retry))
    state = state._replace(ballot=ballot, status=status, op=op,
                           cmd_id=cmd_id, client_id=client_id, votes=votes)

    sec("px.sweep")
    # ---- 7e. per-instance phase-1 sweep (new-leader value discovery,
    # replacing the reference's one-shot CatchUpLog shipping with a
    # chunked, majority-audited pull: bareminpaxos.go:488-513/:912-966
    # behavior, paxosproto Prepare{Instance} machinery) ----
    # While leader: broadcast PREPARE_INST for the next
    # `recovery_rows`-slot chunk of [committed_upto+1, crt_inst);
    # followers answer via 2b; answers accumulate in pvotes (1c) and
    # values adopt + rebroadcast via 7d. When the sweep is done but the
    # frontier still stalls, rescan from the frontier (replies may have
    # been lost).
    K2 = cfg.recovery_rows
    sweep_on = state.is_leader & state.prepared
    # the steady-state sweep stops at tenure_start: slots at/above it
    # are this tenure's own proposals and need no discovery (see the
    # tenure_start field note). The stalled-frontier rescan lifts the
    # bound — if the frontier truly stalls, sweep everything.
    limit = jnp.minimum(state.crt_inst, state.tenure_start)
    done = state.rec_cursor >= limit
    rescan = sweep_on & done & in_flight & (
        state.stall_ticks >= cfg.noop_delay)
    eff_limit = jnp.where(rescan, state.crt_inst, limit)
    cursor = jnp.where(rescan, state.committed_upto + 1, state.rec_cursor)
    cursor = jnp.maximum(cursor, state.committed_upto + 1)
    pi_slots = cursor + jnp.arange(K2, dtype=jnp.int32)
    pi_rel = pi_slots - state.window_base
    pi_row = sidx - pi_rel[0]
    pi_rel_safe = jnp.clip(pi_rel, 0, S - 1)
    pi_ok = sweep_on & (pi_slots < eff_limit) & (pi_rel >= 0) & (
        pi_rel < S)
    pi = MsgBatch.empty(K2)._replace(
        kind=jnp.where(pi_ok, int(MsgKind.PREPARE_INST), 0).astype(jnp.int32),
        src=jnp.full(K2, state.me, jnp.int32),
        ballot=jnp.full(K2, state.default_ballot, jnp.int32),
        inst=pi_slots,
    )
    state = state._replace(
        # the leader answers its own phase 1 as it sweeps; pi_rel is a
        # contiguous range, so the OR-delta is a dense masked select
        # (slot s's source row is s - pi_rel[0]; no scatter)
        pvotes=state.pvotes | jnp.where(
            (pi_row >= 0) & (pi_row < K2)
            & read_cols(jnp.clip(pi_row, 0, K2 - 1), (pi_ok,))[0],
            me_bit, jnp.uint16(0)),
        rec_cursor=jnp.where(
            sweep_on, jnp.minimum(cursor + K2, eff_limit), cursor),
    )

    sec("px.outbox")
    out = _concat_rows(_concat_rows(_concat_rows(_concat_rows(out, pi), fb), cu), rt)
    dst = jnp.concatenate([
        dst,
        jnp.full(K2, -1, jnp.int32),  # phase-1 sweep broadcast
        fb_dst.astype(jnp.int32),  # frontier gossip (bcast / to leader)
        jnp.full(K, peer, jnp.int32),  # catch-up -> laggard
        jnp.full(K, -1, jnp.int32),  # retry broadcast
    ])

    sec("px.exec")
    # ---- 8. execute (executeCommands :1066-1098) ----
    E = cfg.exec_batch
    avail = state.committed_upto - state.executed_upto
    n_exec = jnp.clip(avail, 0, E)
    exec_lo = state.executed_upto + 1
    rel_e = exec_lo - state.window_base + jnp.arange(E, dtype=jnp.int32)
    evalid = jnp.arange(E) < n_exec
    rel_e_safe = jnp.clip(rel_e, 0, S - 1)
    # the batch's seven payload columns in one fetch, before the table
    # is applied (the apply writes none of them)
    (op_s, key_hi_e, key_lo_e, val_hi_e, val_lo_e, cmd_id_e,
     client_id_e) = read_cols(
        rel_e_safe, tuple(getattr(state, f) for f in SLOT_FIELDS[1:]))
    op_e = jnp.where(evalid, op_s.astype(jnp.int32), 0)

    # the sort/lookup/insert pipeline is the step's most expensive
    # fixed block; steps with nothing to execute (pure propose/accept
    # traffic — 2 of the ~3 steps on a serial op's path) skip it
    # entirely via cond instead of running it over all-invalid rows
    def _exec_kv(kv, apply=kv_apply_batch):
        return apply(kv, op_e, key_hi_e, key_lo_e, val_hi_e, val_lo_e,
                     evalid)

    def _no_exec(kv):
        z = jnp.zeros(E, jnp.int32)
        return kv, z, z, jnp.zeros(E, bool)

    if cfg.gate_exec:
        kv, o_hi, o_lo, o_found = jax.lax.cond(
            n_exec > 0, _exec_kv, _no_exec, state.kv)
    else:  # vmapped composition: cond would run both branches anyway
        kv, o_hi, o_lo, o_found = _exec_kv(state.kv, kv_apply_batch_shared)
    state = state._replace(
        kv=kv,
        executed_upto=state.executed_upto + n_exec,
        # executed slots form the contiguous range [rel_e[0],
        # rel_e[0] + n_exec): a range test, not a scatter
        status=jnp.where(
            (sidx >= rel_e[0]) & (sidx < rel_e[0] + n_exec),
            EXECUTED, state.status),
    )
    execr = ExecResult(
        lo=exec_lo, count=n_exec, val_hi=o_hi, val_lo=o_lo, found=o_found,
        op=op_e,
        cmd_id=jnp.where(evalid, cmd_id_e, 0),
        client_id=jnp.where(evalid, client_id_e, 0),
    )

    sec("px.window_slide")
    # ---- 9. window slide ----
    # Retire the executed prefix: roll every per-slot array left by the
    # executed count and reset the freed tail, advancing window_base.
    # This is how a fixed-size device window gives the reference's
    # unbounded (15M-slot) log. All slot addressing is absolute with
    # `_rel` translation, so in-flight messages are unaffected; rows
    # addressing slid-out slots simply drop (they were executed).
    if cfg.slide_window:
        retention = cfg.retention if cfg.retention >= 0 else S // 2
        exec_edge = state.executed_upto + 1
        # Everyone retains up to `retention` executed slots: any replica
        # may become leader later and must be able to serve catch-up
        # for that span. Peers lagging beyond retention are routed to
        # the host stable-store path (runtime/replica.py _host_catchup),
        # so no replica needs to retain more than this uniform span.
        target = exec_edge - retention
        shift = jnp.clip(target - state.window_base, 0, S)
        idx1 = jnp.arange(S, dtype=jnp.int32)
        gone = idx1 >= (S - shift)

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
            window_base=state.window_base + shift,
        )
    return state, Outbox(msgs=out, dst=dst, acked=ack_ok_row), execr


# ---- state transfer: the device twin of the served path's snapshot ----
#
# Catch-up (7c) heals a follower from the leader's window; one that has
# fallen below ``window_base`` it cannot reach. The served path then
# ships a snapshot (runtime/replica.py ``_host_catchup`` ->
# ``_send_snapshot`` -> ``_snap_rx_install`` ->
# ``_install_snapshot_pairs``). A pod has no host path: the same
# transfer is a section of the ROUND, run by the composition over the
# replicas of a group before it steps them (parallel/sharded.py
# ``transfer_round``), because it reads one replica's state and writes
# another's.


def transfer_needs(cfg: MinPaxosConfig, states: ReplicaState, alive):
    """Which replicas of ONE group (leaves ``[R, ...]``) are due a state
    transfer this round, and from whom: ``(need bool[R], donor i32)``.

    The sender's side is ``_host_catchup``'s: the group's prepared
    leader (the live one of the highest ballot, should a deposed one
    not know yet) ships to a peer q whose frontier, as far as its
    reports have told (``peer_commits[q]``, the last report it has; -1
    before any), lies below what its window can still serve:
    ``peer_commits[q] + 1 < window_base``. The receiver's side is
    ``_snap_rx_install``'s "ahead of our own executed frontier",
    tightened to the same line: q installs only while its own executed
    prefix ends below the donor's window, so a stale report costs a
    healthy replica nothing and one install is not followed by a
    second while the leader still awaits q's next report. Both ends
    under ``alive``: a dead replica neither ships nor installs.
    Nothing here reads a fault schedule."""
    reps = jnp.arange(cfg.n_replicas, dtype=jnp.int32)
    can_ship = alive & (states.leader_id == states.me) & states.prepared
    donor = jnp.argmax(jnp.where(can_ship, states.default_ballot,
                                 NO_BALLOT - 1)).astype(jnp.int32)
    base = states.window_base[donor]
    need = (can_ship.any() & alive & (reps != donor)
            & (states.peer_commits[donor] + 1 < base)
            & (states.executed_upto + 1 < base))
    return need, donor


def transfer_gate(cfg: MinPaxosConfig, states: ReplicaState, alive):
    """The section's whole-chip gate, over every group at once (leaves
    ``[G, R, ...]``): open iff some replica is due a transfer (exact,
    not a superset: the section acts on the same ``transfer_needs``)."""
    return jax.vmap(functools.partial(transfer_needs, cfg))(
        states, alive)[0].any()


def state_transfer(cfg: MinPaxosConfig, states: ReplicaState, alive):
    """One group's transfers of this round: ``(states', installs)``.

    A replica q that ``transfer_needs`` names adopts the donor's
    EXECUTED state at the donor's executed frontier f, exactly what
    ``_install_snapshot_pairs`` leaves: the donor's KV table (same
    capacity and hashing, so the arrays are copied whole, the drop
    count with them), ``executed_upto`` = f, ``committed_upto`` =
    max(own, f), ``window_base`` = f + 1, ``crt_inst`` / ``rec_cursor``
    / ``tenure_start`` = max(own, f + 1). Only committed-and-executed
    state crosses; votes, promises and in-flight slots never do.

    What q keeps. Its identity and every ballot it has promised
    (``default_ballot`` / ``max_recv_ballot`` = max(own, donor's); where
    the donor's is higher q follows the donor, as a PREPARE of that
    ballot would have made it, and is not leader). Its window is SLID
    to f + 1, not zeroed: a slot above f that q has accepted may be one
    of the votes its quorum stands on, and forgetting it could let a
    later phase 1 find a majority of "empty" for a committed slot. Slots
    at or below f are covered by the table, as in the served path, whose
    installs only ever meet windows that lie wholly below f (the window
    is then all fill: ``_install_snapshot_pairs``'s zeroed columns).
    ``gossip_upto`` stays, so q's next step reports its new frontier
    and the leader's ``peer_commits[q]`` follows that report; catch-up
    rows and the ACCEPT stream close the last rounds' gap."""
    S, R = cfg.window, cfg.n_replicas
    need, donor = transfer_needs(cfg, states, alive)
    d = jax.tree_util.tree_map(lambda x: x[donor], states)
    f = d.executed_upto

    def adopt(own, new):
        return jnp.where(need.reshape((R,) + (1,) * (own.ndim - 1)), new, own)

    def at_least(own, floor):  # a cursor or ballot never moves back
        return adopt(own, jnp.maximum(own, floor))

    shift = jnp.clip(f + 1 - states.window_base, 0, S)            # [R]
    src = jnp.arange(S, dtype=jnp.int32)[None, :] + shift[:, None]
    kept = src < S
    src = jnp.minimum(src, S - 1)

    def slide(col, fill):
        return adopt(col, jnp.where(
            kept, jnp.take_along_axis(col, src, axis=1), fill))

    raised = need & (d.default_ballot > states.default_ballot)
    return states._replace(
        ballot=slide(states.ballot, NO_BALLOT),
        status=slide(states.status, NONE),
        op=slide(states.op, 0),
        key_hi=slide(states.key_hi, 0),
        key_lo=slide(states.key_lo, 0),
        val_hi=slide(states.val_hi, 0),
        val_lo=slide(states.val_lo, 0),
        cmd_id=slide(states.cmd_id, 0),
        client_id=slide(states.client_id, 0),
        votes=slide(states.votes, 0),
        pvotes=slide(states.pvotes, 0),
        kv=jax.tree_util.tree_map(lambda own, new: adopt(own, new[None]),
                                  states.kv, d.kv),
        window_base=adopt(states.window_base, f + 1),
        executed_upto=adopt(states.executed_upto, f),
        committed_upto=at_least(states.committed_upto, f),
        crt_inst=at_least(states.crt_inst, f + 1),
        rec_cursor=at_least(states.rec_cursor, f + 1),
        tenure_start=at_least(states.tenure_start, f + 1),
        default_ballot=at_least(states.default_ballot, d.default_ballot),
        max_recv_ballot=at_least(states.max_recv_ballot, d.default_ballot),
        leader_id=jnp.where(raised | (need & (states.leader_id < 0)),
                            donor, states.leader_id),
        prepared=states.prepared & ~raised,
    ), need.sum(dtype=jnp.int32)


def transfer_bytes(cfg: MinPaxosConfig) -> int:
    """Bytes one install copies from its donor: the KV table's arrays
    (ops/kvstore.py ``KVState``; the window columns are slid in place
    and the cursors are a few words)."""
    kv = jax.eval_shape(lambda: kv_init(cfg.kv_pow2))
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(kv))


#: sections of a ROUND that the composition runs over the replicas of a
#: group, outside the vmap that steps them, each under a whole-chip
#: gate of its own as ``recovery_gates`` has the step's: ``px.*`` scope
#: -> (gate(cfg, states, alive) over all groups, section(cfg, states,
#: alive) of one group -> (states', acts)). Classic Multi-Paxos is this
#: step under another flag and has them too; Mencius has no leader to
#: ship from and declares none.
replica_step_impl.round_sections = {
    "px.state_transfer": (transfer_gate, state_transfer),
}


# Single-replica entry point used by the host runtime (runtime/replica.py).
replica_step = jax.jit(replica_step_impl, static_argnums=0, donate_argnums=1)
