"""Sharded-Paxos: G independent consensus groups advanced by one jitted
step, laid over the device mesh.

The reference scales by adding replica processes (SURVEY.md section
2.5); the instance *space* inside one group is a single Go array walked
by one goroutine. Here the group itself is the data-parallel unit: the
pod-mode cluster (models/cluster.py, leaves [R, ...]) gains a leading
shard axis [G, R, ...], ``vmap`` runs every group's full protocol round
simultaneously, and the ``shard`` mesh axis partitions G across chips.
Groups never communicate — the same independence EPaxos exploits — so
the partition introduces zero collectives on the shard axis; laying the
``replica`` axis over chips instead turns the routing gather into ICI
all-to-all (see parallel/mesh.py).

This module is the north-star benchmark path (BASELINE.md: 1M
concurrent instances = e.g. 1024 shards x 1024-slot windows, N=5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from minpaxos_tpu.models.cluster import (
    ClusterState,
    _fill_inboxes,
    _pool_counts,
    _tree_stack,
    client_rows_of,
    route_outbox,
    step_replicas,
    tree_slice,
    tree_set,
)
from minpaxos_tpu.models.minpaxos import (
    MinPaxosConfig,
    MsgBatch,
    become_leader,
    init_replica,
    replica_step_impl,
    transfer_bytes,
)
from minpaxos_tpu.obs import register_pod
from minpaxos_tpu.obs.recorder import (
    N_TEL_FIELDS,
    PH_POD_DISPATCH,
    PH_POD_READBACK,
    phase,
    telemetry_valid_rows,
)
from minpaxos_tpu.ops.telemetry import telemetry_row
from minpaxos_tpu.ops.workload import (
    assemble_batch,
    propose_batch,
    workload_lanes,
)
from minpaxos_tpu.wire.messages import Op

#: round-latency histogram resolution for the resident runner: bins are
#: exact integer round latencies 1..LATENCY_BINS-1, last bin = overflow
#: (with a drained run and sane shapes it is 0).
LATENCY_BINS = 512

#: dispatches whose host intervals the resident loop keeps (a 30 s
#: window makes some hundreds; beyond this the ring overwrites)
POD_HOST_RING = 4096

#: which jitted entry points of the fused dispatch path donate their
#: round-state argument (in-place buffer reuse instead of a fresh
#: allocation per dispatch). Asserted against reality by
#: tests/test_workload.py (donated inputs must come back deleted).
DONATION = {
    "sharded_step": True,
    "sharded_run": True,
    "sharded_run_resident": True,
    "elect_all": True,
    "set_alive": True,
    # read-only probes — donating would consume live state:
    "commit_totals": False,
    "shard_cursors": False,
    "count_commands": False,
}

#: the command counts of a multi-owner (Mencius) pod, int32[3],
#: cumulative from boot: client commands the cursor replica's merged
#: frontier has passed, no-op slots (ceded, skipped or taken over) it
#: has passed, client commands their owners have assigned a slot
N_COUNTS = 3


def _init_sharded(cfg: MinPaxosConfig, n_shards: int,
                  init_fn=init_replica) -> ClusterState:
    states = _tree_stack([init_fn(cfg, i) for i in range(cfg.n_replicas)])
    # broadcast one zeroed group to all shards
    def tile(x):
        return jnp.broadcast_to(x[None], (n_shards,) + x.shape)

    return ClusterState(
        states=jax.tree_util.tree_map(tile, states),
        pending=jax.tree_util.tree_map(
            lambda x: jnp.zeros(
                (n_shards, cfg.n_replicas) + x.shape, x.dtype),
            MsgBatch.empty(cfg.inbox)),
        alive=jnp.ones((n_shards, cfg.n_replicas), dtype=bool),
    )


def init_sharded(cfg: MinPaxosConfig, n_shards: int, mesh=None,
                 init_fn=init_replica) -> ClusterState:
    """All-shards cluster state, optionally placed along mesh axis
    'shard' (leading-axis sharding; every group fully on one device).

    With a mesh, the state is BORN sharded (jit out_shardings) — the
    full [G, ...] tree never materializes on a single device, which
    matters at north-star scale (1024 shards of KV tables would OOM one
    chip). ``init_fn`` is the protocol's per-replica init (static):
    init_replica for the paxos family, models/mencius.py's init_mencius
    for Mencius."""
    if mesh is None:
        return jax.jit(_init_sharded, static_argnums=(0, 1, 2))(
            cfg, n_shards, init_fn)
    out_sharding = NamedSharding(mesh, P("shard"))  # prefix: all leaves
    return jax.jit(_init_sharded, static_argnums=(0, 1, 2),
                   out_shardings=out_sharding)(cfg, n_shards, init_fn)


def working_capacity(cfg: MinPaxosConfig, ext_rows: int) -> int:
    """Pending slots per inbox that the small tier of a round delivers
    and routes: a static function of shapes the step already has.

    A healthy round sends the follower being caught up p ACCEPTs, the
    2p committed slots by which its reported frontier trails
    (models/minpaxos.py section 7c) and one frontier row, the other
    followers p + 1, and the leader a handful of run-compressed acks:
    3p + 1 at most, with p <= ``ext_rows``. Four times ``ext_rows`` is
    that with a round's proposals to spare, rounded up to the 128 lanes
    of a vector register. Bursts beyond it (a revived follower's
    ``cfg.catchup_rows`` of catch-up and as many retries) take the
    configured capacity. Where this is not below ``cfg.inbox`` the
    round has one tier."""
    return min(cfg.inbox, 128 * max(1, -(-4 * ext_rows // 128)))


def small_tier_rows(cfg: MinPaxosConfig, ext_rows: int, owners: bool) -> int:
    """The small tier of a round for this protocol: ``working_capacity``
    and, for a multi-owner pod (Mencius), no less than its own healthy
    occupancy — a static function of shapes and protocol.

    Under rotating ownership an inbox holds four peers' traffic at
    once: each of the R-1 peers sends p ACCEPTs, the p slots it
    committed last round as COMMIT rows, one run-compressed ack and at
    most one SKIP row, and ``cfg.catchup_rows`` committed slots to the
    peer whose reported frontier trails most (models/mencius.py
    section 9d: every peer picks the same one, every round). That is
    (R-1) x (2p + catchup_rows + 2), with p <= ``ext_rows`` per owner,
    rounded up to 128 lanes: 1,152 of 2,048 at BASELINE config 4, where
    the fullest inbox reads 1,028 in every loaded round (PERF.md, PR
    28) and 3p + 1 = 256 never held it. Where even that does not fit
    under ``cfg.inbox`` the configured inbox is below the protocol's
    healthy traffic and no capacity lies clear of it: the single-leader
    guess stays (it still engages in part of such a run's rounds)."""
    rows = working_capacity(cfg, ext_rows)
    if owners:
        healthy = (cfg.n_replicas - 1) * (2 * ext_rows + cfg.catchup_rows + 2)
        healthy = 128 * -(-healthy // 128)
        if healthy < cfg.inbox:
            rows = max(rows, healthy)
    return rows


def recovery_sections(step) -> tuple:
    """The ``px.*`` scopes of the recovery sections ``step`` declares
    (``step.recovery_gates``: models/minpaxos.py, models/mencius.py),
    in the order every per-section array here has; () for a step that
    declares none."""
    return tuple(getattr(step, "recovery_gates", ()))


def recovery_gates(cfg: MinPaxosConfig, step, ss: ClusterState,
                   ext: MsgBatch) -> dict:
    """This round's whole-chip recovery gates: per recovery section of
    ``step``, a bool scalar: can the section have work in the round to
    come, from what can be seen before it steps, as the step's module
    declares it (where each is shown to be a superset). Taken OUTSIDE
    the vmap over groups, over all groups at once.

    A message kind is looked for in every pending slot and every ext
    row, which is more than the step is delivered (a dead replica's
    rows are silenced, and the small tier cuts padding alone)."""
    def present(kind):
        return ((ss.pending.kind == int(kind)).any()
                | (ext.kind == int(kind)).any())

    return {section: gate(cfg, ss.states, present) for section, gate
            in getattr(step, "recovery_gates", {}).items()}


def round_sections(step) -> dict:
    """The sections of a ROUND that ``step`` declares beside itself
    (``step.round_sections``, models/minpaxos.py: ``px.state_transfer``),
    by ``px.*`` scope, each a (gate, section) pair; {} for a step that
    declares none (Mencius), whose programs then hold nothing of this."""
    return getattr(step, "round_sections", {})


def recovery_counts(step):
    """Zeroed recovery counts of the resident loop for ``step``
    (``sharded_run_resident``'s ``recovery``): int32[2 n + 1] for its n
    round sections, None for a step that declares none."""
    n = len(round_sections(step))
    return jnp.zeros(2 * n + 1, jnp.int32) if n else None


def transfer_round(cfg: MinPaxosConfig, step, ss: ClusterState):
    """The round sections of ``step``, run before the round steps its
    replicas: (ss', open bool[sections], acts int32[sections]).

    Such a section reads one replica's state and writes another's (a
    state transfer from a group's leader to a follower its window can
    no longer heal), so it runs over the replicas of a group, OUTSIDE
    the vmap that steps them, for every group at once. Its gate is a
    whole-chip scalar of the kind ``recovery_gates`` takes, computed
    from protocol state and the ``alive`` mask that silences a dead
    replica's rows; while it is shut the section is skipped by a
    ``lax.cond`` around the states alone, so a round with nothing to
    transfer pays a reduction over [G, R] words. ``acts`` counts what
    an open section did (installs)."""
    opens, acts = [], []
    for scope, (gate, section) in round_sections(step).items():
        with jax.named_scope(scope):
            is_open = gate(cfg, ss.states, ss.alive)

            def run(states, alive, section=section):
                states, n = jax.vmap(functools.partial(section, cfg))(
                    states, alive)
                return states, n.sum(dtype=jnp.int32)

            states, n = jax.lax.cond(
                is_open, run, lambda states, alive: (states, jnp.int32(0)),
                ss.states, ss.alive)
        ss = ss._replace(states=states)
        opens.append(is_open)
        acts.append(n)
    return ss, jnp.stack(opens), jnp.stack(acts)


def _takes_gates(step) -> bool:
    """Whether ``step`` skips its recovery sections itself, by a
    conditional on the gates it is handed (models/minpaxos.py); else a
    step that declares such sections has a ``steady`` form without
    them (models/mencius.py)."""
    return getattr(step, "takes_gates", False)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _step_groups(cfg: MinPaxosConfig, step, rows: int, steady: bool,
                 ss: ClusterState, ext: MsgBatch, gates: dict | None):
    """Every group's replicas delivered ``rows`` pending slots + ext and
    stepped: (states', outboxes, exec results), leading axes [G, R].
    ``steady`` (static) and ``gates`` (through both vmaps unbatched)
    are the step's own arguments.

    A jit of its own so that the kernel is TRACED once per shape in a
    process, not once per program that holds it: ``sharded_step`` (the
    election's two rounds) and the full tier of the fused dispatches
    call it with the same arguments, and tracing the kernel is seconds
    of every warm set-up (the compile cache spares the compile, never
    the trace). It is inlined where it is called."""
    return jax.vmap(
        lambda cs, ext, gates: step_replicas(cfg, cs, ext, step, rows,
                                             steady, gates),
        in_axes=(0, 0, None))(ss, ext, gates)


def _open_gates(step) -> dict | None:
    """Constant gates, all open, for a step that takes gates (its
    kernel is then the one a fused dispatch's full tier traces); None
    for any other."""
    if not _takes_gates(step):
        return None
    return dict.fromkeys(recovery_sections(step), jnp.ones((), bool))


def _one_tier_round(cfg: MinPaxosConfig, step, ss: ClusterState,
                    ext: MsgBatch, gates: dict | None):
    """``jax.vmap(cluster_step_impl)`` with the outboxes kept:
    (ss', exec results, outboxes)."""
    states, outbox, execr = _step_groups(cfg, step, cfg.inbox, False, ss,
                                         ext, gates)
    pending = jax.vmap(functools.partial(route_outbox, cfg))(outbox,
                                                             ss.alive)
    return ClusterState(states, pending, ss.alive), execr, outbox


def _round_kernels(cfg: MinPaxosConfig, step, work_rows: int) -> list:
    """The kernel variants of a two-tier round, (rows, steady) each, in
    the order ``sharded_round`` indexes them: the configured capacity,
    the working capacity and, for a step with a steady form, the
    working capacity without its recovery sections."""
    kernels = [(cfg.inbox, False), (work_rows, False)]
    if recovery_sections(step) and not _takes_gates(step):
        kernels.append((work_rows, True))
    return kernels


def _pretrace_kernels(cfg: MinPaxosConfig, step, ss: ClusterState,
                      ext_rows: int, tiers: bool = True) -> None:
    """Trace the kernel variants of a fused dispatch (``tiers`` off:
    the configured capacity's alone, which ``sharded_step`` runs too)
    BEFORE the program that holds them is traced; its ``cond`` branches
    then find them in jit's trace cache. Where a kernel is traced
    decides what the trace costs on the chip's host: 1.7 s outside any
    jit (``ShardedCluster`` does that), 2.1 s at the top of one (the
    fused dispatches do, for a caller that has not), 4.3 s inside a
    scan's body and 7.3 s inside a ``cond`` branch there (trace only,
    one process; my chip runs, PRs 27 and 31; the sandbox shows no
    such difference), and every warm set-up pays for it, whatever the
    compile cache holds."""
    cfg = cfg._replace(gate_exec=False)
    ext = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(ss.alive.shape + (ext_rows,), x.dtype),
        MsgBatch.empty(1))
    gates = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), _open_gates(step))
    small = small_tier_rows(cfg, ext_rows, _has_owners(ss.states))
    kernels = [(cfg.inbox, False)]
    if tiers and small < cfg.inbox and cfg.route_fabric != "dense":
        kernels = _round_kernels(cfg, step, small)
    for rows, steady in kernels:
        _step_groups.trace(cfg, step, rows, steady, ss, ext, gates)


def sharded_round(cfg: MinPaxosConfig, step, work_rows: int,
                  ss: ClusterState, ext: MsgBatch):
    """One synchronous round for every shard, [G, R, ...] in and out,
    at the rows that are live and the sections that can have work: what
    ``jax.vmap(cluster_step_impl)`` computes, byte for byte, from
    kernels and a route called at a smaller static shape whenever this
    round's rows fit it.

    Routing packs each destination's rows to a PREFIX of its inbox, so
    the padding is a suffix that can be cut without moving a row. Every
    choice is a whole-chip scalar taken OUTSIDE the vmap over groups
    (inside it a ``lax.cond`` lowers to a select and runs both sides):

    * kernel tier: when no live row lies at or beyond slot
      ``work_rows`` of any pending inbox, deliver
      ``pending[..., :work_rows] ++ ext`` and step at
      M = work_rows + ext, else at M = cfg.inbox + ext. (The cut can
      bring the last pending row next to the first ext row, which only
      matters to ack-run compression if ext carried an ACCEPT that
      continues a peer's run; ext rows are client PROPOSEs and
      election PREPAREs.)
    * route tier: the route's prefix sum is computed once; its last
      column is each destination's row count. When the largest over
      [G, R] is <= ``work_rows``, search and gather ``work_rows`` slots
      and zero-pad to ``cfg.inbox`` so the carried state keeps its
      shape, else ``cfg.inbox`` slots. Overflow beyond ``cfg.inbox``
      drops as it always did.
    * recovery sections: a section whose ``recovery_gates`` entry is
      shut has every write mask false in this round, and is skipped.
      A step that takes gates is handed them and skips by a
      conditional of its own, in both tiers. Any other step that
      declares such sections gets a third kernel, the small tier's
      traced ``steady`` (without them), taken when the rows fit AND
      every gate is shut; a round with one open runs the kernel with
      every section, at the tier its rows fit, as it always did.

    (Two ways, for no reason in the protocols. Kernels of unequal
    structure under one ``cond`` cost the MinPaxos pod its fast
    gathers, in every arrangement tried; and conditionals in Mencius's
    step took its warm set-up past its bound when they were measured,
    which was before ``ShardedCluster`` traced its kernels outside any
    jit: PERF.md section 7 rows 15 and 14, which says what to measure
    next for one way to serve both.)

    All variants are in the one compiled program: a round that falls
    back compiles nothing. With ``work_rows >= cfg.inbox`` (or the
    dense fabric, which has no counts to look at) there is one kernel.

    Returns (ss', exec results, small, open); ``small`` is bool[2]:
    this round's kernel, and its route, ran at ``work_rows``; ``open``
    is bool[``recovery_sections``]: the section's gate was open.
    """
    cfg = cfg._replace(gate_exec=False)  # see cluster_step_impl
    full = cfg.inbox
    gates = recovery_gates(cfg, step, ss, ext)
    gate_open = (jnp.stack(list(gates.values())) if gates
                 else jnp.zeros(0, bool))
    if not _takes_gates(step):
        gates = None
    if work_rows >= full or cfg.route_fabric == "dense":
        ss, execr, _ = _one_tier_round(cfg, step, ss, ext, gates)
        return ss, execr, jnp.zeros(2, bool), gate_open

    def fill(slots):
        return jax.vmap(functools.partial(
            _fill_inboxes, slots=slots, capacity=full))

    def kernel(rows, steady):
        def run(ss, ext):
            states, outbox, execr = _step_groups(cfg, step, rows, steady,
                                                 ss, ext, gates)
            flat, cnt = jax.vmap(_pool_counts)(outbox.msgs, outbox.dst,
                                               ss.alive)
            route_small = cnt[..., -1].max() <= work_rows
            pending = jax.lax.cond(route_small, fill(work_rows), fill(full),
                                   flat, cnt)
            return (ClusterState(states, pending, ss.alive), execr,
                    route_small)
        return run

    kernel_small = ~(ss.pending.kind[..., work_rows:] != 0).any()
    full_k, small_k, *steady_k = [
        kernel(*k) for k in _round_kernels(cfg, step, work_rows)]
    if steady_k:
        which = jnp.where(kernel_small, 2 - gate_open.any(), 0)
        ss, execr, route_small = jax.lax.switch(
            which, [full_k, small_k, *steady_k], ss, ext)
    else:
        ss, execr, route_small = jax.lax.cond(kernel_small, small_k, full_k,
                                              ss, ext)
    return ss, execr, jnp.stack([kernel_small, route_small]), gate_open


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=1)
def sharded_step(cfg: MinPaxosConfig, ss: ClusterState, ext: MsgBatch,
                 step_impl=None):
    """One synchronous round for every shard: [G, R, ...] in, same out.

    ext is [G, R, Mext]. Returns (ss', exec results, client rows,
    client mask) with a leading G axis. Input shardings propagate: with
    ss/ext sharded on 'shard', XLA partitions the whole step with no
    communication. One tier, at the configured capacity: this is the
    host-in-the-loop entry (elections, tests, the multi-host worker),
    and a second kernel variant here is seconds of every set-up; the
    fused dispatches below take ``sharded_round``. Every section runs
    (``_open_gates``).
    """
    step = replica_step_impl if step_impl is None else step_impl
    ss, execr, outbox = _one_tier_round(
        cfg._replace(gate_exec=False), step, ss, ext, _open_gates(step))
    return (ss, execr, *client_rows_of(outbox))


@functools.partial(jax.jit, static_argnums=(0, 2), donate_argnums=1)
def elect_all(cfg: MinPaxosConfig, ss: ClusterState, leader: int):
    """Run become_leader for `leader` in EVERY shard and deposit the
    PREPARE row into each peer's pending inbox (first free row, or row
    0 if full — elections happen on quiet clusters; loss is legal
    anyway, Paxos retries)."""

    def one(cs: ClusterState) -> ClusterState:
        st = tree_slice(cs.states, leader)
        st, prep = become_leader(cfg, st)
        states = tree_set(cs.states, leader, st)
        row = jax.tree_util.tree_map(lambda x: x[0], prep)

        free = jnp.argmin(cs.pending.kind, axis=1)  # [R] first kind==0
        reps = jnp.arange(cfg.n_replicas)
        is_peer = reps != leader

        def put_col(col, v):
            return col.at[reps, jnp.where(is_peer, free, -1)].set(
                jnp.where(is_peer, v, col[reps, -1]))

        pending = jax.tree_util.tree_map(
            lambda col, v: put_col(col, v), cs.pending, row)
        return ClusterState(states, pending, cs.alive)

    return jax.vmap(one)(ss)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 7, 8))
def make_propose_ext(
    cfg: MinPaxosConfig,
    n_shards: int,
    ext_rows: int,
    count,
    leader,
    round_idx,
    seed=0,
    key_space: int = 1 << 20,
    owners: bool = False,
) -> MsgBatch:
    """Device-generated client workload: `count` PUT rows per shard,
    addressed to the leader replica — the TPU equivalent of the
    benchmark client's pre-generated request array
    (reference client/client.go:68-103). Generation lives in
    ops/workload.py (Threefry-2x32 keyed on (seed, round), countered
    on (shard, row)) so the resident scan, this jitted entry point,
    and the NumPy host injector all draw the same byte-identical
    stream. ``owners``: the multi-owner stream (Mencius), every owner
    its own rows."""
    return propose_batch(cfg.n_replicas, n_shards, ext_rows, count,
                         leader, round_idx, seed, key_space, owners=owners)


def _has_owners(states) -> bool:
    """Every replica owns slots and proposes (Mencius): read off the
    state's structure at trace time, as ``has_prepared`` is, so the
    single-leader programs are what they were."""
    return getattr(states, "crt_own", None) is not None


def _owner_cursors(states, cursor_rep):
    """What ``_count_round`` needs of the state BEFORE a round."""
    return states.committed_upto[:, cursor_rep], states.crt_own


def _count_round(cfg: MinPaxosConfig, cursor_rep, before, states, counts,
                 r=None, inj=None, hist=None):
    """One round of a multi-owner pod, in COMMANDS: (counts', inj',
    hist') — ``counts`` is ``N_COUNTS``; ``inj`` and ``hist`` are the
    latency ring and histogram of ``sharded_run_resident``, or None.

    Under rotating ownership the merged log interleaves five owners'
    slots and holds no-ops (slots an owner ceded, a peer's SKIP
    covered, a takeover filled), so the frontier's slot number counts
    no commands and no one cursor says what was assigned. Read instead:

    * assigned: owner o's own slots in [crt_own before, crt_own after)
      that hold a command in o's OWN log (what it proposed into; the
      rest of that span it ceded);
    * committed: the slots (u_prev, u_new] the cursor replica's
      frontier passed this round, split by the op its log holds there
      into commands and no-op slots. They are still in its window: a
      round's frontier moves by at most window - retention slots and
      the window keeps ``retention`` = window / 2 behind the executed
      prefix (models/mencius.py section 12);
    * latency, per command: rounds from the round its owner assigned
      its slot (stamped on the ring for every slot of the span above)
      to the round the cursor's MERGED frontier passed it, which waits
      on every owner's earlier slots. -1 stamps (assigned before the
      ring was armed) stay out of the sample.

    The ring is anchored at the cursor's u_prev + 1 and cannot alias
    while every owner's cursor stays within a window of it."""
    u_prev, co_prev = before
    w, n = cfg.window, cfg.n_replicas
    idx = jnp.arange(w, dtype=jnp.int32)
    co_new = states.crt_own
    noop = jnp.uint8(int(Op.NONE))
    slot_own = states.window_base[..., None] + idx           # [G, R, W]
    assigned = ((slot_own >= co_prev[..., None])
                & (slot_own < co_new[..., None])
                & (jnp.mod(slot_own, n)
                   == jnp.arange(n, dtype=jnp.int32)[:, None])
                & (states.op != noop)).sum(dtype=jnp.int32)
    # ring position -> the one slot of [u_prev + 1, u_prev + 1 + W) there
    a = (u_prev + 1)[:, None]
    slot = a + jnp.mod(idx[None, :] - a, w)                  # [G, W]
    u_new = states.committed_upto[:, cursor_rep]
    passed = slot <= u_new[:, None]
    rel = slot - states.window_base[:, cursor_rep][:, None]
    op_there = jnp.take_along_axis(states.op[:, cursor_rep],
                                   jnp.clip(rel, 0, w - 1), axis=1)
    is_cmd = passed & (rel >= 0) & (rel < w) & (op_there != noop)
    commands = is_cmd.sum(dtype=jnp.int32)
    counts = counts + jnp.stack(
        [commands, passed.sum(dtype=jnp.int32) - commands, assigned])
    if inj is None:
        return counts, None, None
    owner = jnp.mod(slot, n)
    inj = jnp.where(
        (slot >= jnp.take_along_axis(co_prev, owner, axis=1))
        & (slot < jnp.take_along_axis(co_new, owner, axis=1)), r, inj)
    bins = jnp.clip(r - inj, 0, hist.shape[0] - 1)  # latency - 1
    hist = hist.at[bins.reshape(-1)].add(
        (is_cmd & (inj >= 0)).reshape(-1).astype(hist.dtype))
    return counts, inj, hist


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 9, 10, 11),
                   donate_argnums=(4, 12))
def sharded_run(cfg: MinPaxosConfig, n_shards: int, ext_rows: int,
                k_rounds: int, ss: ClusterState, n_proposals, leader, round0,
                seed=0, step_impl=None, key_space: int = 1 << 20,
                substeps: int = 1, counts=None):
    """k protocol rounds in ONE dispatch via ``lax.scan``.

    The per-round host round-trip (dispatch + cursor reads) dominated
    wall time on a remote device (BENCH_r02: seconds per round for
    milliseconds of device compute); fusing k rounds amortizes it k-fold
    and lets XLA pipeline the rounds. Proposals are device-generated per
    round (ops/workload.py propose_batch at round0+t — the workload
    never leaves the chip), and the leader's per-shard
    (committed_upto, crt_inst) cursors
    are recorded per round as scan outputs, so the bench reconstructs
    exact per-slot inject/commit rounds from ONE [k, G] transfer.

    ``substeps``: extra no-new-proposal cluster steps appended to each
    round (static, unrolled inside the scan body). The commit pipeline
    is propose -> accept -> ack -> commit = 3 message deliveries;
    substeps=2 delivers twice per round so a slot commits in ~2 rounds
    instead of 3 — commit-on-quorum within the round the quorum forms.
    Each round costs proportionally more device time, so this trades
    throughput-per-dispatch for commit latency IN ROUNDS; the bench
    measures whether wall-clock p50 wins at a given shape and reports
    whichever it measured (VERDICT round-4 item 5).

    A multi-owner pod (Mencius; trace-time, ``_has_owners``) draws the
    per-owner stream and carries ``counts`` (``N_COUNTS``, donated;
    ``_count_round``); the cursor histories stay SLOTS of replica 0's
    merged log, no-op slots included.

    Returns (ss', uptos [k, G], crts [k, G]), and counts' after them
    for a multi-owner pod.
    """

    step = replica_step_impl if step_impl is None else step_impl
    cursor_rep = jnp.maximum(leader, 0)  # mencius (-1): replica 0's view
    owners = _has_owners(ss.states)
    cstep = functools.partial(sharded_round, cfg, step,
                              small_tier_rows(cfg, ext_rows, owners))
    _pretrace_kernels(cfg, step, ss, ext_rows)
    ts = jnp.arange(k_rounds, dtype=jnp.int32)
    # PRNG lanes for ALL k rounds in one batched call, hoisted out of
    # the scan body (ops/workload.py workload_lanes: per-round tracing
    # of Threefry cost ~40 ms/dispatch in XLA-CPU op overhead)
    keys, vals = workload_lanes(n_shards, ext_rows, round0 + ts, seed,
                                key_space,
                                owners=cfg.n_replicas if owners else 0)

    def body(carry, xs):
        ss, counts = carry
        t, key_t, val_t = xs
        if owners:
            before = _owner_cursors(ss.states, cursor_rep)
        ext = assemble_batch(cfg.n_replicas, n_shards, ext_rows,
                             n_proposals, leader, round0 + t, key_t, val_t)
        if round_sections(step):
            ss, _, _ = transfer_round(cfg, step, ss)
        ss, *_ = cstep(ss, ext)
        # drain-only sub-steps: deliver queued traffic, no new work —
        # the ext batch is ZERO-WIDTH, not zero-filled, so the kernel
        # (and the routed pool behind it) runs at the inbox capacity
        # alone instead of inbox + ext_rows; an all-padding ext region
        # was inert anyway, so the commit stream is unchanged (PR 11)
        ext0 = jax.tree_util.tree_map(lambda x: x[..., :0], ext)
        for _ in range(substeps - 1):
            ss, *_ = cstep(ss, ext0)
        if owners:
            counts, _, _ = _count_round(cfg, cursor_rep, before, ss.states,
                                        counts)
        return (ss, counts), (ss.states.committed_upto[:, cursor_rep],
                              ss.states.crt_inst[:, cursor_rep])

    (ss, counts), (uptos, crts) = jax.lax.scan(body, (ss, counts),
                                               (ts, keys, vals))
    return (ss, uptos, crts, counts) if owners else (ss, uptos, crts)


# paxlint: resident-loop
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 13, 14, 15),
                   donate_argnums=(4, 5, 6, 7, 8, 17, 18, 19))
def sharded_run_resident(cfg: MinPaxosConfig, n_shards: int, ext_rows: int,
                         k_rounds: int, ss: ClusterState, inject_round,
                         lat_hist, telemetry, tiers, n_proposals, leader,
                         round0, seed=0, step_impl=None,
                         key_space: int = 1 << 20, substeps: int = 1,
                         tel_base=0, counts=None, gate_opens=None,
                         recovery=None):
    """k rounds in ONE dispatch with nothing read back but two scalars.

    The fully device-resident measured loop (ISSUE 8): workload rows
    are synthesized inside the scan (ops/workload.py — zero
    host->device transfers in steady state), round state and the
    latency bookkeeping buffers are DONATED (in-place update, no
    per-dispatch allocation of the big tree), and per-slot quorum
    latency is accumulated on device instead of shipping [k, G] cursor
    histories to the host every dispatch:

    * ``inject_round`` [G, window] — for each in-flight slot (ring
      position ``slot % window``), the absolute round it was assigned;
      -1 marks slots injected before the measured window began, which
      are excluded from the sample exactly as the host-side
      ``_latency_rounds`` excludes slots below its pre-phase cursor
      row. The window ring cannot alias: a slot s' = s + window can
      only be assigned after s executed (the window slides past the
      executed prefix only), and s executes only after committing.
    * ``lat_hist`` [LATENCY_BINS] — count of committed slots per exact
      integer round latency (inject and commit in the same round = 1).
      Latencies are integers, so the bench reconstructs the exact
      sample (``np.repeat``) and the percentiles match the host path
      to the bit; the last bin is overflow and is reported, never
      silently clipped.
    * ``telemetry`` [rounds, N_TEL_FIELDS] — the paxray ring (ISSUE
      9): one int32 row per round (obs/recorder.py layout — committed
      delta, in-flight, assigned/injected/inbox/claim row counts,
      election/steady flag) written at index ``(round - tel_base) mod
      rounds``, read back once after the measured window exactly like
      the histogram. A ZERO-ROW buffer is the off switch: the writes
      drop out of the trace at compile time (every benchmark cell
      runs that dispatch). Telemetry never touches protocol
      state — state is byte-identical on/off (tests/test_paxray.py).
    * ``tiers`` int32[3] — how often the two-tier round
      (``sharded_round``) engaged: rounds whose kernel ran at the
      working capacity, rounds whose route did, rounds in all (a round
      of several sub-steps counts as small when each of them was).
      Read back after the window like the histogram.
    * ``gate_opens`` int32[``recovery_sections``] (None: zeros) — per
      recovery section of the step, the rounds in which its gate was
      OPEN (in any of a round's sub-steps): the round then ran the
      kernel with every section, else the steady one where its rows
      fit the small tier (``sharded_round``). Beside ``tiers`` and read
      with it: 0 over a window with no recovery in it.
    * ``recovery`` int32[2 n + 1] for a step that declares n round
      sections (``transfer_round``; None for any other, whose program
      holds nothing of it): per section the rounds in which its gate
      was open, then per section what it did while open (installs of
      ``px.state_transfer``), then the rounds at whose END some LIVE
      replica's ``committed_upto`` trailed its group leader's by more
      than twice the round's proposals (a healthy follower trails by
      one round's). Read with ``tiers``.

    A multi-owner pod (Mencius; a trace-time choice on the state's
    structure, ``_has_owners``, so the single-leader program is
    untouched) differs in three things. Each owner is proposed its OWN
    rows (ops/workload.py, the multi-owner stream), ``n_proposals`` of
    them, one count per owner ([R]; an owner at 0 idles and cedes).
    ``counts`` (``N_COUNTS``, donated, cumulative from boot) is carried
    and the two scalars are COMMANDS: committed (no-op slots apart)
    and assigned but not committed. And ring and histogram are per
    command, from its owner's assignment to the cursor replica's
    merged frontier (``_count_round`` has the definitions).

    Returns (ss', inject_round', lat_hist', telemetry', tiers',
    committed_total, in_flight, counts' or None, gate_opens',
    recovery' or None) — the two
    scalars are the per-dispatch cursors (committed frontier for
    throughput progress, assigned-but-uncommitted count for the drain
    loop's exactness check).
    """
    step = replica_step_impl if step_impl is None else step_impl
    cursor_rep = jnp.maximum(leader, 0)
    owners = _has_owners(ss.states)
    cstep = functools.partial(sharded_round, cfg, step,
                              small_tier_rows(cfg, ext_rows, owners))
    _pretrace_kernels(cfg, step, ss, ext_rows)
    if gate_opens is None:
        gate_opens = jnp.zeros(
            len(recovery_sections(step)), jnp.int32)
    if recovery is None:
        recovery = recovery_counts(step)
    w = cfg.window
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]  # [1, W] ring positions
    ts = jnp.arange(k_rounds, dtype=jnp.int32)
    tel_on = telemetry.shape[0] > 0  # trace-time: off = PR-8 dispatch
    # all k rounds' PRNG lanes, hoisted out of the scan (see sharded_run)
    with jax.named_scope("px.workload"):
        keys, vals = workload_lanes(n_shards, ext_rows, round0 + ts, seed,
                                    key_space,
                                    owners=cfg.n_replicas if owners else 0)
    # steady/election flag source: MinPaxos-family states carry
    # ``prepared`` [G, R]; Mencius has no elections (rotating
    # ownership), so every round is steady. Structural, trace-time.
    has_prepared = getattr(ss.states, "prepared", None) is not None

    def body(carry, xs):
        ss, inj, hist, tel, tiers, counts, gate_opens, recovery = carry
        t, key_t, val_t = xs
        r = round0 + t
        if recovery is not None:
            ss, rs_open, rs_acts = transfer_round(cfg, step, ss)
        u_prev = ss.states.committed_upto[:, cursor_rep]
        c_prev = ss.states.crt_inst[:, cursor_rep]
        if owners:
            before = _owner_cursors(ss.states, cursor_rep)
        if tel_on:
            with jax.named_scope("px.telemetry"):
                e_prev = ss.states.executed_upto[:, cursor_rep]
                # routed peer rows awaiting delivery = this round's
                # inbox; the max per-(shard, replica) DELIVERED rows
                # (routed + injected — injection has a closed form, see
                # `injected` below) is the occupancy one inbox must
                # hold (TEL_INBOX_HWM)
                pending_live = (ss.pending.kind != 0).sum(axis=-1)
                inbox_rows = pending_live.sum()
                ext_live = jnp.where(
                    (jnp.arange(cfg.n_replicas) == leader) | (leader < 0),
                    n_proposals, 0)
                inbox_hwm = (pending_live + ext_live[None, :]).max()
        with jax.named_scope("px.workload"):
            ext = assemble_batch(cfg.n_replicas, n_shards, ext_rows,
                                 n_proposals, leader, r, key_t, val_t)
        ss, _, small, gate_open = cstep(ss, ext)
        # zero-WIDTH drain sub-steps (see sharded_run): smaller static
        # kernel shape, identical commit stream
        ext0 = jax.tree_util.tree_map(lambda x: x[..., :0], ext)
        for _ in range(substeps - 1):
            if tel_on:
                # drain sub-steps deliver pending rows too: fold each
                # drain delivery into the round's sum and hwm, or a
                # substeps>1 run undercounts the occupancy that sizes
                # adaptive capacity (TEL_INBOX_HWM)
                drain_live = (ss.pending.kind != 0).sum(axis=-1)
                inbox_rows = inbox_rows + drain_live.sum()
                inbox_hwm = jnp.maximum(inbox_hwm, drain_live.max())
            ss, _, small_d, open_d = cstep(ss, ext0)
            small, gate_open = small & small_d, gate_open | open_d
        tiers = tiers + jnp.append(small, True).astype(tiers.dtype)
        gate_opens = gate_opens + gate_open.astype(gate_opens.dtype)
        with jax.named_scope("px.lat_hist"):
            u_new = ss.states.committed_upto[:, cursor_rep]
            if recovery is not None:
                lagging = (ss.alive & (u_new[:, None]
                                       - ss.states.committed_upto
                                       > 2 * n_proposals)).any()
                recovery = recovery + jnp.concatenate([
                    rs_open.astype(recovery.dtype), rs_acts,
                    lagging.astype(recovery.dtype)[None]])
            c_new = ss.states.crt_inst[:, cursor_rep]
            if owners:
                counts, inj, hist = _count_round(
                    cfg, cursor_rep, before, ss.states, counts, r, inj, hist)
            else:
                # stamp this round on slots assigned this round:
                # [c_prev, c_new)
                cp = c_prev[:, None]
                slot = cp + jnp.mod(pos - cp, w)  # abs slot per ring pos
                inj = jnp.where(slot < c_new[:, None], r, inj)
                # commit latencies for slots committed this round:
                # [u_prev+1, u_new]
                up = u_prev[:, None] + 1
                cslot = up + jnp.mod(pos - up, w)
                sampled = (cslot <= u_new[:, None]) & (inj >= 0)
                bins = jnp.clip(r - inj, 0, hist.shape[0] - 1)  # latency-1
                hist = hist.at[bins.reshape(-1)].add(
                    sampled.reshape(-1).astype(hist.dtype))
        if tel_on:
            with jax.named_scope("px.telemetry"):
                prep = (ss.states.prepared[:, cursor_rep].sum(
                            dtype=jnp.int32)
                        if has_prepared else jnp.int32(n_shards))
                # injected rows have a closed form (assemble_batch
                # masks col < n_proposals, times G shards, times every
                # owner in mencius mode) — cheaper than reducing
                # ext.kind [G, R, M] on XLA-CPU, where per-op thunk
                # cost is what the 2% obs_smoke overhead gate feels
                injected = n_shards * ext_live.sum() if owners else (
                    n_shards * n_proposals
                    * jnp.where(leader >= 0, 1, cfg.n_replicas))
                row = telemetry_row(
                    round_idx=r,
                    committed_delta=(u_new - u_prev).sum(),
                    in_flight=(c_new - 1 - u_new).sum(),
                    assigned=(c_new - c_prev).sum(),
                    injected_rows=injected,
                    inbox_rows=inbox_rows,
                    claim_rows=(ss.states.executed_upto[:, cursor_rep]
                                - e_prev).sum(),
                    prepared_shards=prep,
                    inbox_hwm=inbox_hwm)
                tel = jax.lax.dynamic_update_index_in_dim(
                    tel, row,
                    jnp.mod(r - tel_base, telemetry.shape[0]), 0)
        return (ss, inj, hist, tel, tiers, counts, gate_opens,
                recovery), None

    (ss, inject_round, lat_hist, telemetry, tiers, counts,
     gate_opens, recovery), _ = jax.lax.scan(
        body, (ss, inject_round, lat_hist, telemetry, tiers, counts,
               gate_opens, recovery), (ts, keys, vals))
    if owners:
        return (ss, inject_round, lat_hist, telemetry, tiers,
                counts[0], counts[2] - counts[0], counts, gate_opens,
                recovery)
    upto = ss.states.committed_upto[:, cursor_rep]
    crt = ss.states.crt_inst[:, cursor_rep]
    return (ss, inject_round, lat_hist, telemetry, tiers,
            (upto + 1).sum(), (crt - 1 - upto).sum(), None, gate_opens,
            recovery)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def set_alive(cfg: MinPaxosConfig, ss: ClusterState, replica, value):
    """Fault injection across all shards: flip one replica's alive bit
    (the programmatic kill/revive of the reference's scripts, on
    device)."""
    return ss._replace(alive=ss.alive.at[:, replica].set(value))


@functools.partial(jax.jit, static_argnums=0)
def commit_totals(cfg: MinPaxosConfig, ss: ClusterState):
    """(total committed instances across shards at the leader-0 view,
    min committed_upto, max committed_upto) — the bench's progress
    probe, one scalar transfer each. SLOTS of the log: under Mencius
    they include no-op slots (``ShardedCluster.committed`` reports
    commands there)."""
    upto = ss.states.committed_upto[:, 0]
    return (upto + 1).sum(), upto.min(), upto.max()


@functools.partial(jax.jit, static_argnums=0)
def count_commands(cfg: MinPaxosConfig, before, ss: ClusterState, counts):
    """``_count_round`` for a round stepped from the host
    (``ShardedCluster.step``): counts' from the cursors read before it
    (``_owner_cursors`` at replica 0) and the state after."""
    return _count_round(cfg, 0, before, ss.states, counts)[0]


@functools.partial(jax.jit, static_argnums=(0, 1))
def shard_cursors(cfg: MinPaxosConfig, leader: int, ss: ClusterState):
    """Per-shard (committed_upto, crt_inst) at the leader replica —
    [G] each. The bench reads these once per step to reconstruct exact
    per-slot quorum-decision latency: slots assigned in step t are
    crt[t-1]..crt[t]-1, and slots committed in step t are
    upto[t-1]+1..upto[t]."""
    return (ss.states.committed_upto[:, leader],
            ss.states.crt_inst[:, leader])


class ShardedCluster:
    """Host wrapper for the sharded bench/tests: boot -> elect ->
    feed device-generated proposals -> step. Mirrors models/cluster.py's
    Cluster but with everything hot staying on device."""

    def __init__(self, cfg: MinPaxosConfig, n_shards: int,
                 ext_rows: int = 512, mesh=None, protocol: str = "minpaxos",
                 key_space: int = 1 << 20, seed: int = 0):
        self.cfg = cfg
        self.n_shards = n_shards
        self.ext_rows = ext_rows
        self.mesh = mesh
        self.protocol = protocol
        # workload PRNG key base: the whole run's proposal stream is a
        # pure function of (seed, round counter) — bit-reproducible
        self.seed = seed
        # distinct keys per shard the device workload draws from; keep
        # below the KV capacity (1 << cfg.kv_pow2) or long benches
        # saturate the table (kv.dropped) and probe chains degenerate —
        # the reference's clients likewise reuse a bounded key array
        # (client.go:68-103 karray)
        self.key_space = key_space
        if protocol == "mencius":
            from minpaxos_tpu.models.mencius import (
                init_mencius,
                mencius_step_impl,
            )

            self._init_fn, self._step_impl = init_mencius, mencius_step_impl
            self.leader = -1  # multi-leader: proposals go to every owner
        else:  # minpaxos / classic paxos (protocol picked by cfg flag)
            self._init_fn, self._step_impl = init_replica, replica_step_impl
            self.leader = 0
        self.ss = init_sharded(cfg, n_shards, mesh, self._init_fn)
        # the kernel every path steps, traced here, outside any jit
        _pretrace_kernels(cfg, self._step_impl, self.ss, ext_rows,
                          tiers=False)
        # the step's recovery sections, in ``sharded_round``'s order
        self._sections = recovery_sections(self._step_impl)
        # and its round sections (``transfer_round``)
        self._round_sections = tuple(round_sections(self._step_impl))
        self._recovery = None
        self._seed = 0
        # a multi-owner pod counts COMMANDS beside the log's slots
        # (``N_COUNTS``), on the device, in every path that steps it
        self._counts = self._counts_armed = None
        if _has_owners(self.ss.states):
            self._counts = self._replicated(jnp.zeros(N_COUNTS, jnp.int32))
        # what the resident loop leaves for a reader after the run
        # (obs.process_pods()): filled on the post-window path only
        # the resident loop's host side, a dispatch a column: what the
        # two pod spans measured (obs.process_pods(): dispatch_ns,
        # readback_ns); restarts with every begin_resident
        self._host_ns = np.zeros((2, POD_HOST_RING), np.int64)
        self._pod = register_pod({
            "dispatches": 0, "dispatch_ns": self._host_ns[0],
            "readback_ns": self._host_ns[1],
            "protocol": protocol, "n_shards": n_shards,
            "n_replicas": cfg.n_replicas, "inbox": cfg.inbox,
            "working_capacity": small_tier_rows(
                cfg, ext_rows, _has_owners(self.ss.states)),
            "tiers": None, "gates": None, "command_commits": None,
            "noop_slots": None, "round_gates": None,
            "state_transfers": None, "state_transfer_bytes": None,
            "lagging_rounds": None})

    def _replicated(self, x):
        """A cross-shard reduction's buffer: replicated on the mesh, to
        match the dispatch's output sharding (``begin_resident``)."""
        if self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def elect(self, leader: int = 0) -> None:
        if self.protocol == "mencius":
            raise ValueError("mencius has no elections (rotating ownership)")
        self.ss = elect_all(self.cfg, self.ss, leader)
        self.leader = leader
        self.step(0)  # deliver PREPAREs
        self.step(0)  # deliver replies -> leader prepared

    def step(self, n_proposals: int) -> None:
        owners = self._counts is not None
        ext = make_propose_ext(
            self.cfg, self.n_shards, self.ext_rows,
            jnp.int32(min(n_proposals, self.ext_rows)),
            jnp.int32(self.leader), jnp.int32(self._seed),
            jnp.int32(self.seed), self.key_space, owners)
        if owners:  # copies: the step donates the state they are read off
            before = jax.tree_util.tree_map(
                jnp.copy, _owner_cursors(self.ss.states, 0))
        if self.mesh is not None:
            ext = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(self.mesh, P("shard"))), ext)
        self._seed += 1
        self.ss, _, _, _ = sharded_step(self.cfg, self.ss, ext,
                                        self._step_impl)
        if owners:
            self._counts = count_commands(self.cfg, before, self.ss,
                                          self._counts)

    def committed(self) -> tuple[int, int, int]:
        """(committed in all, lowest and highest committed slot over
        the shards) at replica 0. The first is log instances, which a
        single-leader log fills with commands only; for a multi-owner
        pod it is client COMMANDS, its no-op slots left out."""
        tot, lo, hi = commit_totals(self.cfg, self.ss)
        if self._counts is not None:
            tot = self._counts[0]
        return int(tot), int(lo), int(hi)

    def _owner_counts(self, n_proposals):
        """The resident loop's ``n_proposals`` for a multi-owner pod:
        one count per owner, from one for all or a sequence of R."""
        # paxlint: disable=resident-loop -- host ints in, nothing read back
        n = np.minimum(np.broadcast_to(np.asarray(n_proposals, np.int32),
                                       (self.cfg.n_replicas,)),
                       self.ext_rows)
        return jnp.asarray(n, jnp.int32)

    def run_fused(self, k_rounds: int, n_proposals: int,
                  substeps: int = 1):
        """k rounds in one dispatch; returns per-round cursor histories
        (numpy [k, G] committed_upto and crt_inst at the leader).
        Host-in-the-loop readback per dispatch — the reference the
        resident scan is held to byte for byte
        (tests/test_workload.py)."""
        out = sharded_run(
            self.cfg, self.n_shards, self.ext_rows, k_rounds, self.ss,
            jnp.int32(min(n_proposals, self.ext_rows)),
            jnp.int32(self.leader), jnp.int32(self._seed),
            jnp.int32(self.seed), self._step_impl, self.key_space, substeps,
            self._counts)
        self.ss, uptos, crts = out[:3]
        if self._counts is not None:
            self._counts = out[3]
        self._seed += k_rounds
        return np.asarray(uptos), np.asarray(crts)

    # -- device-resident measured loop (ISSUE 8) --

    def begin_resident(self, lat_bins: int = LATENCY_BINS,
                       telemetry_rounds: int = 0) -> None:
        """Arm the resident loop's device-side bookkeeping: a fresh
        inject-round ring (all -1: slots already in flight are excluded
        from the latency sample, mirroring the host path's pre-phase
        cursor row), a zeroed latency histogram, zeroed tier,
        section-gate and recovery counts and — when
        ``telemetry_rounds`` > 0 — the
        paxray telemetry ring (one row per round, round column -1 =
        never written; 0 rows compiles the telemetry-free PR-8
        dispatch)."""
        _pretrace_kernels(self.cfg, self._step_impl, self.ss, self.ext_rows)
        self._inject_round = jnp.full(
            (self.n_shards, self.cfg.window), -1, jnp.int32)
        self._lat_hist = jnp.zeros(lat_bins, jnp.int32)
        self._telemetry = jnp.full((telemetry_rounds, N_TEL_FIELDS), -1,
                                   jnp.int32)
        self._tiers = jnp.zeros(3, jnp.int32)
        self._gate_opens = jnp.zeros(len(self._sections), jnp.int32)
        self._recovery = recovery_counts(self._step_impl)
        # the window's command counts are those since this arming (a
        # copy: the dispatches donate the live buffer)
        self._counts_armed = (None if self._counts is None
                              else jnp.copy(self._counts))
        # ring indices are relative to the round counter at arming
        # time, so re-arming (bench: warmup, then measured phase)
        # restarts the ring at row 0
        self._tel_base = int(self._seed)
        self._pod["dispatches"] = 0  # the host ring restarts too
        if self.mesh is not None:
            # ring rides the shard axis like the state; the histogram,
            # the telemetry rows and the tier and gate counts are
            # cross-shard reductions and are REPLICATED on the mesh —
            # all placed up front to match
            # the dispatch's output shardings exactly, or the second
            # dispatch recompiles (~9 s observed: arm-time
            # SingleDeviceSharding vs XLA's NamedSharding(P()) output
            # for the histogram)
            self._inject_round = jax.device_put(
                self._inject_round,
                NamedSharding(self.mesh, P("shard")))
            self._lat_hist = self._replicated(self._lat_hist)
            self._telemetry = self._replicated(self._telemetry)
            self._tiers = self._replicated(self._tiers)
            self._gate_opens = self._replicated(self._gate_opens)
            if self._recovery is not None:
                self._recovery = self._replicated(self._recovery)

    # paxlint: resident-loop
    def run_resident(self, k_rounds: int, n_proposals,
                     substeps: int = 1) -> tuple[int, int]:
        """k rounds in one dispatch, fully device-resident; returns
        (committed_total, in_flight) — the sanctioned per-dispatch
        scalar readbacks (progress cursor + drain check). Everything
        else (state, inject ring, latency histogram, telemetry ring,
        tier, gate and recovery counts) stays on device in donated
        buffers until
        ``end_resident``. For a multi-owner pod ``n_proposals`` is per
        OWNER (one number for all, or a sequence of R) and the scalars
        count commands (``sharded_run_resident``)."""
        # the span holds all the device waits for between two
        # dispatches on this side of the call: the host-made scalars
        # (each a small transfer) and the call itself
        with phase(PH_POD_DISPATCH) as sent:
            if self._counts is None:
                n_prop = jnp.int32(min(n_proposals, self.ext_rows))
            else:
                n_prop = self._owner_counts(n_proposals)
            (self.ss, self._inject_round, self._lat_hist, self._telemetry,
             self._tiers, committed, in_flight, self._counts,
             self._gate_opens, self._recovery) = sharded_run_resident(
                self.cfg, self.n_shards, self.ext_rows, k_rounds, self.ss,
                self._inject_round, self._lat_hist, self._telemetry,
                self._tiers, n_prop,
                jnp.int32(self.leader), jnp.int32(self._seed),
                jnp.int32(self.seed), self._step_impl, self.key_space,
                substeps, jnp.int32(self._tel_base), self._counts,
                self._gate_opens, self._recovery)
        self._seed += k_rounds
        # the per-dispatch scalar readback — the ONLY host sync in the
        # measured steady state (paxlint's resident-loop rule keeps it
        # that way; this suppression marks the sanctioned boundary)
        with phase(PH_POD_READBACK) as read:
            # paxlint: disable=resident-loop -- sanctioned scalar readback
            out = int(committed), int(in_flight)
        pod = self._pod
        at = pod["dispatches"] % POD_HOST_RING
        self._host_ns[0, at] = sent.ns
        self._host_ns[1, at] = read.ns
        pod["dispatches"] += 1
        return out

    def resident_hist(self) -> np.ndarray:
        """Snapshot the device histogram WITHOUT disarming — the
        bench's early-emit path after a measured window whose fault leg
        hasn't run yet (still a post-window read, never per-dispatch).
        Takes the tier counts with it (``resident_tiers``)."""
        self.resident_tiers()
        return np.asarray(self._lat_hist)

    def resident_tiers(self) -> dict:
        """How often the two-tier round engaged since
        ``begin_resident``: ``kernel_small_rounds`` and
        ``route_small_rounds`` (rounds whose kernel, and whose route,
        ran at ``working_capacity`` rows), ``rounds`` in all, and the
        two capacities, with, under ``gates``, the rounds in which the
        gate of each recovery section of the step was open, by ``px.*``
        scope (``sharded_round``). A post-window read by the same
        discipline as ``resident_telemetry``; the reading is kept, as
        of this call,
        in ``obs.process_pods()`` — with, for a multi-owner pod, the
        ``command_commits`` and ``noop_slots`` the cursor replica's
        frontier passed over the same rounds. A pod whose step declares
        round sections (``transfer_round``) adds its recovery counts
        over the same rounds: ``round_gates`` (as ``gates``, for the
        round's own sections: ``px.state_transfer``),
        ``state_transfers`` (installs done), ``state_transfer_bytes``
        (what they copied from their donors: installs x the KV table's
        bytes) and ``lagging_rounds`` (rounds at whose end a live
        replica trailed its leader by more than two rounds'
        proposals)."""
        kernel_small, route_small, rounds = np.asarray(self._tiers).tolist()
        self._pod["tiers"] = {"kernel_small_rounds": kernel_small,
                              "route_small_rounds": route_small,
                              "rounds": rounds}
        self._pod["gates"] = dict(zip(
            self._sections, np.asarray(self._gate_opens).tolist()))
        if self._counts is not None:
            commands, noops, _ = (np.asarray(self._counts)
                                  - np.asarray(self._counts_armed)).tolist()
            self._pod.update(command_commits=commands, noop_slots=noops)
        out = {**self._pod["tiers"], "gates": dict(self._pod["gates"]),
               "working_capacity": self._pod["working_capacity"],
               "inbox": self._pod["inbox"]}
        if self._recovery is not None:
            n = len(self._round_sections)
            *counts, lagging = np.asarray(self._recovery).tolist()
            installs = dict(zip(self._round_sections,
                                counts[n:]))["px.state_transfer"]
            recovery = {
                "round_gates": dict(zip(self._round_sections, counts[:n])),
                "state_transfers": installs,
                "state_transfer_bytes": installs * transfer_bytes(self.cfg),
                "lagging_rounds": lagging}
            self._pod.update(recovery)
            out.update(recovery, round_gates=dict(recovery["round_gates"]))
        return out

    def command_counts(self) -> dict:
        """A multi-owner pod's counts, cumulative from boot, at the
        cursor replica (0): client ``commands`` and ``noop_slots`` its
        merged frontier has passed (together: every slot up to it) and
        commands ``assigned`` a slot by their owners. Blocks on the
        device: a post-window read."""
        commands, noops, assigned = np.asarray(self._counts).tolist()
        return {"commands": commands, "noop_slots": noops,
                "assigned": assigned}

    def resident_telemetry(self) -> np.ndarray:
        """The paxray post-window telemetry readback: written rows
        sorted by round ([n, N_TEL_FIELDS] numpy,
        obs/recorder.py layout). A post-window read by the same
        discipline as ``end_resident`` — NEVER call it between
        measured dispatches (paxlint's resident-loop pass flags any
        call site reachable from a marked dispatch root). Call before
        ``end_resident`` (which disarms the ring)."""
        return telemetry_valid_rows(np.asarray(self._telemetry))

    def end_resident(self):
        """The once-after-the-measured-window full readback: returns
        the latency histogram (numpy [LATENCY_BINS], exact integer
        round latencies) and disarms the resident bookkeeping
        (telemetry included — read ``resident_telemetry`` first)."""
        hist = self.resident_hist()
        self._inject_round = None
        self._lat_hist = None
        self._telemetry = None
        self._tiers = self._gate_opens = self._recovery = None
        return hist

    def kill(self, replica: int) -> None:
        self.ss = set_alive(self.cfg, self.ss, jnp.int32(replica), False)

    def revive(self, replica: int) -> None:
        self.ss = set_alive(self.cfg, self.ss, jnp.int32(replica), True)
