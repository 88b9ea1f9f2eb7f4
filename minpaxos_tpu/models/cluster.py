"""Pod-mode cluster: every replica resident on the accelerator, one
jitted step advancing them all.

This is the TPU-native reframing SURVEY.md section 7.1 calls for: where
the reference runs N processes exchanging TCP messages
(genericsmr.go:125-172), pod mode stacks the N replicas' states along a
leading array axis, runs the identical per-replica protocol step under
``vmap``, and *routes messages as array ops*: each replica's outbox rows
carry a ``dst``; routing pools all outboxes and compacts each replica's
addressed rows into its next inbox in ONE segmented pass (a single
segment-prefix-sum over the pooled rows + a scatter-free rank-select
winner — ops/segscatter.py; the original per-destination cumsum-scatter
fabric survives behind ``route_fabric="dense"`` for the byte-equality
pin). Replica failure is a mask (see ``alive``): a dead replica's rows
are dropped and its inbox zeroed — the programmatic version of the
reference's kill/revive scripts.

The same ``replica_step_impl`` drives both this mode and the
distributed TCP runtime, so protocol correctness proven here (against
the oracle in tests/test_minpaxos_protocol.py) transfers to the wire.

Sharding: models/cluster.py is mesh-agnostic; parallel/sharded.py lays
the shard axis of a sharded-Paxos deployment over devices with the
replica axis inside each shard.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from minpaxos_tpu.models.minpaxos import (
    ExecResult,
    MinPaxosConfig,
    MsgBatch,
    ReplicaState,
    _concat_rows,
    become_leader,
    init_replica,
    replica_step_impl,
)
from minpaxos_tpu.ops.packed import join_i64, split_i64
from minpaxos_tpu.ops.segscatter import gather_rows, plan_slots, route_counts
from minpaxos_tpu.ops.winner import gather_row, slot_winner
from minpaxos_tpu.wire.messages import MsgKind, Op


class ClusterState(NamedTuple):
    states: ReplicaState  # stacked, leading axis R
    pending: MsgBatch  # [R, M] routed but undelivered messages
    alive: jnp.ndarray  # bool[R] failure-injection mask


def _tree_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def tree_slice(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def tree_set(tree, i, sub):
    return jax.tree_util.tree_map(lambda x, s: x.at[i].set(s), tree, sub)


def _route(cfg: MinPaxosConfig, out_msgs: MsgBatch, dst: jnp.ndarray,
           alive: jnp.ndarray, capacity: int) -> MsgBatch:
    """The ORIGINAL dense routing fabric (``route_fabric="dense"``):
    pool all replicas' outboxes and build each replica's next inbox
    with a masked cumsum + scatter per destination.

    dst semantics: -1 broadcast to all *other* replicas, >=0 unicast,
    -2 client-bound (excluded here; the host collects those).
    Overflow beyond ``capacity`` rows is dropped — legal under Paxos
    (message loss), sized to be impossible in steady state.

    Kept for the byte-equality pin of the segmented fabric
    (tests/test_route_fabric.py); O(R²·M) scans plus a per-destination
    scatter that serializes on XLA:CPU — ``_route_segmented`` replaces
    it on the hot path (PR 11).
    """
    r = cfg.n_replicas
    flat = jax.tree_util.tree_map(lambda x: x.reshape(-1), out_msgs)  # [R*M]
    src_rep = jnp.repeat(jnp.arange(r), out_msgs.kind.shape[1])
    fdst = dst.reshape(-1)
    live_src = alive[src_rep]

    def inbox_for(me):
        mine = (flat.kind != 0) & live_src & alive[me] & (src_rep != me) & (
            (fdst == -1) | (fdst == me))
        pos = jnp.cumsum(mine.astype(jnp.int32)) - 1
        tgt = jnp.where(mine & (pos < capacity), pos, capacity)
        # ONE scatter of the source row index (positions are unique by
        # construction), then a dense gather per column: per-column
        # scatters serialize on TPU (ops/winner.py rationale)
        win, hit = slot_winner(capacity, tgt, mine & (pos < capacity))
        return jax.tree_util.tree_map(
            lambda col: gather_row(win, hit, col,
                                   jnp.zeros(capacity, col.dtype)),
            flat)

    return jax.vmap(inbox_for)(jnp.arange(r))


def _pool_counts(out_msgs: MsgBatch, dst: jnp.ndarray,
                 alive: jnp.ndarray) -> tuple[MsgBatch, jnp.ndarray]:
    """First half of the segmented fabric: the replicas' outboxes
    pooled to [R*M] rows, and the segment-prefix-sum over them
    (ops/segscatter.py ``route_counts``: cnt[d, -1] is the number of
    rows destination d is sent)."""
    r, m = out_msgs.kind.shape
    with jax.named_scope("px.route.plan"):
        flat = jax.tree_util.tree_map(lambda x: x.reshape(-1), out_msgs)
        src_rep = jnp.repeat(jnp.arange(r, dtype=jnp.int32), m)
        return flat, route_counts(flat.kind, src_rep, dst.reshape(-1),
                                  alive)


def _fill_inboxes(flat: MsgBatch, cnt: jnp.ndarray, slots: int,
                  capacity: int) -> MsgBatch:
    """Second half: the winner of each of the first ``slots`` slots of
    every inbox and the 12 gathers that fill them, zero-padded to
    ``capacity`` rows. Filled slots are a prefix, so with every count
    <= ``slots`` this is the ``capacity``-slot inbox byte for byte."""
    with jax.named_scope("px.route.plan"):
        win, hit = plan_slots(cnt, slots)
    with jax.named_scope("px.route.gather"):
        rows = gather_rows(flat, win, hit)
        if slots < capacity:
            rows = jax.tree_util.tree_map(
                lambda x: jnp.pad(x, ((0, 0), (0, capacity - slots))), rows)
        return rows


def _route_segmented(cfg: MinPaxosConfig, out_msgs: MsgBatch,
                     dst: jnp.ndarray, alive: jnp.ndarray,
                     capacity: int) -> MsgBatch:
    """One-pass segmented routing fabric (``route_fabric="segmented"``,
    the default): each pooled outbox row's destination segment is
    computed once, ONE segment-prefix-sum yields per-destination
    offsets (broadcast rows expand in index arithmetic only — the
    payload pool is never copied per destination), and the winner per
    inbox slot is recovered scatter-free by a rank-select over those
    offsets (ops/rankselect.py: vector compares; the binary search it
    replaced in PR 29 was the top device op of both pod cells, ledger
    PR 28). Byte-identical to ``_route``
    including row order and overflow-drop semantics — pinned by
    tests/test_route_fabric.py and the golden kernel fixtures."""
    flat, cnt = _pool_counts(out_msgs, dst, alive)
    return _fill_inboxes(flat, cnt, capacity, capacity)


def _deliver_inbox(pending: MsgBatch, ext: MsgBatch, alive: jnp.ndarray,
                   rows: int | None = None) -> MsgBatch:
    """Merge routed pending rows + host-injected ext rows into the
    inbox the protocol kernel consumes; dead replicas see silence.

    ``rows`` (static) delivers only the first ``rows`` pending slots:
    routing packs each inbox to a prefix, so when no live row lies at
    or beyond slot ``rows`` the cut drops padding alone and every
    [M]-shaped kernel computation runs at rows + ext instead of
    inbox + ext. The caller holds that condition (parallel/sharded.py's
    two-tier round)."""
    if rows is not None and rows < pending.kind.shape[-1]:
        pending = jax.tree_util.tree_map(lambda x: x[..., :rows], pending)
    inbox = _concat_rows(pending, ext)
    return inbox._replace(
        kind=jnp.where(alive[:, None], inbox.kind, 0))


def step_replicas(cfg: MinPaxosConfig, cs: ClusterState, ext: MsgBatch,
                  step_impl=replica_step_impl, rows: int | None = None,
                  steady: bool = False, gates: dict | None = None):
    """Deliver (the first ``rows`` slots of) pending + ext and step all
    replicas of one group: (states', outbox, exec results). ``cfg``
    comes with ``gate_exec`` already off (see ``cluster_step_impl``).
    ``steady`` / ``gates``: for a step that takes the one or the other
    (models/mencius.py, models/minpaxos.py); the gates reach it
    unbatched, or its conditionals would be selects."""
    with jax.named_scope("px.deliver"):
        inbox = _deliver_inbox(cs.pending, ext, cs.alive, rows)
    step = functools.partial(step_impl, cfg)
    if steady:
        step = functools.partial(step, steady=True)
    if gates is None:
        return jax.vmap(step)(cs.states, inbox)
    return jax.vmap(
        lambda state, inbox, gates: step(state, inbox, gates=gates),
        in_axes=(0, 0, None))(cs.states, inbox, gates)


def route_outbox(cfg: MinPaxosConfig, outbox, alive: jnp.ndarray) -> MsgBatch:
    """One group's next pending inboxes from its replicas' outboxes, at
    the configured capacity, through the fabric ``cfg`` selects."""
    route = _route if cfg.route_fabric == "dense" else _route_segmented
    return route(cfg, outbox.msgs, outbox.dst, alive, cfg.inbox)


def client_rows_of(outbox) -> tuple[MsgBatch, jnp.ndarray]:
    """Client-bound rows of an outbox: (rows [R, M_total], mask)."""
    return outbox.msgs, (outbox.dst == -2) & (outbox.msgs.kind != 0)


def cluster_step_impl(
    cfg: MinPaxosConfig, cs: ClusterState, ext: MsgBatch,
    step_impl=replica_step_impl,
) -> tuple[ClusterState, "ExecResult", MsgBatch, jnp.ndarray]:
    """One synchronous round: deliver pending + ext, step all replicas,
    route the new outboxes.

    ext is [R, Mext] host-injected rows (client proposes to the leader,
    PREPAREs from elections). Returns (state', exec results [R, E],
    client-bound rows [R, M_total], client-bound mask).

    ``step_impl`` is the per-replica protocol step (static): MinPaxos /
    classic paxos use replica_step_impl; Mencius passes
    models/mencius.py's mencius_step_impl. The routing fabric is
    protocol-agnostic — it only reads the Outbox.
    """
    # every pod/sharded composition vmaps the replica step, where a
    # gated exec (lax.cond) lowers to select and runs both branches —
    # strip the gate at this choke point so callers don't each have to
    # remember to pass gate_exec=False
    cfg = cfg._replace(gate_exec=False)
    states, outbox, execr = step_replicas(cfg, cs, ext, step_impl)
    pending = route_outbox(cfg, outbox, cs.alive)
    client_rows, client_mask = client_rows_of(outbox)
    return ClusterState(states, pending, cs.alive), execr, client_rows, client_mask


# Jitted entry point for single-group (unsharded) pod mode; parallel/
# sharded.py vmaps cluster_step_impl over a shard axis instead.
cluster_step = jax.jit(cluster_step_impl, static_argnums=(0, 3),
                       donate_argnums=1)


def pack_reply_key(client_id, cmd_id) -> np.ndarray:
    """(client_id, cmd_id) -> one i64 key, vectorized — lets the reply
    collectors prefilter executed rows with ``np.isin`` instead of a
    Python dict probe per row."""
    return (np.asarray(client_id, np.int64) << 32) | (
        np.asarray(cmd_id, np.int64) & 0xFFFFFFFF)


class KeyBuf:
    """Append-only packed-key buffer with amortized-doubling growth:
    O(1) amortized append, zero-copy view. (A chunk-list concatenated
    on read would re-copy the whole proposal history every time a
    collect follows a propose.) Keys are never pruned: a key must
    survive its reply so late duplicate executions (e.g. post-recovery
    replay) still surface as ``duplicate`` entries in the reply log —
    the safety tests assert on exactly that.

    Membership checks go through ``contains``, which keeps a sorted
    snapshot refreshed only when appends happened and probes it with
    ``np.searchsorted`` — ``np.isin`` against ``view()`` would re-sort
    the whole proposal history on EVERY collect call, an O(n log n)
    per-tick cost that grows with the cluster's lifetime."""

    __slots__ = ("_arr", "_n", "_sorted", "_sorted_n")

    def __init__(self) -> None:
        self._arr = np.empty(256, np.int64)
        self._n = 0
        self._sorted = self._arr[:0]
        self._sorted_n = 0

    def append(self, keys) -> None:
        keys = np.atleast_1d(keys)
        need = self._n + len(keys)
        if need > len(self._arr):
            arr = np.empty(max(2 * len(self._arr), need), np.int64)
            arr[: self._n] = self._arr[: self._n]
            self._arr = arr
        self._arr[self._n : need] = keys
        self._n = need

    def view(self) -> np.ndarray:
        return self._arr[: self._n]

    def contains(self, keys: np.ndarray) -> np.ndarray:
        """Vectorized membership: bool mask over ``keys``."""
        if self._sorted_n != self._n:
            self._sorted = np.sort(self._arr[: self._n])
            self._sorted_n = self._n
        v = self._sorted
        if not len(v):
            return np.zeros(len(np.atleast_1d(keys)), bool)
        pos = np.searchsorted(v, keys)
        return v[np.minimum(pos, len(v) - 1)] == keys


def collect_exec_replies(cl, execr: ExecResult, *,
                         drop_skip_fills: bool = False,
                         record_inst: bool = True) -> None:
    """Host side of ReplyProposeTS (genericsmr.go:529), shared by
    Cluster and MenciusCluster (``cl`` needs cfg / _prop_keys /
    _proposed_at / replies / reply_log).

    One transfer per field, then a vectorized group-by prefilter: no-op
    fills (cid < 0; with ``drop_skip_fills`` also Mencius SKIP fills)
    and slots whose client proposed elsewhere drop via one ``np.isin``
    against the replica's proposed-key set. Only rows that become
    actual replies reach the per-row dict writes (the dict IS the
    client-facing API). The final ``_proposed_at`` probe re-checks
    ownership exactly: a key re-proposed to another replica after a
    failover passes the isin prefilter but must not reply here.
    """
    counts = np.asarray(execr.count)
    e_vhi, e_vlo = np.asarray(execr.val_hi), np.asarray(execr.val_lo)
    e_found, e_op = np.asarray(execr.found), np.asarray(execr.op)
    e_cid, e_mid = np.asarray(execr.client_id), np.asarray(execr.cmd_id)
    e_lo = np.asarray(execr.lo) if record_inst else None
    for rep in range(cl.cfg.n_replicas):
        n = int(counts[rep])
        if not n:
            continue
        keys = cl._prop_keys.get(rep)
        if keys is None:
            continue  # nothing ever proposed to this replica
        cid_n, mid_n, op_n = e_cid[rep][:n], e_mid[rep][:n], e_op[rep][:n]
        cand = cid_n >= 0
        if drop_skip_fills:
            cand &= ~((op_n == 0) & (mid_n == 0))
        if not cand.any():
            continue
        cand &= keys.contains(pack_reply_key(cid_n, mid_n))
        idx = np.nonzero(cand)[0]
        if not idx.size:
            continue
        vals = join_i64(e_vhi[rep][idx], e_vlo[rep][idx])
        founds, ops = e_found[rep][idx], op_n[idx]
        for j, i in enumerate(idx):
            cid, mid = int(cid_n[i]), int(mid_n[i])
            if cl._proposed_at.get((cid, mid)) != rep:
                continue  # re-proposed elsewhere since (failover)
            rep_row = dict(ok=True, value=int(vals[j]),
                           found=bool(founds[j]), op=int(ops[j]))
            if record_inst:
                rep_row["inst"] = int(e_lo[rep]) + int(i)
            if (cid, mid) in cl.replies:
                cl.reply_log.append(dict(duplicate=True, client_id=cid,
                                         cmd_id=mid))
            cl.replies[(cid, mid)] = rep_row
            cl.reply_log.append(dict(duplicate=False, client_id=cid,
                                     cmd_id=mid, **rep_row))


class Cluster:
    """Host-side convenience wrapper: boot, propose, crash, recover.

    The programmatic equivalent of the reference's shell harness
    (bareminrun.sh boots master + 3 replicas; kill/revive scripts
    inject faults — SURVEY.md section 4).
    """

    def __init__(self, cfg: MinPaxosConfig, ext_rows: int = 1024):
        # certify the (q1, q2[, qf]) thresholds this config compiles
        # before any kernel runs them (verify/quorum.py; the model
        # checker bypasses this wrapper to plant mutants on purpose)
        from minpaxos_tpu.verify.quorum import validate_config_quorums

        validate_config_quorums(cfg)
        self.cfg = cfg
        self.ext_rows = ext_rows
        states = _tree_stack([init_replica(cfg, i) for i in range(cfg.n_replicas)])
        self.cs = ClusterState(
            states=states,
            pending=jax.tree_util.tree_map(
                lambda x: jnp.zeros((cfg.n_replicas,) + x.shape, x.dtype),
                MsgBatch.empty(cfg.inbox)),
            alive=jnp.ones(cfg.n_replicas, dtype=bool),
        )
        self._ext_queue: list[tuple[int, np.ndarray]] = []  # (replica, rows)
        self.replies: dict[tuple[int, int], dict] = {}  # (client_id, cmd_id) -> reply
        self.reply_log: list[dict] = []
        # replies are connection-scoped: only the replica a client
        # proposed to replies (reference lb.clientProposals,
        # bareminpaxos.go:75-82); other replicas execute silently
        self._proposed_at: dict[tuple[int, int], int] = {}
        # packed-key buffers per replica, the vectorized face of
        # _proposed_at (np.isin prefilter in _collect_exec)
        self._prop_keys: dict[int, KeyBuf] = {}

    # -- control plane --

    @property
    def leader(self) -> int:
        """Leader per the highest-ballot alive replica (what a client
        would learn from GetLeader + ProposeReplyTS.Leader hints)."""
        alive = np.asarray(self.cs.alive)
        ballots = np.asarray(self.cs.states.default_ballot)
        leaders = np.asarray(self.cs.states.leader_id)
        cand = np.where(alive, ballots, -(2**31))
        return int(leaders[int(np.argmax(cand))])

    def elect(self, replica: int) -> None:
        """BeTheLeader: run a real Prepare round via ext PREPARE rows."""
        st = tree_slice(self.cs.states, replica)
        st, prep = become_leader(self.cfg, st)
        states = tree_set(self.cs.states, replica, st)
        self.cs = self.cs._replace(states=states)
        row = jax.tree_util.tree_map(lambda x: np.asarray(x)[0], prep)
        for peer in range(self.cfg.n_replicas):
            if peer != replica:
                self._ext_queue.append((peer, row))

    def kill(self, replica: int) -> None:
        self.cs = self.cs._replace(alive=self.cs.alive.at[replica].set(False))

    def revive(self, replica: int) -> None:
        self.cs = self.cs._replace(alive=self.cs.alive.at[replica].set(True))

    # -- data plane --

    def propose(self, ops, keys, vals, cmd_ids, client_id: int, to: int | None = None):
        """Queue client PROPOSE rows for delivery to ``to`` (default:
        current leader) on the next step. Batches larger than
        ``ext_rows`` are chunked across steps. ``to=-1`` broadcasts
        the rows to EVERY replica — the Fast Flexible Paxos client
        shape (cfg.fast_path: followers fast-accept them directly);
        replies are still tracked at the leader, the only committer."""
        broadcast = to == -1
        if broadcast:
            to = self.leader
        else:
            to = self.leader if to is None else to
        if to < 0:
            raise ValueError("no known leader; call elect() first or pass to=")
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
            key_hi=k_hi.astype(np.int32),
            key_lo=k_lo.astype(np.int32),
            val_hi=v_hi.astype(np.int32),
            val_lo=v_lo.astype(np.int32),
            cmd_id=np.asarray(cmd_ids, dtype=np.int32),
            client_id=np.full(n, client_id, np.int32),
        )
        for mid in np.asarray(cmd_ids, dtype=np.int64):
            self._proposed_at[(client_id, int(mid))] = to
        self._prop_keys.setdefault(to, KeyBuf()).append(
            pack_reply_key(client_id, cmd_ids))
        batch = MsgBatch(**{f: row[f] for f in MsgBatch._fields})
        targets = (range(self.cfg.n_replicas) if broadcast else (to,))
        for tgt in targets:
            for lo in range(0, n, self.ext_rows):
                self._ext_queue.append((tgt, jax.tree_util.tree_map(
                    lambda x: x[lo : lo + self.ext_rows], batch)))

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
        """One cluster round + host-side reply collection."""
        ext = self._drain_ext()
        self.cs, execr, crows, cmask = cluster_step(self.cfg, self.cs, ext)
        self._collect_exec(execr)
        self._collect_client_rows(crows, cmask)

    def run(self, n: int) -> None:
        for _ in range(n):
            self.step()

    # -- reply collection (host side of ReplyProposeTS, genericsmr.go:529) --

    def _collect_exec(self, execr: ExecResult) -> None:
        collect_exec_replies(self, execr)

    def _collect_client_rows(self, crows: MsgBatch, cmask) -> None:
        cmask = np.asarray(cmask)
        if not cmask.any():
            return
        # one transfer per column, then pure-numpy fancy indexing (the
        # old path pulled each element off-device individually)
        kinds = np.asarray(crows.kind)
        sel = cmask & (kinds == int(MsgKind.PROPOSE_REPLY))
        if not sel.any():
            return
        cids = np.asarray(crows.client_id)[sel]
        mids = np.asarray(crows.cmd_id)[sel]
        leaders = np.asarray(crows.ballot)[sel]
        for cid, mid, ldr in zip(cids, mids, leaders):
            self.reply_log.append(dict(
                duplicate=False, client_id=int(cid), cmd_id=int(mid),
                ok=False, leader=int(ldr)))
