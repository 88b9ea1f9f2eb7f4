"""Byte-equality pin of the segmented routing fabric (PR 11).

``_route_segmented`` (one segment-prefix-sum + searchsorted winner,
ops/segscatter.py) must reproduce the original dense fabric
(``_route``) BYTE-FOR-BYTE: same rows, same per-destination order,
same overflow-drop semantics — ack-run compression and winner
tie-breaks read row order, so "equivalent but reordered" is not good
enough. The old fabric stays in-tree behind
``route_fabric="dense"`` exactly so this pin owns the rewrite; the
golden kernel fixtures (tests/test_kernel_golden.py) extend the pin
through whole multi-protocol cluster scenarios.

Also here: the two-tier round (parallel/sharded.py ``sharded_round``,
PR 27), which calls the kernels and the route at a smaller static
shape whenever a round's rows fit the working capacity. It must leave
the whole ``ClusterState``, the latency histogram and the telemetry
ring byte-identical to the one-tier program after EVERY round, across
all three protocols and through a fault leg whose recovery overflows
the working capacity (these cases took the place of PR 11's
``compact_inbox`` ones, which pinned the same property for the static,
lossy knob the tier replaced).
"""

from __future__ import annotations

import functools
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minpaxos_tpu.models.cluster import (
    ClusterState,
    _route,
    _route_segmented,
)
from minpaxos_tpu.models.minpaxos import (
    MinPaxosConfig,
    MsgBatch,
    Outbox,
    replica_step_impl,
)
from minpaxos_tpu.parallel import sharded
from minpaxos_tpu.wire.messages import MsgKind

R = 5


def _mk_outboxes(m, n_live, seed, bc_frac=0.5, uni_frac=0.3):
    """Random [R, m] outboxes: n_live live rows each, dst mixing
    broadcast (-1), unicast (0..R-1, self included), client (-2)."""
    rng = np.random.default_rng(seed)
    cols = {f: np.zeros((R, m), np.int32) for f in MsgBatch._fields}
    dst = np.full((R, m), -1, np.int32)
    for r in range(R):
        # scatter live rows across positions, not only a prefix: the
        # fabric must compact arbitrary gap patterns
        pos = np.sort(rng.choice(m, size=n_live, replace=False))
        cols["kind"][r, pos] = rng.integers(1, 10, n_live)
        for f in MsgBatch._fields:
            if f != "kind":
                cols[f][r, pos] = rng.integers(-5, 1 << 20, n_live)
        u = rng.random(n_live)
        dst[r, pos] = np.where(
            u < bc_frac, -1,
            np.where(u < bc_frac + uni_frac, rng.integers(0, R, n_live), -2))
    msgs = MsgBatch(**{f: jnp.asarray(v) for f, v in cols.items()})
    return msgs, jnp.asarray(dst)


def _assert_tree_equal(a, b, ctx=""):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=ctx)


@pytest.mark.parametrize("m,n_live,capacity", [
    (32, 16, 32),    # ordinary mix
    (32, 32, 16),    # heavy overflow: fan-out far beyond capacity
    (64, 3, 64),     # sparse
    (16, 16, 128),   # capacity beyond pool: all rows land, tail empty
])
def test_segmented_matches_dense(m, n_live, capacity):
    cfg = MinPaxosConfig(n_replicas=R, window=64, inbox=capacity)
    for seed in range(4):
        msgs, dst = _mk_outboxes(m, n_live, seed)
        alive = jnp.ones(R, bool)
        _assert_tree_equal(
            _route(cfg, msgs, dst, alive, capacity),
            _route_segmented(cfg, msgs, dst, alive, capacity),
            ctx=f"seed={seed}")


def test_segmented_matches_dense_dead_replicas():
    """Dead sources' rows drop; dead destinations receive zeroed
    inboxes — every alive-mask combination at N=5 (jitted once,
    alive as a runtime arg: 32 masks, 2 compiles)."""
    import jax as _jax

    cfg = MinPaxosConfig(n_replicas=R, window=64, inbox=24)
    msgs, dst = _mk_outboxes(24, 18, seed=3)
    dense = _jax.jit(lambda a: _route(cfg, msgs, dst, a, 24))
    seg = _jax.jit(lambda a: _route_segmented(cfg, msgs, dst, a, 24))
    for mask in range(1 << R):
        alive = jnp.asarray([(mask >> i) & 1 == 1 for i in range(R)])
        _assert_tree_equal(dense(alive), seg(alive),
                           ctx=f"alive={mask:05b}")


def test_broadcast_unicast_client_semantics():
    """Hand-built outbox: broadcast reaches all OTHER live replicas,
    unicast exactly its target, client-bound (-2) rows never route,
    and per-destination order is pooled-row order."""
    cfg = MinPaxosConfig(n_replicas=R, window=64, inbox=8)
    cols = {f: np.zeros((R, 4), np.int32) for f in MsgBatch._fields}
    dst = np.full((R, 4), -2, np.int32)
    # replica 0: row0 broadcast, row1 unicast->3, row2 client, row3 pad
    cols["kind"][0, :3] = [int(MsgKind.ACCEPT), int(MsgKind.PREPARE_REPLY),
                           int(MsgKind.PROPOSE_REPLY)]
    cols["cmd_id"][0, :3] = [100, 101, 102]
    dst[0, :3] = [-1, 3, -2]
    # replica 2: row0 unicast->3 (lands AFTER replica 0's rows), row1
    # unicast->2 (self: dropped)
    cols["kind"][2, :2] = [int(MsgKind.ACCEPT_REPLY), int(MsgKind.COMMIT)]
    cols["cmd_id"][2, :2] = [200, 201]
    dst[2, :2] = [3, 2]
    msgs = MsgBatch(**{f: jnp.asarray(v) for f, v in cols.items()})
    alive = jnp.ones(R, bool)
    got = _route_segmented(cfg, msgs, jnp.asarray(dst), alive, 8)
    kind = np.asarray(got.kind)
    cid = np.asarray(got.cmd_id)
    # replica 0's broadcast reaches 1..4 but not 0
    assert kind[0, 0] == 0
    for d in (1, 2, 4):
        assert kind[d, 0] == int(MsgKind.ACCEPT) and cid[d, 0] == 100
        assert kind[d, 1] == 0  # nothing else routed there
    # replica 3: broadcast first (pooled order), then the two unicasts
    assert list(kind[3, :3]) == [int(MsgKind.ACCEPT),
                                 int(MsgKind.PREPARE_REPLY),
                                 int(MsgKind.ACCEPT_REPLY)]
    assert list(cid[3, :3]) == [100, 101, 200]
    # client-bound + self-unicast rows route nowhere
    assert not (cid == 102).any() and not (cid == 201).any()


def test_overflow_drops_beyond_capacity():
    """More addressed rows than capacity: exactly the first
    ``capacity`` rows (pooled order) land, the rest drop silently."""
    cfg = MinPaxosConfig(n_replicas=R, window=64, inbox=4)
    m = 8
    cols = {f: np.zeros((R, m), np.int32) for f in MsgBatch._fields}
    cols["kind"][0, :] = int(MsgKind.ACCEPT)
    cols["cmd_id"][0, :] = np.arange(m) + 1
    dst = np.full((R, m), -2, np.int32)
    dst[0, :] = 1  # 8 unicasts at capacity 4
    msgs = MsgBatch(**{f: jnp.asarray(v) for f, v in cols.items()})
    alive = jnp.ones(R, bool)
    got = _route_segmented(cfg, msgs, jnp.asarray(dst), alive, 4)
    assert list(np.asarray(got.cmd_id)[1]) == [1, 2, 3, 4]
    _assert_tree_equal(got, _route(cfg, msgs, jnp.asarray(dst), alive, 4))


# ------------------------------------------------- the two-tier round

#: a toy shape with two tiers: 16 proposals a round give a working
#: capacity of 128 rows against an inbox of 384; a follower revived
#: after ten dead rounds is sent 16 ACCEPTs + 128 catch-up rows + 1,
#: which overflows it
_TIER_KW = dict(n_replicas=5, window=512, inbox=384, exec_batch=32,
                kv_pow2=10, catchup_rows=128, recovery_rows=16)
_TIER_EXT = 16
_TIER_ROUNDS, _KILL_AT, _REVIVE_AT, _DRAIN_FROM = 34, 6, 16, 26

_STATICS = {"sharded_run": (0, 1, 2, 3, 9, 10, 11),
            "sharded_run_resident": (0, 1, 2, 3, 13, 14, 15)}


def _one_tier(mp):
    """The one-tier program for comparison, with no switch in the
    program to ask for it: the working capacity is patched up to the
    inbox and the entry points re-jitted from their plain functions
    (behind a fresh lambda each: jit's trace cache is keyed by the
    function, and the tiered trace is in it)."""
    mp.setattr(sharded, "working_capacity", lambda cfg, ext_rows: cfg.inbox)
    for name, statics in _STATICS.items():
        plain = getattr(sharded, name).__wrapped__
        mp.setattr(sharded, name, jax.jit(
            (lambda f: lambda *a: f(*a))(plain), static_argnums=statics))


def _tier_cfg(protocol):
    from minpaxos_tpu.models.paxos import classic_config

    return (classic_config(**_TIER_KW) if protocol == "classic"
            else MinPaxosConfig(**_TIER_KW))


@functools.lru_cache(maxsize=None)
def _tier_run(protocol: str, one_tier: bool):
    """Healthy rounds, kill, dead rounds, revive, recovery, drain, one
    round a dispatch; every round's whole state, the counter after
    every round, and the window's histogram and telemetry ring."""
    with pytest.MonkeyPatch.context() as mp:
        if one_tier:
            _one_tier(mp)
        sc = sharded.ShardedCluster(
            _tier_cfg(protocol), 2, ext_rows=_TIER_EXT, key_space=256,
            protocol="mencius" if protocol == "mencius" else "minpaxos")
        if protocol != "mencius":
            # the election's two deliveries through the resident
            # dispatch too (ShardedCluster.elect would compile the
            # one-tier sharded_step for them)
            sc.ss = sharded.elect_all(sc.cfg, sc.ss, 0)
        sc.begin_resident(telemetry_rounds=64)
        if protocol != "mencius":
            sc.run_resident(2, 0)
            sc.begin_resident(telemetry_rounds=64)
        states, tiers = [], []
        for i in range(_TIER_ROUNDS):
            if i == _KILL_AT:
                sc.kill(2)
            if i == _REVIVE_AT:
                sc.revive(2)
            sc.run_resident(1, _TIER_EXT if i < _DRAIN_FROM else 0)
            states.append([np.asarray(x)
                           for x in jax.tree_util.tree_leaves(sc.ss)])
            tiers.append(sc.resident_tiers())
        tel = sc.resident_telemetry()
        return states, tiers, tel, sc.end_resident(), sc.committed()


@pytest.mark.parametrize("protocol", ["minpaxos", "classic", "mencius"])
def test_tiered_round_state_equivalence(protocol):
    """After every round the whole ClusterState (states, pending,
    alive) equals the one-tier program's, byte for byte; so do the
    latency histogram and the telemetry ring. The leg covers healthy
    rounds, a dead follower, its revival (whose catch-up burst takes
    the full tier) and the drain."""
    got, tiers, tel, hist, committed = _tier_run(protocol, False)
    want, ref_tiers, ref_tel, ref_hist, ref_committed = _tier_run(
        protocol, True)
    assert tiers[-1]["working_capacity"] == 128 < tiers[-1]["inbox"]
    assert ref_tiers[-1]["kernel_small_rounds"] == 0  # one tier: none
    for i, (a, b) in enumerate(zip(got, want)):
        for j, (x, y) in enumerate(zip(a, b)):
            np.testing.assert_array_equal(
                x, y, err_msg=f"{protocol}: round {i}, leaf {j}")
    np.testing.assert_array_equal(tel, ref_tel)
    np.testing.assert_array_equal(hist, ref_hist)
    assert committed == ref_committed and hist.sum() > 0
    # both tiers really ran, or the comparison showed nothing
    last = tiers[-1]
    assert 0 < last["kernel_small_rounds"] < last["rounds"] == _TIER_ROUNDS
    assert 0 < last["route_small_rounds"] < last["rounds"]


def test_tier_counter_small_when_healthy_full_after_revive():
    """Healthy and dead rounds count as small in both tiers; the round
    that routes the revived follower's catch-up burst (145 rows at a
    working capacity of 128) counts a full route, the round that
    delivers it a full kernel; small + full = rounds; and the reading
    is left in ``obs.process_pods()`` as of the last read. No recovery
    gate opened: a follower that dies and heals under a live quorum
    stalls no leader and starts no discovery."""
    from minpaxos_tpu import obs
    from minpaxos_tpu.obs.recorder import TEL_INBOX_HWM

    _, tiers, tel, _, _ = _tier_run("minpaxos", False)
    k = np.diff([0] + [t["kernel_small_rounds"] for t in tiers])
    r = np.diff([0] + [t["route_small_rounds"] for t in tiers])
    assert [t["rounds"] for t in tiers] == list(range(1, _TIER_ROUNDS + 1))
    assert k[:_REVIVE_AT].all() and r[:_REVIVE_AT].all()
    # the kernel is small exactly when the delivered high-water mark
    # (pending + the leader's ext) shows no inbox above 128 + ext
    hwm = tel[:, TEL_INBOX_HWM]
    assert (hwm > 128).any()
    np.testing.assert_array_equal(k == 0, hwm > 128)
    # a route that overflowed is the NEXT round's full kernel
    np.testing.assert_array_equal(r[:-1] == 0, k[1:] == 0)
    assert k[_DRAIN_FROM + 2:].all() and r[_DRAIN_FROM + 2:].all()
    pod = [p for p in obs.process_pods()
           if p["tiers"] and p["working_capacity"] == 128
           and p["protocol"] == "minpaxos"][-1]
    assert pod["tiers"] == {f: tiers[-1][f] for f in (
        "kernel_small_rounds", "route_small_rounds", "rounds")}
    assert pod["gates"] == tiers[-1]["gates"] == dict.fromkeys(
        sharded.recovery_sections(replica_step_impl), 0)


class _Canned(NamedTuple):
    """The state of ``_canned_step``: the outbox it will emit."""
    msgs: MsgBatch
    dst: jnp.ndarray


def _canned_step(cfg, state, inbox):
    """A stand-in kernel that emits its state as its outbox, whatever
    it is delivered: puts the route tier of ``sharded_round`` alone
    under test."""
    acked = jnp.zeros(state.dst.shape, bool)
    return state, Outbox(state.msgs, state.dst, acked), inbox.kind.sum()


@pytest.mark.parametrize("count,small", [(128, True), (129, False)])
def test_route_tier_boundary_exact_capacity_small_one_more_full(count,
                                                                small):
    """A destination sent exactly ``working_capacity`` rows is routed
    at the small tier, one row more at the full one, and either way
    the inboxes are the one-tier fabric's: no row lost, pooled-row
    order kept."""
    cfg = MinPaxosConfig(n_replicas=R, window=64, inbox=384)
    g, m = 2, 160
    cols = {f: np.zeros((g, R, m), np.int32) for f in MsgBatch._fields}
    dst = np.full((g, R, m), -2, np.int32)
    rng = np.random.default_rng(count)
    # group 1, replica 0 unicasts `count` rows to replica 3, the rest
    # of the pool is a sparse mix (far fewer rows per destination)
    for gi in range(g):
        for r in range(R):
            pos = np.sort(rng.choice(m, size=12, replace=False))
            cols["kind"][gi, r, pos] = rng.integers(1, 10, 12)
            cols["cmd_id"][gi, r, pos] = rng.integers(1, 1 << 20, 12)
            dst[gi, r, pos] = rng.integers(-1, R, 12)
    cols["kind"][1, 0, :] = 0
    cols["kind"][1, 0, :count] = int(MsgKind.ACCEPT)
    cols["cmd_id"][1, 0, :count] = np.arange(count) + 1
    dst[1, 0, :] = 3
    dst[1, 1:, :][dst[1, 1:, :] == 3] = -2  # only replica 0 sends to 3
    dst[1, 1:, :][dst[1, 1:, :] == -1] = -2
    msgs = MsgBatch(**{f: jnp.asarray(v) for f, v in cols.items()})
    alive = jnp.ones((g, R), bool)
    ss = ClusterState(
        states=_Canned(msgs, jnp.asarray(dst)),
        pending=jax.tree_util.tree_map(
            lambda x: jnp.zeros((g, R, cfg.inbox), x.dtype),
            MsgBatch.empty(1)),
        alive=alive)
    ext = jax.tree_util.tree_map(lambda x: x[..., :0], ss.pending)
    out, _, flags, ran = jax.jit(functools.partial(
        sharded.sharded_round, cfg, _canned_step, 128))(ss, ext)
    assert flags.tolist() == [True, small]  # empty pending: small kernel
    assert ran.shape == (0,)  # a step that declares no recovery section
    want = jax.vmap(lambda o, d, a: _route_segmented(cfg, o, d, a,
                                                     cfg.inbox))(
        msgs, jnp.asarray(dst), alive)
    _assert_tree_equal(out.pending, want)
    got = np.asarray(out.pending.cmd_id)[1, 3]
    assert list(got[:count]) == list(range(1, count + 1))
    assert not got[count:].any()


def _conds(jaxpr) -> list:
    """Every ``cond`` equation of a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _conds(sub)
    return found


def _count_conds(jaxpr) -> int:
    return len(_conds(jaxpr))


def test_working_capacity_at_or_above_inbox_compiles_one_tier():
    """Where the working capacity does not lie below the inbox there
    is no tier to choose: the traced round holds no ``cond`` but the
    kernel's own, on its recovery gate; below it, the kernel's choice
    and one route choice inside each of its sides, each side with the
    conditional of its own kernel."""
    cfg = MinPaxosConfig(n_replicas=3, window=64, inbox=256, exec_batch=8,
                         kv_pow2=6, catchup_rows=8, recovery_rows=8)
    ss = sharded.init_sharded(cfg, 2)

    def conds(ext_rows):
        ext = jax.tree_util.tree_map(
            lambda x: jnp.zeros((2, 3, ext_rows), x.dtype),
            MsgBatch.empty(1))
        rows = sharded.working_capacity(cfg, ext_rows)
        return rows, _count_conds(jax.make_jaxpr(functools.partial(
            sharded.sharded_round, cfg, replica_step_impl, rows))(
                ss, ext).jaxpr)

    gates = len(sharded.recovery_sections(replica_step_impl))
    assert conds(64) == (256, gates)
    assert conds(16) == (128, 3 + 2 * gates)
    assert sharded.working_capacity(cfg, 0) == 128
    assert sharded.working_capacity(cfg._replace(inbox=1280), 128) == 512
    assert sharded.working_capacity(cfg._replace(inbox=2688), 512) == 2048


# ----------------------------------------- the whole-chip recovery gates

#: a toy shape whose window and inboxes a fault fills in a few rounds
_GATE_KW = dict(n_replicas=5, window=128, inbox=256, exec_batch=16,
                kv_pow2=8, catchup_rows=32, recovery_rows=16)
_GATE_EXT, _GATE_GROUPS = 8, 2


def _alive(ss, replicas, value):
    return ss._replace(alive=ss.alive.at[:, jnp.asarray(replicas)].set(value))


def _leader_schedule(cfg, i, ss):
    """MinPaxos / classic: election, steady load, the quorum lost for
    ten rounds (the leader stalls: retry, then the rescan's discovery),
    three followers revived under load (catch-up), a leader change
    under load (PREPARE_INST / PREPARE_INST_REPLY flow), drain.
    Returns (ss, proposals, leader)."""
    if i == 0:
        ss = sharded.elect_all(cfg, ss, 0)
    if i == 10:
        ss = _alive(ss, [2, 3, 4], False)
    if i == 20:
        ss = _alive(ss, [2, 3, 4], True)
    if i == 34:
        ss = sharded.elect_all(cfg, ss, 1)
    return (ss, 0 if i < 2 or i >= 50 else _GATE_EXT, 0 if i < 34 else 1)


def _owner_schedule(cfg, i, ss):
    """Mencius: every owner loaded, owner 2 idle for six rounds (it
    cedes: SKIP rows flow), owner 1 dead for twenty (its slots block
    the frontier: a takeover runs), revived, drain."""
    if i == 20:
        ss = _alive(ss, [1], False)
    if i == 40:
        ss = _alive(ss, [1], True)
    load = [4, 4, 0, 4, 4] if 8 <= i < 14 else [4] * R
    return ss, load if i < 50 else [0] * R, -1


@pytest.mark.parametrize("protocol,rounds", [
    ("minpaxos", 60), ("classic", 60), ("mencius", 70)])
def test_gated_round_equals_the_vmapped_cluster_step(protocol, rounds):
    """``sharded_round``, which skips the recovery sections whose
    gates are shut (MinPaxos and classic by a conditional in the step,
    Mencius by a steady kernel traced without them), against
    ``jax.vmap(cluster_step_impl)``, which knows no gate: every leaf
    of the ClusterState and of the exec results equal after EVERY
    round, over a schedule in which every section's gate is seen both
    open and shut, the small tier is taken with gates open and with
    all shut, and the full tier too."""
    from minpaxos_tpu.models.cluster import cluster_step_impl
    from minpaxos_tpu.models.mencius import init_mencius, mencius_step_impl
    from minpaxos_tpu.models.minpaxos import init_replica
    from minpaxos_tpu.models.paxos import classic_config

    cfg = (classic_config(**_GATE_KW) if protocol == "classic"
           else MinPaxosConfig(**_GATE_KW))
    owners = protocol == "mencius"
    step, init, schedule = (
        (mencius_step_impl, init_mencius, _owner_schedule) if owners
        else (replica_step_impl, init_replica, _leader_schedule))
    gated = jax.jit(functools.partial(
        sharded.sharded_round, cfg, step,
        sharded.small_tier_rows(cfg, _GATE_EXT, owners) if owners else 64))
    plain = jax.jit(jax.vmap(
        lambda cs, ext: cluster_step_impl(cfg, cs, ext, step)[:2]))
    ss = sharded.init_sharded(cfg, _GATE_GROUPS, None, init)
    sections = sharded.recovery_sections(step)
    opened = np.zeros(len(sections), int)
    kernels = {"small, gates shut": 0, "small, a gate open": 0, "full": 0}
    for i in range(rounds):
        ss, load, leader = schedule(cfg, i, ss)
        ext = sharded.make_propose_ext(
            cfg, _GATE_GROUPS, _GATE_EXT, jnp.asarray(load, jnp.int32),
            jnp.int32(leader), jnp.int32(i), jnp.int32(3), 256, owners)
        got_ss, got_exec, small, gate_open = gated(ss, ext)
        _assert_tree_equal((got_ss, got_exec), plain(ss, ext),
                           f"{protocol}: round {i}")
        ss = got_ss
        opened += np.asarray(gate_open)
        kernels["full" if not small[0] else
                "small, a gate open" if np.asarray(gate_open).any()
                else "small, gates shut"] += 1
    assert int(np.asarray(ss.states.committed_upto).min()) > 200
    assert ((0 < opened) & (opened < rounds)).all(), dict(
        zip(sections, opened))
    assert all(kernels.values()), kernels


def _gate_kernel(step, init, steady, gates=None):
    """The jaxpr of the vmapped kernel of the toy pod."""
    cfg = MinPaxosConfig(**_GATE_KW)._replace(gate_exec=False)
    ss = sharded.init_sharded(cfg, _GATE_GROUPS, None, init)
    ext = jax.tree_util.tree_map(
        lambda x: jnp.zeros((_GATE_GROUPS, R, _GATE_EXT), x.dtype),
        MsgBatch.empty(1))
    if gates:
        gates = sharded.recovery_gates(cfg, step, ss, ext)
    return jax.make_jaxpr(lambda ss, ext, gates: sharded._step_groups(
        cfg, step, 128, steady, ss, ext, gates))(ss, ext, gates).jaxpr


def _served_conds(step, init) -> list:
    """The ``cond``s of the step alone, as the served path calls it."""
    cfg = MinPaxosConfig(**_GATE_KW)
    return [str(c.source_info.name_stack) for c in _conds(jax.make_jaxpr(
        lambda st, ib: step(cfg, st, ib))(
            init(cfg, 0), MsgBatch.empty(cfg.inbox)).jaxpr)]


def test_gates_are_conditionals_of_the_step_not_selects():
    """With gates, the vmapped MinPaxos kernel holds one ``cond`` per
    gated section, on an unbatched scalar under the section's scope (a
    batched predicate would have lowered to a ``select_n`` of both
    sides); without them it holds none, and the step alone only the
    exec gate it always had."""
    from minpaxos_tpu.models.minpaxos import init_replica

    step, init = replica_step_impl, init_replica
    scopes = list(sharded.recovery_sections(step))
    assert scopes == ["px.retry"] and sharded._takes_gates(step)
    conds = _conds(_gate_kernel(step, init, False, gates=True))
    assert [c.invars[0].aval.shape for c in conds] == [()] * len(scopes)
    assert [next(s for s in scopes if s in str(c.source_info.name_stack))
            for c in conds] == scopes
    assert _conds(_gate_kernel(step, init, False)) == []
    served = _served_conds(step, init)
    assert len(served) == 1 and "px.exec" in served[0]


def _scope_eqns(jaxpr, counts=None) -> dict:
    """Equations of a jaxpr, nested ones included, per ``px.*`` scope
    that encloses them."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        for sub in subs:
            _scope_eqns(sub, counts)
        if not subs:
            for scope in set(re.findall(r"px\.[a-z_0-9.]*[a-z_0-9]",
                                        str(eqn.source_info.name_stack))):
                counts[scope] = counts.get(scope, 0) + 1
    return counts


def test_the_steady_kernel_leaves_its_sections_out():
    """Mencius's steady kernel holds nothing under the scopes of its
    recovery sections, and every other section as the plain kernel has
    it, SKIP rows among them; neither holds a ``cond`` (one on a
    per-replica predicate would be a select of both sides under the
    vmaps), the step alone holds only the exec gate it always had, and
    the round chooses among its three kernels by ONE conditional on an
    unbatched scalar, whose third side alone lacks the sections."""
    from minpaxos_tpu.models.mencius import init_mencius, mencius_step_impl

    step, init = mencius_step_impl, init_mencius
    gated = set(sharded.recovery_sections(step))
    assert gated == {"px.takeover_phase1", "px.takeover"}
    assert not sharded._takes_gates(step)
    kernels = [_gate_kernel(step, init, steady) for steady in (False, True)]
    assert [_conds(k) for k in kernels] == [[], []]
    plain, steady = map(_scope_eqns, kernels)
    assert gated <= set(plain) and set(steady) <= set(plain)
    for scope, n in plain.items():
        if scope in gated:
            assert steady.get(scope, 0) <= 5 < n, (scope, n, steady)
        elif scope != "px.outbox":  # which joins the sections' rows
            assert steady[scope] == n, (scope, n, steady)
    served = _served_conds(step, init)
    assert len(served) == 1 and "px.exec" in served[0]
    cfg = MinPaxosConfig(**_GATE_KW)
    ss = sharded.init_sharded(cfg, _GATE_GROUPS, None, init)
    ext = jax.tree_util.tree_map(
        lambda x: jnp.zeros((_GATE_GROUPS, R, _GATE_EXT), x.dtype),
        MsgBatch.empty(1))
    top = [e for e in jax.make_jaxpr(functools.partial(
        sharded.sharded_round, cfg, step, 128))(ss, ext).jaxpr.eqns
           if e.primitive.name == "cond"]
    assert len(top) == 1 and top[0].invars[0].aval.shape == ()
    sides = [_scope_eqns(b.jaxpr) for b in top[0].params["branches"]]
    assert [all(side.get(scope, 0) > 5 for scope in gated)
            for side in sides] == [True, True, False]


def test_gate_counters_count_the_rounds_a_gate_was_open():
    """Through the resident loop: 0 for every section over healthy
    rounds; with the quorum dead the frontier still moves in the first
    round (acks on their way), the leader's stall counter then takes
    ``RETRY_STALL_TICKS`` - 1 rounds to stand one short of its
    threshold, and the gate of ``px.retry`` is open from the next on;
    the reading is the pod's entry in ``obs.process_pods()``, and
    arming again zeroes it."""
    from minpaxos_tpu import obs
    from minpaxos_tpu.models.minpaxos import RETRY_STALL_TICKS

    cfg = MinPaxosConfig(**_GATE_KW)
    sc = sharded.ShardedCluster(cfg, _GATE_GROUPS, ext_rows=_GATE_EXT,
                                key_space=256)
    sc.ss = sharded.elect_all(cfg, sc.ss, 0)
    sc.begin_resident()
    for n in (0, 0) + (_GATE_EXT,) * 6:
        sc.run_resident(1, n)
    idle = dict.fromkeys(sharded.recovery_sections(replica_step_impl), 0)
    assert sc.resident_tiers()["gates"] == idle == {"px.retry": 0}
    for r in (2, 3, 4):
        sc.kill(r)
    for _ in range(8):
        sc.run_resident(1, _GATE_EXT)
    tiers = sc.resident_tiers()
    assert tiers["rounds"] == 16
    assert tiers["gates"] == {**idle, "px.retry": 8 - RETRY_STALL_TICKS}
    assert obs.process_pods()[-1]["gates"] == tiers["gates"]
    sc.begin_resident()
    assert sc.resident_tiers()["gates"] == idle
