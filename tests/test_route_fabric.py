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
    is left in ``obs.process_pods()`` as of the last read."""
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
    out, _, flags = jax.jit(functools.partial(
        sharded.sharded_round, cfg, _canned_step, 128))(ss, ext)
    assert flags.tolist() == [True, small]  # empty pending: small kernel
    want = jax.vmap(lambda o, d, a: _route_segmented(cfg, o, d, a,
                                                     cfg.inbox))(
        msgs, jnp.asarray(dst), alive)
    _assert_tree_equal(out.pending, want)
    got = np.asarray(out.pending.cmd_id)[1, 3]
    assert list(got[:count]) == list(range(1, count + 1))
    assert not got[count:].any()


def _count_conds(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_conds(sub)
    return n


def test_working_capacity_at_or_above_inbox_compiles_one_tier():
    """Where the working capacity does not lie below the inbox there
    is nothing to choose: the traced round holds no ``cond`` and is
    the one-tier program; below it, the kernel's choice and one route
    choice inside each of its sides."""
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

    assert conds(64) == (256, 0)
    assert conds(16) == (128, 3)
    assert sharded.working_capacity(cfg, 0) == 128
    assert sharded.working_capacity(cfg._replace(inbox=1280), 128) == 512
    assert sharded.working_capacity(cfg._replace(inbox=2688), 512) == 2048
