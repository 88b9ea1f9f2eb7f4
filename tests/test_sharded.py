"""Sharded-Paxos over the virtual 8-device CPU mesh.

Validates the north-star path (BASELINE.md): many independent groups
advanced by one jitted step, shard axis partitioned over real (virtual)
devices, commits flowing in every shard, failure masking per shard.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minpaxos_tpu.models.minpaxos import MinPaxosConfig
from minpaxos_tpu.parallel import ShardedCluster, make_mesh
from minpaxos_tpu.parallel.sharded import init_sharded, elect_all, sharded_step


SMALL = MinPaxosConfig(
    n_replicas=3, window=256, inbox=256, exec_batch=64, kv_pow2=10,
    catchup_rows=16, recovery_rows=16)


def test_mesh_shapes():
    mesh = make_mesh()
    assert mesh.devices.size == len(jax.devices())
    assert mesh.axis_names == ("shard", "replica")
    mesh2 = make_mesh(n_shard_devices=4, n_replica_devices=2)
    assert mesh2.shape["shard"] == 4 and mesh2.shape["replica"] == 2


def test_sharded_commits_all_shards():
    mesh = make_mesh()
    g = 16  # 16 shards over 8 devices
    sc = ShardedCluster(SMALL, g, ext_rows=64, mesh=mesh)
    sc.elect(0)
    for _ in range(4):
        sc.step(32)
    for _ in range(3):
        sc.step(0)  # drain
    tot, lo, hi = sc.committed()
    assert lo == hi, "shards advance in lockstep under identical load"
    assert tot == g * 4 * 32


def test_sharded_state_is_actually_sharded():
    mesh = make_mesh()
    ss = init_sharded(SMALL, 8, mesh)
    sharding = ss.states.ballot.sharding
    assert len(sharding.device_set) == len(jax.devices())


def test_sharded_step_preserves_sharding():
    mesh = make_mesh()
    sc = ShardedCluster(SMALL, 8, ext_rows=64, mesh=mesh)
    sc.elect(0)
    sc.step(8)
    assert len(sc.ss.states.ballot.sharding.device_set) == len(jax.devices())


def test_replica_axis_mesh_executes():
    """Replicas spread across devices: routing becomes collectives."""
    mesh = make_mesh(n_shard_devices=2, n_replica_devices=4)
    # replica-axis sharding of a 4-replica group: R axis over 4 devices
    cfg = MinPaxosConfig(n_replicas=4, window=128, inbox=128,
                         exec_batch=32, kv_pow2=8)
    from jax.sharding import NamedSharding, PartitionSpec as P

    ss = init_sharded(cfg, 2)
    def put(x):
        spec = P("shard", "replica") if x.ndim >= 2 else P("shard")
        return jax.device_put(x, NamedSharding(mesh, spec))
    ss = jax.tree_util.tree_map(put, ss)
    ss = elect_all(cfg, ss, 0)
    from minpaxos_tpu.parallel.sharded import make_propose_ext
    ext = make_propose_ext(cfg, 2, 128, 16, jnp.int32(0), jnp.int32(0))
    quiet = jax.tree_util.tree_map(jnp.zeros_like, ext)
    # deliver prepares, then replies, then proposals, then drain
    ss, _, _, _ = sharded_step(cfg, ss, quiet)
    ss, _, _, _ = sharded_step(cfg, ss, quiet)
    ss, _, _, _ = sharded_step(cfg, ss, ext)
    for _ in range(4):
        ss, _, _, _ = sharded_step(cfg, ss, quiet)
    upto = np.asarray(ss.states.committed_upto[:, 0])
    assert (upto >= 15).all()


def test_per_shard_failure_mask():
    """Killing a follower in shard 0 only affects shard 0 (and not even
    it: majority still commits)."""
    g = 4
    sc = ShardedCluster(SMALL, g, ext_rows=64)
    sc.elect(0)
    sc.ss = sc.ss._replace(alive=sc.ss.alive.at[0, 2].set(False))
    for _ in range(3):
        sc.step(16)
    for _ in range(3):
        sc.step(0)
    tot, lo, hi = sc.committed()
    assert tot == g * 3 * 16, "2-of-3 majority still commits everywhere"


def test_fused_run_bounded_keyspace_never_drops_kv_inserts():
    """The saturation guard: with key_space bounded below KV
    capacity, long fused runs churn (PUT overwrites reuse slots) and
    kv.dropped stays 0 everywhere. With an UNBOUNDED key space the same
    run inserts more distinct keys than the table holds — the scenario
    the guard exists for (every shape of minpaxos_tpu/deployments.py
    and of the benchmark's pod cells bounds its key space)."""
    g = 4
    sc = ShardedCluster(SMALL, g, ext_rows=64,
                        key_space=1 << (SMALL.kv_pow2 - 1))
    sc.elect(0)
    # 24 rounds x 64 proposals/shard = 1536 distinct-capable inserts
    # per shard, 3x the 512-entry key space and 1.5x table capacity
    for _ in range(3):
        sc.run_fused(8, 64)
    sc.run_fused(8, 0)  # drain
    dropped = np.asarray(sc.ss.states.kv.dropped)
    assert (dropped == 0).all(), dropped
    # device-generated proposals that outrun the 256-slot window are
    # rejected (no client retry on-device), so assert the part the
    # test needs: every shard committed well past the key space, so
    # the table really churned overwrite-heavy without dropping
    tot, lo, hi = sc.committed()
    assert lo + 1 > 2 * (1 << (SMALL.kv_pow2 - 1)), (tot, lo, hi)


def test_multihost_glue_single_process_degenerate():
    """Single-process: initialize() no-ops, the global mesh covers all
    local devices, and the process shard slice is the whole range —
    the same launcher path that multi-controller jobs take."""
    from minpaxos_tpu.parallel import multihost

    multihost.initialize(num_processes=1)  # must not raise / contact anyone
    mesh = multihost.global_shard_mesh()
    assert mesh.devices.size == len(jax.devices())
    assert multihost.process_shard_slice(16) == slice(0, 16)
    # the mesh drives a real sharded cluster end-to-end
    sc = ShardedCluster(SMALL, 16, ext_rows=64, mesh=mesh)
    sc.elect(0)
    sc.run_fused(4, 16)
    tot, _, _ = sc.committed()
    assert tot > 0


def test_fused_substeps_cut_commit_rounds():
    """substeps=2 delivers message traffic twice per fused round, so a
    proposal's commit lands ~one ROUND earlier (commit-on-quorum
    within the round the quorum forms — VERDICT round-4 item 5). Same
    commits, fewer rounds-to-commit; the throughput/latency tradeoff
    is not measured (every benchmark cell passes 1), correctness is
    pinned here."""
    def first_round_reaching(substeps):
        sc = ShardedCluster(SMALL, 2)
        sc.elect(0)
        uptos, _ = sc.run_fused(6, 16, substeps=substeps)
        want = 15  # all 16 round-0 proposals committed
        for r in range(6):
            if uptos[r].min() >= want:
                return r, uptos
        return 99, uptos

    r1, u1 = first_round_reaching(1)
    r2, u2 = first_round_reaching(2)
    assert r1 < 99 and r2 < 99, (u1, u2)
    assert r2 < r1, (r1, r2, u1[:, 0], u2[:, 0])


def test_tiered_fused_run_on_the_mesh_matches_one_tier_steps():
    """``sharded_run`` at a shape with two tiers (working capacity 128
    of an inbox of 384), its groups spread over the 8-device mesh so
    that each tier choice is a cross-shard reduction: after every round
    the state equals that of the one-tier ``sharded_step`` fed the same
    (seed, round) stream off the mesh, through a dead follower and the
    revival whose catch-up burst takes the full tier."""
    from minpaxos_tpu.parallel.sharded import (
        make_propose_ext,
        set_alive,
        sharded_run,
        working_capacity,
    )

    cfg = MinPaxosConfig(n_replicas=5, window=512, inbox=384, exec_batch=32,
                         kv_pow2=10, catchup_rows=128, recovery_rows=16)
    g, p, seed = 8, 16, 5
    assert working_capacity(cfg, p) == 128
    got = elect_all(cfg, init_sharded(cfg, g, make_mesh()), 0)
    want = elect_all(cfg, init_sharded(cfg, g), 0)
    full_rounds = 0
    for i in range(24):
        if i in (6, 16):  # kill, then revive ten rounds behind
            got = set_alive(cfg, got, jnp.int32(2), i == 16)
            want = set_alive(cfg, want, jnp.int32(2), i == 16)
        n = jnp.int32(p if 2 <= i < 20 else 0)  # two election rounds first
        full_rounds += int((np.asarray(got.pending.kind)[..., 128:] != 0)
                           .any())
        got, uptos, _ = sharded_run(cfg, g, p, 1, got, n, jnp.int32(0),
                                    jnp.int32(i), jnp.int32(seed), None, 256)
        ext = make_propose_ext(cfg, g, p, n, jnp.int32(0), jnp.int32(i),
                               jnp.int32(seed), 256)
        want, _, _, _ = sharded_step(cfg, want, ext)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"round {i}")
    assert 0 < full_rounds < 24  # both kernel tiers ran
    assert len(got.states.ballot.sharding.device_set) == len(jax.devices())
    assert (np.asarray(uptos) >= 18 * p - 1).all()
