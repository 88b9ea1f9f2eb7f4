"""On-device workload generator + device-resident measured loop.

Two properties carry the PR-8 acceptance criteria:

* host/device workload equivalence — the jnp Threefry generator and
  the independent NumPy host injector produce BYTE-IDENTICAL proposal
  rows from the same (seed, round) across shards, rounds, and leader
  modes, and the stream is pinned against golden values so it can
  never silently drift (bench runs must stay comparable across
  sessions and jax versions);
* resident/legacy loop equivalence — the device-resident measured
  loop (donated buffers, on-device latency histogram, two-scalar
  readback) commits exactly what the host-in-the-loop legacy path
  commits, lands in an identical state, and its histogram reproduces
  the host-side latency percentiles bit-for-bit, with the drain
  leaving zero uncommitted slots.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minpaxos_tpu.models.minpaxos import MinPaxosConfig
from minpaxos_tpu.ops.workload import (
    propose_batch,
    propose_batch_host,
    threefry2x32,
    threefry2x32_host,
)
from minpaxos_tpu.parallel.sharded import (
    DONATION,
    LATENCY_BINS,
    ShardedCluster,
    shard_cursors,
    sharded_run_resident,
)

SMALL = MinPaxosConfig(
    n_replicas=3, window=256, inbox=256, exec_batch=64, kv_pow2=10,
    catchup_rows=16, recovery_rows=16)


def batches_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f)))
               for f in a._fields)


# ------------------------------------------------- threefry equivalence


def test_threefry_device_matches_host_reference():
    """Same key/counter -> identical uint32 lanes, elementwise, for a
    spread of keys including wraparound-heavy ones."""
    c0 = np.arange(64, dtype=np.uint32)
    c1 = np.arange(64, dtype=np.uint32) * np.uint32(2654435761)
    for k0, k1 in ((0, 0), (1, 0), (7, 42), (0xFFFFFFFF, 0x12345678)):
        d0, d1 = threefry2x32(jnp.uint32(k0), jnp.uint32(k1),
                              jnp.asarray(c0), jnp.asarray(c1))
        h0, h1 = threefry2x32_host(k0, k1, c0, c1)
        assert np.array_equal(np.asarray(d0), h0)
        assert np.array_equal(np.asarray(d1), h1)


def test_threefry_golden_pin():
    """The stream is pinned: these values were produced by this
    implementation AND verified against jax._src.prng.threefry_2x32
    (key [7, 42], counter 0..7). If this test starts failing, bench
    runs are no longer comparable with recorded artifacts."""
    h0, h1 = threefry2x32_host(7, 42, np.arange(4, dtype=np.uint32),
                               np.arange(4, 8, dtype=np.uint32))
    assert h0.tolist() == [2626804800, 2398813549, 2223630828, 3945575549]
    assert h1.tolist() == [592614780, 124672495, 3815937248, 2652798884]


def test_workload_rows_device_host_identical_across_rounds_shards():
    """The acceptance property: same seed => byte-identical [G, R, M]
    proposal rows, across rounds and shards, for both the
    single-leader and the Mencius every-owner addressing modes."""
    for leader in (0, 2, -1):
        for rnd in (0, 1, 17, 4096):
            dev = propose_batch(5, 4, 32, jnp.int32(20), jnp.int32(leader),
                                jnp.int32(rnd), jnp.int32(99), 1 << 10)
            host = propose_batch_host(5, 4, 32, 20, leader, rnd, 99, 1 << 10)
            assert batches_equal(dev, host), (leader, rnd)


def test_workload_rows_format_and_gating():
    """Row format invariants the protocol step relies on: int32
    columns, rows past ``count`` are dead (kind 0), keys live in
    [0, key_space), only the addressed replica gets live rows, and
    cmd_id encodes (round, row) for exactly-once auditing."""
    g, r, m, count, rnd = 3, 5, 16, 9, 7
    b = propose_batch_host(r, g, m, count, 1, rnd, 0, 1 << 8)
    for f in b._fields:
        assert getattr(b, f).dtype == np.int32, f
    assert (b.kind[:, 1, :count] != 0).all()
    assert (b.kind[:, 1, count:] == 0).all()
    assert (b.kind[:, [0, 2, 3, 4], :] == 0).all()
    assert (b.key_lo >= 0).all() and (b.key_lo < (1 << 8)).all()
    # keys are DISTINCT within a (shard, round): duplicate keys in one
    # exec batch serialize the KV claim loop (the 199 vs 122 ms/round
    # regression this schedule exists to avoid — PERF.md)
    for sh in range(g):
        assert len(np.unique(b.key_lo[sh, 1, :count])) == count
    assert np.array_equal(b.cmd_id[:, 1, :count],
                          np.broadcast_to(rnd * m + np.arange(count),
                                          (g, count)))
    assert np.array_equal(b.client_id[:, 1, :count],
                          np.broadcast_to(np.arange(g)[:, None], (g, count)))


def test_workload_distinct_rounds_distinct_rows():
    """Counter-based: different rounds (and different seeds) give
    different key material — the generator cannot silently replay."""
    a = propose_batch_host(3, 2, 16, 16, 0, 0, 0)
    b = propose_batch_host(3, 2, 16, 16, 0, 1, 0)
    c = propose_batch_host(3, 2, 16, 16, 0, 0, 1)
    assert not np.array_equal(a.key_lo, b.key_lo)
    assert not np.array_equal(a.key_lo, c.key_lo)
    # shards draw distinct streams too
    assert not np.array_equal(a.key_lo[0], a.key_lo[1])


# --------------------------------------- resident loop: exact equivalence


def _run_legacy(sc, dispatches=3, k=6, p=24):
    """The host-in-the-loop reference of the resident scan:
    per-dispatch cursor-history readback (``run_fused``) + host latency
    reconstruction (tests/latency_oracle.py)."""
    from tests.latency_oracle import latency_rounds

    u0, c0 = shard_cursors(sc.cfg, sc.leader, sc.ss)
    U, C = [np.asarray(u0)[None].copy()], [np.asarray(c0)[None].copy()]
    for _ in range(dispatches):
        u, c = sc.run_fused(k, p)
        U.append(u)
        C.append(c)
    for _ in range(6):
        u, c = sc.run_fused(k, 0)
        U.append(u)
        C.append(c)
        if (u[-1] >= c[-1] - 1).all():
            break
    return latency_rounds(np.concatenate(U), np.concatenate(C), 1.0)


def _run_resident(sc, dispatches=3, k=6, p=24):
    sc.begin_resident()
    for _ in range(dispatches):
        committed, in_flight = sc.run_resident(k, p)
    for _ in range(6):
        committed, in_flight = sc.run_resident(k, 0)
        if in_flight == 0:
            break
    return sc.end_resident(), committed, in_flight


def test_resident_loop_equals_legacy_loop():
    """Resident scan vs the host-in-the-loop reference: identical
    committed results AND identical final cluster state from the same
    seed, with the device histogram reproducing the host-side latency
    sample and percentiles exactly."""
    sc_a = ShardedCluster(SMALL, 2, ext_rows=32, key_space=1 << 8, seed=5)
    sc_a.elect(0)
    p50, p99, n, unc = _run_legacy(sc_a)

    sc_b = ShardedCluster(SMALL, 2, ext_rows=32, key_space=1 << 8, seed=5)
    sc_b.elect(0)
    hist, committed, in_flight = _run_resident(sc_b)

    assert unc == 0 and in_flight == 0  # both drained exactly
    assert committed == sc_a.committed()[0]
    # byte-identical end states: same proposal stream, same rounds
    la, lb = jax.tree_util.tree_leaves(sc_a.ss), jax.tree_util.tree_leaves(
        sc_b.ss)
    for xa, xb in zip(la, lb):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))
    # exact latency sample: reconstruct from the histogram
    assert int(hist.sum()) == n
    sample = np.repeat(np.arange(1, hist.size + 1), hist)
    assert float(np.percentile(sample, 50)) == p50
    assert float(np.percentile(sample, 99)) == p99
    assert hist[-1] == 0  # no overflow at test scale


def test_resident_determinism_pin():
    """Two fresh runs, same seed -> identical committed totals and
    identical latency histograms (the artifact-metrics determinism
    pin); a different seed changes the stream but not the totals."""
    runs = []
    for seed in (3, 3, 4):
        sc = ShardedCluster(SMALL, 2, ext_rows=32, key_space=1 << 8,
                            seed=seed)
        sc.elect(0)
        hist, committed, in_flight = _run_resident(sc)
        assert in_flight == 0
        runs.append((committed, hist.tolist(),
                     np.asarray(sc.ss.states.kv.key_lo).copy()))
    assert runs[0][0] == runs[1][0] == runs[2][0]
    assert runs[0][1] == runs[1][1]
    assert np.array_equal(runs[0][2], runs[1][2])
    # different seed: same protocol progress, different key material
    assert not np.array_equal(runs[0][2], runs[2][2])


def test_resident_latency_histogram_matches_hand_computed():
    """First dispatch from idle: slots proposed in round r commit at
    the propose->accept->ack pipeline depth, and the histogram's total
    equals the committed count exactly (no censoring, no padding).
    (Shape/k chosen to share the equality tests' compiled dispatch —
    tier-1 budget discipline.)"""
    sc = ShardedCluster(SMALL, 2, ext_rows=32, key_space=1 << 8)
    sc.elect(0)
    sc.begin_resident()
    committed, in_flight = sc.run_resident(6, 16)
    for _ in range(4):
        committed, in_flight = sc.run_resident(6, 0)
        if in_flight == 0:
            break
    hist = sc.end_resident()
    assert in_flight == 0
    assert int(hist.sum()) == committed
    lats = np.nonzero(hist)[0] + 1
    # the commit pipeline is 3 message deliveries -> every slot commits
    # in exactly 3 rounds under the lock-step pod composition
    assert lats.tolist() == [3], hist[:8]


def test_resident_histogram_overflow_bin_reports_tail():
    """A latency beyond the bin range lands in the LAST bin (counted,
    never dropped): feed a tiny hist so the 3-round pipeline overflows."""
    sc = ShardedCluster(SMALL, 2, ext_rows=32, key_space=1 << 8)
    sc.elect(0)
    sc.begin_resident(lat_bins=2)
    committed, in_flight = sc.run_resident(6, 16)
    for _ in range(4):
        committed, in_flight = sc.run_resident(6, 0)
        if in_flight == 0:
            break
    hist = sc.end_resident()
    assert int(hist.sum()) == committed
    assert hist[-1] == committed  # all 3-round latencies overflow 2 bins


def test_resident_buffers_are_donated():
    """The donation contract (``sharded.DONATION``):
    round state and both bookkeeping buffers are consumed by the
    dispatch — in-place update, no per-dispatch allocation of the big
    tree. (jax marks donated inputs as deleted.)"""
    assert DONATION["sharded_run_resident"] is True
    sc = ShardedCluster(SMALL, 2, ext_rows=32, key_space=1 << 8)
    sc.elect(0)
    sc.begin_resident()
    old_ballot = sc.ss.states.ballot
    old_inj = sc._inject_round
    old_hist = sc._lat_hist
    sc.run_resident(6, 8)
    assert old_ballot.is_deleted()
    assert old_inj.is_deleted()
    assert old_hist.is_deleted()


def test_resident_hist_default_bins():
    sc = ShardedCluster(SMALL, 1, ext_rows=8, key_space=1 << 8)
    sc.elect(0)
    sc.begin_resident()
    assert sc._lat_hist.shape == (LATENCY_BINS,)
    assert sc.resident_hist().sum() == 0


def test_host_injected_rows_commit_identically():
    """Feeding propose_batch_host's rows from the HOST (sharded_step,
    one round at a time) commits exactly the slots the device
    generator commits inside the fused scan — the generator really is
    the host injector's row format."""
    from minpaxos_tpu.models.cluster import ClusterState  # noqa: F401
    from minpaxos_tpu.parallel.sharded import sharded_step

    g, p, k = 2, 16, 6
    sc_dev = ShardedCluster(SMALL, g, ext_rows=p, key_space=1 << 8, seed=9)
    sc_dev.elect(0)
    sc_dev.run_fused(k, p)

    sc_host = ShardedCluster(SMALL, g, ext_rows=p, key_space=1 << 8, seed=9)
    sc_host.elect(0)
    for t in range(k):
        ext = propose_batch_host(SMALL.n_replicas, g, p, p, 0,
                                 sc_host._seed, 9, 1 << 8)
        ext = jax.tree_util.tree_map(jnp.asarray, ext)
        sc_host._seed += 1
        sc_host.ss, _, _, _ = sharded_step(SMALL, sc_host.ss, ext,
                                           sc_host._step_impl)
    for xa, xb in zip(jax.tree_util.tree_leaves(sc_dev.ss),
                      jax.tree_util.tree_leaves(sc_host.ss)):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))


@pytest.mark.slow
def test_mencius_resident_loop_commits_and_drains():
    """The resident loop is protocol-generic: Mencius (leader -1,
    every owner proposing) commits, drains exactly, and samples
    latencies on device too. (slow: its own protocol compile — the
    tier-1 870 s budget is already tight; run with -m slow.)"""
    cfg = SMALL._replace(inbox=512, catchup_rows=64, noop_delay=8)
    sc = ShardedCluster(cfg, 2, ext_rows=8, protocol="mencius",
                        key_space=1 << 8)
    sc.begin_resident()
    committed, in_flight = sc.run_resident(8, 8)
    for _ in range(6):
        committed, in_flight = sc.run_resident(8, 0)
        if in_flight == 0:
            break
    hist = sc.end_resident()
    assert committed > 0
    assert in_flight == 0
    assert hist.sum() > 0
