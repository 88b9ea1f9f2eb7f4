"""Unit tests for bench.py's measurement helpers.

The headline latency numbers are RECONSTRUCTED from per-round cursor
histories (slot injected when crt_inst first passes it, committed when
committed_upto first reaches it) — a bug here misreports the benchmark
without failing it, so the reconstruction gets its own oracle tests.
Also covers the sibling-offset port allocator the TCP harnesses use.
"""

from __future__ import annotations

import socket

import numpy as np

import bench
from minpaxos_tpu.utils.netutil import free_ports


def test_latency_single_shard_hand_computed():
    # row 0 is the pre-phase baseline cursor; rows 1.. are rounds
    crts = np.array([[0], [2], [4], [4], [4]])   # 0-1 in r1, 2-3 in r2
    uptos = np.array([[-1], [-1], [1], [2], [3]])  # 0-1 @r2, 2 @r3, 3 @r4
    p50, p99, n, unc = bench._latency_rounds(uptos, crts, round_ms=1.0)
    # slot0: in r1 c r2 -> 2; slot1: 2; slot2: in r2 c r3 -> 2;
    # slot3: in r2 c r4 -> 3
    assert n == 4 and unc == 0
    assert p50 == 2.0
    assert np.isclose(p99, np.percentile([2, 2, 2, 3], 99))


def test_latency_same_round_inject_commit_is_one_round():
    crts = np.array([[0], [3]])
    uptos = np.array([[-1], [2]])
    p50, p99, n, unc = bench._latency_rounds(uptos, crts, round_ms=2.5)
    assert n == 3 and unc == 0
    assert p50 == 2.5 and p99 == 2.5  # 1 round at 2.5 ms/round


def test_latency_slots_before_baseline_excluded():
    # slots 0-4 were assigned before the measured phase (baseline crt=5)
    crts = np.array([[5], [7]])
    uptos = np.array([[-1], [6]])
    p50, p99, n, unc = bench._latency_rounds(uptos, crts, round_ms=1.0)
    assert n == 2 and unc == 0  # only slots 5, 6 enter the sample


def test_latency_uncommitted_tail_reported_not_sampled():
    crts = np.array([[0], [5], [10]])
    uptos = np.array([[-1], [4], [6]])  # slots 7-9 assigned, never committed
    p50, p99, n, unc = bench._latency_rounds(uptos, crts, round_ms=1.0)
    assert unc == 3
    assert n == 7  # slots 0-6 committed and sampled


def test_latency_from_hist_hand_computed():
    """Resident-loop histogram percentiles: bin b = latency b+1 rounds;
    the sample reconstructs exactly, so percentiles match
    np.percentile of the explicit per-slot latencies."""
    hist = np.zeros(16, np.int32)
    hist[1] = 3  # three slots at 2 rounds
    hist[2] = 1  # one slot at 3 rounds
    p50, p99, n, overflow = bench._latency_from_hist(hist, round_ms=2.0)
    assert n == 4 and overflow == 0
    assert p50 == np.percentile(np.array([2, 2, 2, 3]) * 2.0, 50)
    assert p99 == np.percentile(np.array([2, 2, 2, 3]) * 2.0, 99)


def test_latency_from_hist_empty_and_overflow():
    p50, p99, n, overflow = bench._latency_from_hist(
        np.zeros(8, np.int32), 1.0)
    assert n == 0 and overflow == 0 and np.isnan(p50) and np.isnan(p99)
    hist = np.zeros(4, np.int32)
    hist[-1] = 5  # tail beyond the bin range: counted, reported
    p50, p99, n, overflow = bench._latency_from_hist(hist, 1.0)
    assert n == 5 and overflow == 5
    assert p50 == 4.0  # clipped AT the last bin, never dropped


def test_overflow_warning_is_loud_and_parser_safe():
    """A saturated histogram must warn on STDOUT (the artifact stamp
    alone was missable) without corrupting the one-JSON-line contract:
    the line cannot start with '{' (consumers filter on that) and must
    name the count."""
    assert bench.overflow_warning(0) is None
    w = bench.overflow_warning(37)
    assert w.startswith("WARNING") and not w.startswith("{")
    assert "latency_hist_overflow=37" in w and "SATURATED" in w


def test_latency_hist_agrees_with_latency_rounds():
    """The two latency paths are the same estimator: build a cursor
    history, compute host-side percentiles, then bin the same per-slot
    latencies into a histogram and compare bit-for-bit."""
    crts = np.array([[0], [2], [4], [4], [4]])
    uptos = np.array([[-1], [-1], [1], [2], [3]])
    p50_a, p99_a, n_a, _ = bench._latency_rounds(uptos, crts, 1.5)
    hist = np.zeros(512, np.int32)
    for lat in (2, 2, 2, 3):  # hand-derived from the history above
        hist[lat - 1] += 1
    p50_b, p99_b, n_b, _ = bench._latency_from_hist(hist, 1.5)
    assert (p50_a, p99_a, n_a) == (p50_b, p99_b, n_b)


def test_latency_round_ms_scales_linearly():
    rng = np.random.default_rng(3)
    # monotone random cursor walk, 3 shards
    crts = np.cumsum(rng.integers(0, 5, (20, 3)), axis=0)
    uptos = np.maximum(crts - rng.integers(1, 6, (20, 3)), -1)
    uptos[-1] = crts[-1] - 1  # drained
    a = bench._latency_rounds(uptos, crts, 1.0)
    b = bench._latency_rounds(uptos, crts, 7.0)
    assert np.isclose(b[0], 7 * a[0]) and np.isclose(b[1], 7 * a[1])
    assert a[2] == b[2] and a[3] == b[3] == 0


def test_free_ports_sibling_reserved():
    ports = free_ports(3, sibling_offset=1000)
    assert len(set(ports)) == 3
    for p in ports:
        for q in (p, p + 1000):
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", q))  # both halves actually free
            finally:
                s.close()


def test_free_ports_collision_skipped():
    # hold some port's sibling; allocator must never hand out that port
    held = socket.socket()
    held.bind(("127.0.0.1", 0))
    blocked_sibling = held.getsockname()[1]
    try:
        ports = free_ports(20, sibling_offset=1000)
        assert blocked_sibling - 1000 not in ports
    finally:
        held.close()


def test_keybuf_amortized_append_and_view():
    from minpaxos_tpu.models.cluster import KeyBuf, pack_reply_key

    kb = KeyBuf()
    expect = []
    for i in range(40):  # crosses several doubling boundaries
        keys = pack_reply_key(i % 5, np.arange(i * 31, i * 31 + 17))
        kb.append(keys)
        expect.append(np.atleast_1d(keys))
    got = kb.view()
    ref = np.concatenate(expect)
    assert got.dtype == np.int64 and np.array_equal(got, ref)
    # scalar append path
    kb2 = KeyBuf()
    kb2.append(pack_reply_key(7, 9))
    assert kb2.view().tolist() == [(7 << 32) | 9]


def test_keybuf_contains_matches_isin():
    from minpaxos_tpu.models.cluster import KeyBuf, pack_reply_key

    kb = KeyBuf()
    assert not kb.contains(np.asarray([1, 2, 3])).any()  # empty buffer
    rng = np.random.default_rng(7)
    for i in range(5):  # interleave appends and probes (cache refresh)
        kb.append(pack_reply_key(i, rng.integers(0, 1000, size=50)))
        probe = pack_reply_key(rng.integers(0, 6, size=200),
                               rng.integers(0, 1200, size=200))
        assert np.array_equal(kb.contains(probe),
                              np.isin(probe, kb.view()))


def test_pack_reply_key_no_collisions_across_clients():
    from minpaxos_tpu.models.cluster import pack_reply_key

    a = pack_reply_key(1, np.arange(1000))
    b = pack_reply_key(2, np.arange(1000))
    assert len(np.intersect1d(a, b)) == 0
    # cmd_id is masked to 32 bits; same (cid, mid) always packs equal
    assert pack_reply_key(3, 5) == pack_reply_key(3, 5)


def test_free_ports_impossible_request_raises():
    import pytest

    with pytest.raises(OSError):
        # no port p can have p+70000 as a sibling (> 65535)
        free_ports(1, sibling_offset=70000)


# -- shape_ladder adaptive-capacity policy (PR 11): pure helpers, no
# compile — the measured behavior is gated by the tier-1 ladder smoke


def test_adaptive_capacity_policy():
    from tools.shape_ladder import adaptive_capacity

    # hwm + 25% headroom, rounded up to 32; floor of 64
    assert adaptive_capacity(49) == 96
    assert adaptive_capacity(0) == 64
    assert adaptive_capacity(1281) == 1632
    for hwm in (1, 31, 32, 100, 500, 4096):
        cap = adaptive_capacity(hwm)
        assert cap % 32 == 0 and cap >= hwm + hwm // 4
        assert cap >= 64


def test_ladder_legality_contract():
    """Base points keep the PR-8/9 bar (drain-exact); adaptive points
    must additionally show no capacity-attributable loss — absolute
    lossless OR equal-to-base committed totals (deep-pipeline shapes
    bounce proposals off the full window at ANY capacity)."""
    from tools.shape_ladder import _legal

    base_lossy = {"drained_exact": True, "lossless": False}
    assert _legal(base_lossy)  # window bounce, not a capacity fault
    assert not _legal({"drained_exact": False, "lossless": True})
    assert not _legal({"drained_exact": True, "error": "boom"})
    adaptive_clean = {"drained_exact": True, "adaptive": True,
                      "lossless": True}
    assert _legal(adaptive_clean)
    adaptive_vs_base = {"drained_exact": True, "adaptive": True,
                        "lossless": False, "lossless_vs_base": True}
    assert _legal(adaptive_vs_base)
    adaptive_lossy = {"drained_exact": True, "adaptive": True,
                      "lossless": False}
    assert not _legal(adaptive_lossy)  # capacity dropped proposals
    mencius_base = {"drained_exact": True, "lossless": None}
    assert _legal(mencius_base)
