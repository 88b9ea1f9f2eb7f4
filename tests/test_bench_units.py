"""Unit tests for small host-side helpers.

The reference of the device latency histogram RECONSTRUCTS latencies
from per-round cursor histories (slot injected when crt_inst first
passes it, committed when committed_upto first reaches it) — a bug
there would let a wrong histogram pass tests/test_workload.py, so the
reconstruction gets its own hand-computed tests. Also covers the
sibling-offset port allocator the TCP harnesses use, and the pod
cluster's reply-key buffer.
"""

from __future__ import annotations

import socket

import numpy as np

from minpaxos_tpu.utils.netutil import free_ports
from tests.latency_oracle import latency_rounds


def test_latency_single_shard_hand_computed():
    # row 0 is the pre-phase baseline cursor; rows 1.. are rounds
    crts = np.array([[0], [2], [4], [4], [4]])   # 0-1 in r1, 2-3 in r2
    uptos = np.array([[-1], [-1], [1], [2], [3]])  # 0-1 @r2, 2 @r3, 3 @r4
    p50, p99, n, unc = latency_rounds(uptos, crts, round_ms=1.0)
    # slot0: in r1 c r2 -> 2; slot1: 2; slot2: in r2 c r3 -> 2;
    # slot3: in r2 c r4 -> 3
    assert n == 4 and unc == 0
    assert p50 == 2.0
    assert np.isclose(p99, np.percentile([2, 2, 2, 3], 99))


def test_latency_same_round_inject_commit_is_one_round():
    crts = np.array([[0], [3]])
    uptos = np.array([[-1], [2]])
    p50, p99, n, unc = latency_rounds(uptos, crts, round_ms=2.5)
    assert n == 3 and unc == 0
    assert p50 == 2.5 and p99 == 2.5  # 1 round at 2.5 ms/round


def test_latency_slots_before_baseline_excluded():
    # slots 0-4 were assigned before the measured phase (baseline crt=5)
    crts = np.array([[5], [7]])
    uptos = np.array([[-1], [6]])
    p50, p99, n, unc = latency_rounds(uptos, crts, round_ms=1.0)
    assert n == 2 and unc == 0  # only slots 5, 6 enter the sample


def test_latency_uncommitted_tail_reported_not_sampled():
    crts = np.array([[0], [5], [10]])
    uptos = np.array([[-1], [4], [6]])  # slots 7-9 assigned, never committed
    p50, p99, n, unc = latency_rounds(uptos, crts, round_ms=1.0)
    assert unc == 3
    assert n == 7  # slots 0-6 committed and sampled






def test_latency_round_ms_scales_linearly():
    rng = np.random.default_rng(3)
    # monotone random cursor walk, 3 shards
    crts = np.cumsum(rng.integers(0, 5, (20, 3)), axis=0)
    uptos = np.maximum(crts - rng.integers(1, 6, (20, 3)), -1)
    uptos[-1] = crts[-1] - 1  # drained
    a = latency_rounds(uptos, crts, 1.0)
    b = latency_rounds(uptos, crts, 7.0)
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
