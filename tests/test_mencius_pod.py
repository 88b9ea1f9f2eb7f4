"""The Mencius pod (BASELINE config 4) through the resident loop:
per-owner client streams, and COMMAND accounting on the device.

CPU only, counts only. The resident runs reuse the Mencius shape
tests/test_route_fabric.py compiles (two groups x five replicas x
window 512, one round a dispatch, a 64-row telemetry ring), so the
kernel is compiled once for both files through the persistent cache.
What the slow ``test_workload.py
test_mencius_resident_loop_commits_and_drains`` held (Mencius commits,
drains exactly and samples latencies in the resident loop) is held
here, in tier-1.
"""

from __future__ import annotations

import functools
import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from minpaxos_tpu import obs
from minpaxos_tpu.models.minpaxos import MinPaxosConfig
from minpaxos_tpu.ops.workload import (
    owner_key_range,
    propose_batch,
    propose_batch_host,
)
from minpaxos_tpu.parallel import sharded
from minpaxos_tpu.wire.messages import MsgKind, Op

R, G, ROWS, KEY_SPACE = 5, 4, 32, 1 << 10


def _equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(getattr(a, f)), getattr(b, f))
               for f in a._fields)


def _digest(batch) -> str:
    return hashlib.sha256(b"".join(
        np.ascontiguousarray(getattr(batch, f)).tobytes()
        for f in batch._fields)).hexdigest()


# ------------------------------------------------------ the streams

@pytest.mark.parametrize("count", [20, [20, 0, 5, 32, 7]])
@pytest.mark.parametrize("leader", [-1, 2])
def test_owner_stream_device_and_host_byte_equal(leader, count):
    """Same (seed, round) => byte-identical [G, R, M] rows on the
    device and on the host, for every owner proposing and for one
    alone, with one count for all and with one per owner."""
    for rnd in (0, 1, 17, 4096):
        dev = propose_batch(R, G, ROWS, jnp.asarray(count, jnp.int32),
                            jnp.int32(leader), jnp.int32(rnd), jnp.int32(99),
                            KEY_SPACE, owners=True)
        host = propose_batch_host(R, G, ROWS, count, leader, rnd, 99,
                                  KEY_SPACE, owners=True)
        assert _equal(dev, host), (leader, rnd)
    live = host.kind == int(MsgKind.PROPOSE)
    want = np.broadcast_to(np.asarray(count), (R,))
    want = np.where((np.arange(R) == leader) | (leader < 0), want, 0)
    np.testing.assert_array_equal(live.sum(axis=2),
                                  np.broadcast_to(want, (G, R)))


def test_leader_below_zero_means_every_owner_its_own_rows():
    """``leader < 0`` without ``owners`` is the multi-owner stream, on
    both sides: no two owners are handed the same rows any more."""
    dev = propose_batch(R, G, ROWS, jnp.int32(ROWS), jnp.int32(-1),
                        jnp.int32(3), jnp.int32(7), KEY_SPACE)
    host = propose_batch_host(R, G, ROWS, ROWS, -1, 3, 7, KEY_SPACE)
    assert _equal(dev, host)
    assert _equal(host, propose_batch_host(R, G, ROWS, ROWS, -1, 3, 7,
                                           KEY_SPACE, owners=True))
    for a in range(R):
        for b in range(a + 1, R):
            assert not np.array_equal(host.val_lo[:, a], host.val_lo[:, b])


def test_owner_ranges_disjoint_keys_distinct_ids_unique():
    per_owner = owner_key_range(KEY_SPACE, R)
    assert per_owner == 128 and owner_key_range(8192, 5) == 1024
    seen_ids: set = set()
    keys_of = [set() for _ in range(R)]
    for rnd in range(24):
        b = propose_batch_host(R, G, ROWS, ROWS, -1, rnd, 5, KEY_SPACE)
        assert (b.op == int(Op.PUT)).all()
        for o in range(R):
            k = b.key_lo[:, o]
            # an owner's keys stay in its own range ...
            assert (k >= o * per_owner).all() and (k < (o + 1) * per_owner).all()
            # ... and are duplicate-free within a (group, round)
            for g in range(G):
                assert len(np.unique(k[g])) == ROWS
            keys_of[o] |= set(k.ravel().tolist())
        # ids are unique across owners, rows and rounds of a group,
        # and every owner answers its own client
        ids = set(zip(b.client_id[0].ravel().tolist(),
                      b.cmd_id[0].ravel().tolist()))
        assert len(ids) == R * ROWS and not ids & seen_ids
        assert len(set(b.cmd_id[0].ravel().tolist())) == R * ROWS
        seen_ids |= ids
        np.testing.assert_array_equal(
            b.client_id[:, :, 0], np.arange(G)[:, None] * R + np.arange(R))
    # keys recur across rounds (768 draws from 128 keys each)
    assert all(len(k) <= per_owner for k in keys_of)
    assert all(not keys_of[a] & keys_of[b]
               for a in range(R) for b in range(a + 1, R))


def test_single_leader_stream_is_the_parents():
    """``leader >= 0``: the stream is what it was before the
    multi-owner one existed (digests taken from the parent commit)."""
    assert _digest(propose_batch_host(5, 4, 32, 20, 0, 17, 99, 1 << 10)) == (
        "c7f8455fe492ad8a89a2ad3adf5fad4c54dabbd2627e4e1b07de95198ca0c5f9")
    assert _digest(propose_batch_host(5, 4, 32, 20, 3, 4096, 7, 1 << 12)) == (
        "c369ab5ea0b8a6af18f85635f0d1399708e1f94cc75218dcaa051860a3f34570")
    dev = propose_batch(5, 4, 32, jnp.int32(20), jnp.int32(3),
                        jnp.int32(4096), jnp.int32(7), 1 << 12)
    assert _equal(dev, propose_batch_host(5, 4, 32, 20, 3, 4096, 7, 1 << 12))


# ---------------------------------------------- the resident loop

#: tests/test_route_fabric.py's two-tier shape (``_TIER_KW``): the same
#: program, found in the compile cache by whichever file runs second
_KW = dict(n_replicas=5, window=512, inbox=384, exec_batch=32, kv_pow2=10,
           catchup_rows=128, recovery_rows=16)
_EXT, _GROUPS = 16, 2
#: six a round per owner: 30 a group, inside the exec batch of 32
_P = 6
_ROUNDS, _IDLE, _DRAIN_FROM = 40, range(8, 16), 26


def _offered(i: int) -> list[int]:
    if i >= _DRAIN_FROM:
        return [0] * R
    return [_P, _P, 0, _P, _P] if i in _IDLE else [_P] * R


@functools.lru_cache(maxsize=None)
def _resident_run():
    """Loaded rounds (owner 2 idle for eight of them), then the drain,
    one round a dispatch; after every round the cursors and each
    replica's log, for the host-side computation."""
    sc = sharded.ShardedCluster(MinPaxosConfig(**_KW), _GROUPS,
                                ext_rows=_EXT, key_space=256,
                                protocol="mencius")
    sc.begin_resident(telemetry_rounds=64)
    history, scalars = [], []
    for i in range(_ROUNDS):
        scalars.append(sc.run_resident(1, _offered(i)))
        st = sc.ss.states
        history.append({f: np.asarray(getattr(st, f)) for f in (
            "committed_upto", "executed_upto", "crt_own", "window_base",
            "client_id", "status")})
    tiers = sc.resident_tiers()
    counts = sc.command_counts()
    return history, scalars, counts, tiers, sc.end_resident(), sc.committed()


def _host_side(history):
    """Commands, no-op slots and the latency histogram from the
    per-round cursor histories and logs, slot by slot, by another
    observable than the device's: a command is a slot whose logged
    client id is a client's (no-op slots carry -1)."""
    assigned_at = [dict() for _ in range(_GROUPS)]  # slot -> round
    hist = np.zeros(sharded.LATENCY_BINS, np.int64)
    commands = noops = 0
    co_prev = np.tile(np.arange(R), (_GROUPS, 1))
    u_prev = np.full(_GROUPS, -1)
    for r, h in enumerate(history):
        for g in range(_GROUPS):
            for o in range(R):
                base = int(h["window_base"][g, o])
                for slot in range(int(co_prev[g, o]), int(h["crt_own"][g, o]), R):
                    if h["client_id"][g, o, slot - base] >= 0:
                        assigned_at[g][slot] = r
            base = int(h["window_base"][g, 0])
            for slot in range(int(u_prev[g]) + 1,
                              int(h["committed_upto"][g, 0]) + 1):
                if h["client_id"][g, 0, slot - base] >= 0:
                    commands += 1
                    hist[r - assigned_at[g][slot]] += 1
                else:
                    noops += 1
        co_prev, u_prev = h["crt_own"], h["committed_upto"][:, 0]
    return commands, noops, hist, sum(len(a) for a in assigned_at)


def test_resident_mencius_drains_exactly_in_commands_with_an_idle_owner():
    history, scalars, counts, _, hist, committed = _resident_run()
    injected = _GROUPS * sum(sum(_offered(i)) for i in range(_ROUNDS))
    last = history[-1]
    # the two scalars are commands: all committed, none in flight
    assert scalars[-1] == (injected, 0)
    assert committed[0] == counts["commands"] == counts["assigned"] == injected
    # the idle owner ceded its slots: no-op slots were committed, and
    # they are no part of the commits
    assert counts["noop_slots"] > 0
    slots = int((last["committed_upto"][:, 0] + 1).sum())
    assert slots == counts["commands"] + counts["noop_slots"] > injected
    # every replica committed and executed the same prefix
    assert (last["committed_upto"] == last["committed_upto"][:, :1]).all()
    assert (last["executed_upto"] == last["committed_upto"]).all()
    # committed never runs ahead of assigned, round by round
    assert all(c >= 0 and f >= 0 for c, f in scalars)
    assert [c for c, _ in scalars] == sorted(c for c, _ in scalars)
    assert hist.sum() == injected and hist[-1] == 0


def test_resident_mencius_counts_and_histogram_match_the_host_side():
    history, scalars, counts, _, hist, _ = _resident_run()
    commands, noops, want_hist, assigned = _host_side(history)
    assert (counts["commands"], counts["noop_slots"], counts["assigned"]) == (
        commands, noops, assigned)
    np.testing.assert_array_equal(hist, want_hist)
    # a command waits on every owner's earlier slots: never under 3
    # rounds (propose, accept + ack, commit row), and the idle owner's
    # rounds stretch it
    assert hist[:2].sum() == 0 and hist[2:5].sum() > 0 and hist[4:].sum() > 0


def test_pod_entry_carries_the_windows_command_counts():
    _, _, counts, tiers, _, _ = _resident_run()
    pod = [p for p in obs.process_pods() if p["protocol"] == "mencius"
           and p["command_commits"] is not None][-1]
    assert pod["command_commits"] == counts["commands"]
    assert pod["noop_slots"] == counts["noop_slots"]
    assert pod["tiers"]["rounds"] == tiers["rounds"] == _ROUNDS
    # the recovery gates: an idle owner cedes by SKIP rows, which are
    # no recovery, and nothing stalled, so no takeover's gate opened
    # and every round took the small tier's steady kernel
    assert pod["gates"] == tiers["gates"] == {
        "px.takeover_phase1": 0, "px.takeover": 0}
    assert counts["noop_slots"] > 0
    # a single-leader pod has no such counts
    single = sharded.ShardedCluster(MinPaxosConfig(**_KW), 1, ext_rows=_EXT)
    assert single._counts is None
    assert obs.process_pods()[-1]["command_commits"] is None


def test_host_stepped_rounds_count_commands_too():
    """``ShardedCluster.step`` (one round from the host) keeps the same
    counts, so ``committed()`` means commands in every path."""
    sc = sharded.ShardedCluster(MinPaxosConfig(**_KW), _GROUPS,
                                ext_rows=_EXT, key_space=256,
                                protocol="mencius")
    for _ in range(6):
        sc.step(_P)
    for _ in range(6):
        sc.step(0)
    counts = sc.command_counts()
    assert sc.committed()[0] == counts["commands"] == counts["assigned"] \
        == 6 * _P * R * _GROUPS
    assert counts["noop_slots"] == 0
    assert int((np.asarray(sc.ss.states.committed_upto)[:, 0] + 1).sum()) \
        == counts["commands"]


def test_fused_rounds_count_commands_too():
    """``run_fused`` (the host-in-the-loop runner) carries the counts
    through its scan; its cursor histories stay slots of the log."""
    sc = sharded.ShardedCluster(MinPaxosConfig(**_KW), _GROUPS,
                                ext_rows=_EXT, key_space=256,
                                protocol="mencius")
    sc.run_fused(4, _P)
    uptos, crts = sc.run_fused(4, 0)
    assert sc.committed()[0] == sc.command_counts()["assigned"] \
        == 4 * _P * R * _GROUPS
    assert (uptos[-1] + 1).sum() == sc.committed()[0]  # no no-op here
    assert (crts[-1] - 1 == uptos[-1]).all()
