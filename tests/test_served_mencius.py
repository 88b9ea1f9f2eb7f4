"""Served Mencius (``cli/server.py -m``) as the benchmark's runner
composes it: ``ChaosCluster(protocol="mencius")``, every replica a
proposer, clients spread over all three owners on overlapping keys.

What PR 35's first rehearsal of the served cell found is pinned here:
the kernel folds all of one owner's SKIP rows of a batch into one
range, so a value the owner proposed BETWEEN two cedes (one TCP read
under three loaded owners) was committed as a no-op on the receiver;
``ReplicaServer._skip_rows_that_fit`` holds the second cede over for
the next dispatch.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from minpaxos_tpu.chaos.campaign import ChaosCluster
from minpaxos_tpu.models.minpaxos import MinPaxosConfig
from minpaxos_tpu.obs.trace import (ST_COMMIT, ST_DECODE, ST_DRAIN, ST_EXEC,
                                    ST_ORIGIN, ST_OWN_COMMIT, ST_REPLY_SER,
                                    merge_wait_ms, span_chains,
                                    stage_decomposition)
from minpaxos_tpu.runtime.client import MultiClient, gen_workload
from minpaxos_tpu.runtime.replica import ReplicaServer, RuntimeFlags
from minpaxos_tpu.runtime.transport import FROM_PEER
from minpaxos_tpu.verify.invariants import check_cluster
from minpaxos_tpu.wire.messages import MsgKind, Op, make_batch

#: ChaosCluster's default shape: the Mencius served step that
#: tests/test_distributed.py already builds
CFG = MinPaxosConfig(n_replicas=3, window=1 << 10, inbox=1024,
                     exec_batch=512, kv_pow2=12, catchup_rows=64,
                     recovery_rows=64)
N_OPS, KEYS = 600, 24  # three owners write the same few keys


def _quiesce(cluster, timeout_s=30.0) -> list[dict]:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        snaps = [s.snapshot for _, s in sorted(cluster.servers.items())]
        if (len({s["frontier"] for s in snaps}) == 1
                and all(s.get("executed") == s["frontier"] for s in snaps)):
            return snaps
        time.sleep(0.05)
    raise AssertionError(f"never quiesced: {snaps}")


@pytest.fixture
def cluster(tmp_path):
    c = ChaosCluster(n=3, store_dir=str(tmp_path), protocol="mencius",
                     flags={"trace_pow2": 0}, boot_timeout_s=120.0)
    yield c
    c.stop()


def test_three_owner_load_answers_as_the_sequential_oracle(cluster):
    assert {s.protocol for s in cluster.servers.values()} == {"mencius"}
    mc = MultiClient(cluster.maddr, check=True, mode="rr")
    ops, keys, vals = gen_workload(N_OPS, key_range=KEYS, write_pct=50,
                                   seed=35)
    # uneven on purpose (300 / 150 / 150): the lighter owners cede turns
    parts = [np.nonzero(np.isin(np.arange(N_OPS) % 4, own))[0]
             for own in ((0, 1), (2,), (3,))]
    results: list = [None] * 3
    threads = [threading.Thread(
        target=lambda r=r: results.__setitem__(r, mc.clients[r].run_partition(
            parts[r], ops, keys, vals, timeout_s=120.0)), daemon=True)
        for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=150)
    assert [r["acked"] for r in results] == [300, 150, 150], results
    assert sum(r["duplicates"] for r in results) == 0
    snaps = _quiesce(cluster)
    time.sleep(0.3)  # no append in flight under the checker
    replies: dict[int, dict] = {}
    for c in mc.clients:
        with c._lock:
            replies.update(c.replies)
    mc.close()
    # the sequential oracle: the merged log replayed slot by slot into
    # a dict, every acknowledged GET held to it (verify/invariants.py)
    report = check_cluster(cluster.stores(), replies=replies,
                           workload=(ops, keys, vals))
    assert report.ok, report.to_dict()
    assert report.checked_gets == int((ops == int(Op.GET)).sum())

    # the new counters add up to the slots the merged frontier passed
    stats = [s.stats for _, s in sorted(cluster.servers.items())]
    frontier = snaps[0]["frontier"]
    for st in stats:
        assert st["noop_slots"] + st["command_slots"] == frontier + 1
        assert st["command_slots"] == N_OPS
        assert st["executed"] == frontier + 1
    assert [st["client_proposals"] for st in stats] == [300, 150, 150]
    assert stats[0]["noop_slots"] > 0  # owners do cede turns

    # every owner's sampled commands carry the merge_wait stage:
    # own_commit (its COMMIT row left the device) no later than commit
    # (the merged frontier passed the slot)
    for _, srv in sorted(cluster.servers.items()):
        spans = np.asarray(srv.trace_sink.collect()["spans"],
                           np.int64).reshape(-1, 5)
        chains = span_chains(spans)
        both = [c for c in chains.values()
                if ST_OWN_COMMIT in c and ST_COMMIT in c]
        assert len(both) >= 100, len(both)
        assert all(c[ST_OWN_COMMIT][1] <= c[ST_COMMIT][1] for c in both)
        assert all(c[ST_OWN_COMMIT][2] == c[ST_COMMIT][2] for c in both)  # slot
        assert all(merge_wait_ms(c) >= 0 for c in both)


def test_merge_wait_is_the_tail_of_the_commit_stage():
    """A hand-made chain: own_commit at 5 ms, commit at 9 ms."""
    ms = 1_000_000
    marks = {ST_ORIGIN: (0, 0), ST_DECODE: (1, 1), ST_DRAIN: (2, 2),
             ST_OWN_COMMIT: (5, 5), ST_COMMIT: (9, 9), ST_EXEC: (10, 10),
             ST_REPLY_SER: (10, 11)}
    spans = np.array([[77, stage, a * ms, b * ms, 3]
                      for stage, (a, b) in marks.items()], np.int64)
    (d,) = stage_decomposition(span_chains(spans))
    assert d["merge_wait_ms"] == 4.0 and d["stages"]["commit"] == 7.0
    assert sum(d["stages"].values()) == d["total_ms"] == 11.0
    # without the stamp (MinPaxos, or a slot the frontier passed before
    # its COMMIT row left) the chain is whole and the wait unknown
    (d,) = stage_decomposition(span_chains(spans[spans[:, 1] != ST_OWN_COMMIT]))
    assert d["merge_wait_ms"] is None and d["stages"]["commit"] == 7.0
    # an own_commit AFTER the commit belongs to no chain
    late = spans.copy()
    late[late[:, 1] == ST_OWN_COMMIT, 2:4] = 12 * ms
    assert ST_OWN_COMMIT not in span_chains(late)[77]


def test_restart_keeps_the_protocol(cluster):
    cluster.kill(1)
    cluster.restart(1)
    assert cluster.servers[1].protocol == "mencius"
    deadline = time.monotonic() + 60
    while not cluster.servers[1].stats["ticks"]:
        assert time.monotonic() < deadline, "the restarted replica never ticked"
        time.sleep(0.05)


# ------------------------------------------ the SKIP rows of one batch

def _skip(owner, start, end):
    return (FROM_PEER, owner, MsgKind.SKIP, make_batch(
        MsgKind.SKIP, leader_id=owner, start_inst=start, end_inst=end))


def _accept(owner, inst, key, val, cmd):
    return (FROM_PEER, owner, MsgKind.ACCEPT, make_batch(
        MsgKind.ACCEPT, leader_id=owner, inst=inst, ballot=0,
        last_committed=-1, op=int(Op.PUT), key=key, val=val, cmd_id=cmd,
        client_id=3))


@pytest.fixture
def server(tmp_path):
    """Replica 0 of three, no thread or socket started: the test
    drives ``_drain`` / ``_device_tick`` itself."""
    srv = ReplicaServer(0, [("127.0.0.1", 7070 + i) for i in range(3)], CFG,
                        RuntimeFlags(store_dir=str(tmp_path)),
                        protocol="mencius")
    srv.transport.send_peer = lambda *a, **k: True  # no peers to reach
    yield srv
    srv.store.close()


def test_a_cede_after_a_proposal_waits_for_the_next_dispatch(server):
    """Owner 1 cedes slot 1, proposes a PUT into slot 4, cedes slot 7:
    one TCP read. In one batch the kernel would fold the two cedes
    into [1, 7] and commit slot 4 as a no-op."""
    for item in (_skip(1, 1, 1), _accept(1, 4, 7, 9, 5), _skip(1, 7, 7),
                 _skip(2, 2, 2)):
        server.queue.put(item)
    server._drain(0.01)
    kinds = server.inbox.cols["kind"][:server.inbox.fill].tolist()
    assert kinds == [int(MsgKind.SKIP), int(MsgKind.ACCEPT)]
    assert server._held[2] == MsgKind.SKIP and server._more_queued()
    server._device_tick(server.inbox)
    server._drain(0.01)  # the held cede first, then owner 2's
    assert server._held is None
    assert server.inbox.cols["inst"][:server.inbox.fill].tolist() == [7, 2]
    server._device_tick(server.inbox)
    rel = 4 - server.snapshot["window_base"]
    assert int(np.asarray(server.state.op)[rel]) == int(Op.PUT)
    rec = server.store.read_range(4, 4)
    assert (int(rec["op"][0]), int(rec["key"][0]), int(rec["val"][0])) == (
        int(Op.PUT), 7, 9)


def test_cedes_that_touch_share_a_batch(server):
    """Ranges with no own slot of the owner between them fold exactly:
    nothing is held, whatever order they come in."""
    frame = make_batch(MsgKind.SKIP, leader_id=[1, 1, 2, 1],
                       start_inst=[1, 7, 2, 13], end_inst=[4, 10, 5, 13])
    assert server._skip_rows_that_fit(frame) == 4
    assert server._skip_span == {1: (1, 13), 2: (2, 5)}
    # a gap of one own slot (16 is owner 1's and in neither range)
    gap = make_batch(MsgKind.SKIP, leader_id=[1, 1], start_inst=[19, 22],
                     end_inst=[19, 22])
    assert server._skip_rows_that_fit(gap) == 0
    server._skip_span.clear()
    assert server._skip_rows_that_fit(gap) == 2  # 19 and 22 touch
