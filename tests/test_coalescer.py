"""Ingress-coalescer policy units and the event-driven runtime's
equality contract (runtime/batches.py IngressCoalescer + the
runtime/replica.py exec chase).

Policy units drive the coalescer directly — no cluster, no sockets:
max-wait/max-rows boundaries, single-command dispatch, the cv kick,
and the admission-reject path are all observable through the queue
protocol plus the paxmon counters the coalescer registers.

The equality pin mirrors tests/test_pipeline.py: the event-driven
path's claim is RESCHEDULING, not approximation — coalesced ingress
plus the overlapped commit->exec->reply chase must produce
byte-identical replies and leaf-identical device state versus the
cadence-driven strict order (-nocoalesce -nooverlapexec), over a
randomized multi-tick trace.
"""

from __future__ import annotations

import queue
import threading
import time

import jax
import numpy as np
import pytest

from minpaxos_tpu.models.minpaxos import MinPaxosConfig
from minpaxos_tpu.runtime.batches import IngressCoalescer
from minpaxos_tpu.runtime.replica import CONTROL, ReplicaServer, RuntimeFlags
from minpaxos_tpu.runtime.transport import FROM_CLIENT, FROM_PEER
from minpaxos_tpu.wire.messages import MsgKind, Op, make_batch

CID = 7

CFG = MinPaxosConfig(n_replicas=1, window=128, inbox=16, exec_batch=8,
                     kv_pow2=8, catchup_rows=8, recovery_rows=8,
                     gossip_ticks=1)


def _frame(rows: int, base: int = 0) -> np.ndarray:
    return make_batch(
        MsgKind.PROPOSE,
        cmd_id=(base + np.arange(rows)).astype(np.int32),
        op=np.full(rows, int(Op.PUT), np.uint8),
        key=np.arange(rows).astype(np.int64),
        val=np.arange(rows).astype(np.int64),
        timestamp=0)


def _client_item(rows: int, base: int = 0):
    return (FROM_CLIENT, CID, MsgKind.PROPOSE, _frame(rows, base))


# ------------------------------------------------- batch-formation policy


def test_single_command_dispatches_at_max_wait_not_poll_interval():
    """A lone command lingers AT MOST max_wait_us (counted as a
    deadline hit), never a poll interval: the whole point of the
    coalescer for the serial-latency story."""
    c = IngressCoalescer(max_wait_us=2000, max_rows=64)
    c.put(_client_item(1))
    t0 = time.perf_counter()
    src, cid, kind, rows = c.get(timeout=5.0)
    dt = time.perf_counter() - t0
    assert kind == MsgKind.PROPOSE and len(rows) == 1
    assert dt < 0.5  # 2 ms linger with wide scheduling slack
    assert c._c_deadline_hits.value == 1
    assert c.last_occupancy == 1
    assert c.empty()


def test_zero_max_wait_dispatches_immediately():
    c = IngressCoalescer(max_wait_us=0, max_rows=64)
    c.put(_client_item(1))
    c.get(timeout=1.0)
    assert c._c_deadline_hits.value == 0  # no linger, no deadline


def test_max_rows_boundary_skips_the_linger():
    """Pending rows >= max_rows: the batch is device-sized already —
    dispatch without waiting out max_wait (no deadline hit)."""
    c = IngressCoalescer(max_wait_us=10_000_000, max_rows=8)
    c.put(_client_item(8))
    t0 = time.perf_counter()
    c.get(timeout=1.0)
    assert time.perf_counter() - t0 < 1.0  # not the 10 s max-wait
    assert c._c_deadline_hits.value == 0
    assert c.last_occupancy == 8


def test_max_rows_boundary_one_below_lingers():
    """max_rows - 1 pending rows DOES linger (deadline hit): the
    boundary is >=, not >."""
    c = IngressCoalescer(max_wait_us=1000, max_rows=8)
    c.put(_client_item(7))
    c.get(timeout=1.0)
    assert c._c_deadline_hits.value == 1


def test_linger_accumulates_occupancy_across_frames():
    """Frames queued before the drain all count toward the drained
    batch's occupancy (the histogram sample), and FIFO order holds."""
    c = IngressCoalescer(max_wait_us=500, max_rows=256)
    for f in range(3):
        c.put(_client_item(4, base=f * 4))
    first = c.get(timeout=1.0)
    assert c.last_occupancy == 12  # all three frames were pending
    assert int(first[3]["cmd_id"][0]) == 0  # FIFO
    assert int(c.get_nowait()[3]["cmd_id"][0]) == 4
    assert int(c.get_nowait()[3]["cmd_id"][0]) == 8
    with pytest.raises(queue.Empty):
        c.get_nowait()


def test_cv_kick_wakes_a_parked_getter():
    """put() must wake a blocked get() immediately — the cadence
    replacement. The getter parks with a long timeout; the kick lands
    well before it."""
    c = IngressCoalescer(max_wait_us=0, max_rows=64)
    got: list = []

    def park():
        got.append(c.get(timeout=5.0))

    t = threading.Thread(target=park)
    t.start()
    time.sleep(0.05)  # let the getter park
    t0 = time.perf_counter()
    c.put(_client_item(1))
    t.join(timeout=2.0)
    assert not t.is_alive() and got
    assert time.perf_counter() - t0 < 1.0  # woke on the kick
    assert c._c_wakeups.value == 1


def test_get_timeout_raises_empty():
    c = IngressCoalescer(max_wait_us=0, max_rows=64)
    with pytest.raises(queue.Empty):
        c.get(timeout=0.01)


def test_non_client_items_carry_zero_row_weight():
    """CONTROL and peer frames pass through without counting toward
    the batch-formation policy (they are not coalescable proposals)."""
    c = IngressCoalescer(max_wait_us=10_000_000, max_rows=4)
    c.put((CONTROL, 0, "be_the_leader", None))
    c.put((FROM_PEER, 1, MsgKind.BEACON, _frame(4)))
    assert c.qsize() == 2 and c._pending_rows == 0
    t0 = time.perf_counter()
    assert c.get(timeout=1.0)[2] == "be_the_leader"
    assert time.perf_counter() - t0 < 1.0  # zero pending rows: no linger
    assert c._c_deadline_hits.value == 0


# ------------------------------------------------------ admission control


def test_backpressure_reject_counts_and_drops():
    """Gate True + pending beyond max_rows: the put is DROPPED and
    counted — bounded queueing, the client's retransmit recovers."""
    c = IngressCoalescer(max_wait_us=0, max_rows=4,
                         admit_gate=lambda: True)
    c.put(_client_item(4))       # fills the bound
    c.put(_client_item(4, 100))  # beyond the bound: shed
    assert c._c_rejects.value == 4
    assert c.qsize() == 1
    assert c._pending_rows == 4


def test_admission_gate_false_admits_beyond_bound():
    """A healthy replica (gate False) never sheds: the bound only
    engages under the overload verdict."""
    c = IngressCoalescer(max_wait_us=0, max_rows=4,
                         admit_gate=lambda: False)
    c.put(_client_item(4))
    c.put(_client_item(4, 100))
    assert c._c_rejects.value == 0
    assert c.qsize() == 2


def test_admission_never_sheds_control_or_peer_traffic():
    """Only client PROPOSE rows are sheddable: protocol traffic and
    control events must get through no matter how hot the gate is."""
    c = IngressCoalescer(max_wait_us=0, max_rows=1,
                         admit_gate=lambda: True)
    c.put(_client_item(1))
    c.put((CONTROL, 0, "be_the_leader", None))
    c.put((FROM_PEER, 1, MsgKind.ACCEPT, _frame(8)))
    assert c.qsize() == 3
    assert c._c_rejects.value == 0


def test_paxmon_metrics_registered():
    from minpaxos_tpu.obs.metrics import MetricsRegistry

    m = MetricsRegistry(namespace="test")
    c = IngressCoalescer(max_wait_us=500, max_rows=8, metrics=m)
    c.put(_client_item(3))
    c.get(timeout=1.0)
    snap = m.snapshot()
    counters = dict(snap.get("counters") or {})
    counters.update(snap.get("gauges") or {})
    assert counters.get("coalesce_deadline_hits") == 1
    assert c._pending_rows == 0 and c.qsize() == 0
    hist = (snap.get("histograms") or {}).get("coalesce_batch_rows")
    assert hist and hist["count"] == 1


# --------------------------------------- strict vs event-driven equality


def _mk_server(tmp_path, name: str, event_driven: bool) -> ReplicaServer:
    d = tmp_path / name
    d.mkdir()
    flags = RuntimeFlags(store_dir=str(d), coalesce=event_driven,
                         overlap_exec=event_driven,
                         coalesce_wait_us=200)
    return ReplicaServer(0, [("127.0.0.1", 7077)], CFG, flags)


def _capture_replies(srv: ReplicaServer, log: list) -> None:
    srv.transport.send_client = (  # type: ignore[method-assign]
        lambda cid, kind, rows: log.append((cid, int(kind), rows.copy()))
        or True)


def _elect(srv: ReplicaServer) -> None:
    srv.queue.put((CONTROL, 0, "be_the_leader", None))
    for _ in range(20):
        if srv._drain(0.001):
            srv._become_leader()
        srv._device_tick(srv.inbox)
        if srv.snapshot["prepared"]:
            return
    raise AssertionError(f"never prepared: {srv.snapshot}")


def _trace(n_frames: int, rows: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    out = []
    for f in range(n_frames):
        ops = rng.choice([int(Op.PUT), int(Op.GET)], size=rows,
                         p=[0.7, 0.3])
        out.append(make_batch(
            MsgKind.PROPOSE,
            cmd_id=(1000 + f * rows + np.arange(rows)).astype(np.int32),
            op=ops.astype(np.uint8),
            key=rng.integers(0, 40, rows).astype(np.int64),
            val=rng.integers(1, 1 << 20, rows).astype(np.int64),
            timestamp=0))
    return out


def _run_trace_ticks(srv: ReplicaServer, trace: list[np.ndarray],
                     n_ticks: int) -> list:
    """Drive the REAL ``_tick`` (drain + dispatch + exec chase) — not
    the bare _drain/_device_tick pair test_pipeline uses — so the
    event-driven server exercises its chase and the strict server its
    cadence, over identical queued input."""
    replies: list = []
    _capture_replies(srv, replies)
    _elect(srv)
    for frame in trace:
        srv.queue.put((FROM_CLIENT, CID, MsgKind.PROPOSE, frame))
    for _ in range(n_ticks):
        srv._tick()
    srv._flush_inflight()
    return replies


def test_event_driven_equals_strict_order_over_randomized_trace(tmp_path):
    """Byte-exact replies (content and per-connection order) and
    leaf-identical device state: coalescer+chase ON vs OFF, same
    trace. The event-driven run must actually coalesce (wakeups or
    drained occupancy observed) and chase (more dispatches per wakeup
    than ticks), else this proves nothing."""
    trace = _trace(n_frames=6, rows=CFG.inbox, seed=11)
    n_ticks = 3 * len(trace) + 12
    srv_e = _mk_server(tmp_path, "event", event_driven=True)
    srv_s = _mk_server(tmp_path, "strict", event_driven=False)
    try:
        rep_e = _run_trace_ticks(srv_e, trace, n_ticks)
        rep_s = _run_trace_ticks(srv_s, trace, n_ticks)
        assert srv_e.coalescer is not None
        assert srv_s.coalescer is None
        # both runs fully drained the trace
        for srv in (srv_e, srv_s):
            assert srv.stats["executed"] == 6 * CFG.inbox, srv.stats
        n_cmds = sum(len(rep[2]["cmd_id"]) for rep in rep_e
                     if rep[1] == int(MsgKind.PROPOSE_REPLY))
        assert n_cmds == 6 * CFG.inbox
        assert len(rep_e) == len(rep_s), (len(rep_e), len(rep_s))
        for i, ((ca, ka, ra), (cb, kb, rb)) in enumerate(zip(rep_e, rep_s)):
            assert (ca, ka) == (cb, kb), i
            for f in ra.dtype.names:
                if f == "timestamp":
                    continue  # wall-clock stamp: the one intended delta
                np.testing.assert_array_equal(
                    ra[f], rb[f], err_msg=f"reply {i} field {f}")
        assert srv_e.snapshot == srv_s.snapshot
        for leaf_e, leaf_s in zip(
                jax.tree_util.tree_leaves(srv_e.state),
                jax.tree_util.tree_leaves(srv_s.state)):
            np.testing.assert_array_equal(np.asarray(leaf_e),
                                          np.asarray(leaf_s))
    finally:
        srv_e.store.close()
        srv_s.store.close()


def test_exec_chase_runs_followups_in_one_wakeup(tmp_path):
    """The chase's observable effect: after one _tick on a committed
    backlog with an empty queue, execution has caught the frontier —
    the strict server needs further ticks for the same progress."""
    srv = _mk_server(tmp_path, "chase", event_driven=True)
    _capture_replies(srv, [])
    try:
        _elect(srv)
        srv.queue.put((FROM_CLIENT, CID, MsgKind.PROPOSE,
                       _frame(CFG.inbox, base=1000)))
        srv._tick()  # drains, dispatches, then chases the exec backlog
        srv._flush_inflight()
        snap = srv.snapshot
        assert snap["frontier"] >= 0
        assert int(snap.get("executed", -1)) == int(snap["frontier"]), snap
    finally:
        srv.store.close()


def test_recorder_carries_coalescer_fields(tmp_path):
    """Schema-v7 rows: drained occupancy and the cumulative wakeup
    count ride the flight recorder on the event-driven server."""
    from minpaxos_tpu.obs.recorder import F_COAL_OCC, F_COAL_WAKE

    srv = _mk_server(tmp_path, "rec", event_driven=True)
    _capture_replies(srv, [])
    try:
        _elect(srv)
        for f in range(3):
            srv.queue.put((FROM_CLIENT, CID, MsgKind.PROPOSE,
                           _frame(CFG.inbox, base=1000 + f * CFG.inbox)))
        for _ in range(12):
            srv._tick()
        srv._flush_inflight()
        rows = srv.recorder.snapshot()
        assert rows.shape[1] >= F_COAL_WAKE + 1
        assert (rows[:, F_COAL_OCC] > 0).any()  # some tick drained rows
        wake = rows[:, F_COAL_WAKE]
        assert (np.diff(wake[wake > 0]) >= 0).all()  # cumulative counter
    finally:
        srv.store.close()


def test_nocoalesce_cli_flags_reach_runtime_flags():
    """cli/server.py wires the ISSUE-15 escape hatches into
    RuntimeFlags (source-text pin, like -nopipeline's)."""
    import inspect

    from minpaxos_tpu.cli import server as cli_server

    src = inspect.getsource(cli_server)
    assert "-nocoalesce" in src
    assert "coalesce=not args.nocoalesce" in src
    assert "-nooverlapexec" in src
    assert "overlap_exec=not args.nooverlapexec" in src
    assert "-coalesce-wait-us" in src
    assert "coalesce_wait_us=args.coalesce_wait_us" in src
