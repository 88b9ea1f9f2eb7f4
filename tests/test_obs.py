"""paxmon observability layer: typed registry, flight recorder, trace
export, control-socket STATS/TRACE verbs, master fan-out, paxtop.

Unit half (no cluster): registry/recorder semantics incl. ring
wraparound and Chrome trace-event schema validity for ALL four
dispatch regimes. Integration half: one real 3-replica in-process
cluster driven through commits + an idle window, then observed end to
end — replica control socket, master fan-out, and tools/paxtop.py as
a genuine subprocess.
"""

from __future__ import annotations

import json
import socket
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from minpaxos_tpu.obs.metrics import Histogram, MetricsRegistry
from minpaxos_tpu.obs.recorder import (
    KIND_FULL,
    KIND_FUSED,
    KIND_IDLE_SKIP,
    KIND_NAMES,
    KIND_NARROW,
    FlightRecorder,
    chrome_trace,
    validate_chrome_trace,
)

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------- registry


def test_registry_counters_gauges_and_snapshot_isolation():
    reg = MetricsRegistry("r0")
    c = reg.counter("dispatches", "device round-trips")
    c.inc()
    c.inc(4)
    g = reg.gauge("committed")
    g.set(17)
    reg.fn_gauge("conns", lambda: 3)
    snap = reg.counters()
    assert snap == {"dispatches": 5, "committed": 17, "conns": 3}
    # snapshots are FRESH dicts: mutating one never touches the
    # registry, and later advances never mutate an old snapshot
    snap["dispatches"] = -1
    c.inc()
    assert reg.counters()["dispatches"] == 6
    assert snap["dispatches"] == -1
    # get-or-create returns the same underlying metric
    assert reg.counter("dispatches") is c


def test_registry_full_snapshot_shape_is_json_serializable():
    reg = MetricsRegistry("r1")
    reg.counter("ticks").inc(2)
    reg.histogram("tick_wall_ms").observe(0.7)
    snap = reg.snapshot()
    assert snap["namespace"] == "r1"
    assert snap["counters"]["ticks"] == 2
    h = snap["histograms"]["tick_wall_ms"]
    assert h["count"] == 1 and len(h["counts"]) == len(h["bounds"]) + 1
    json.dumps(snap)  # the control plane ships this as JSON lines


def test_histogram_percentiles_and_bad_bounds():
    h = Histogram("t", bounds=(1.0, 2.0, 4.0, 8.0))
    for v in [0.5] * 50 + [3.0] * 49 + [100.0]:  # overflow observed
        h.observe(v)
    assert h.total == 100 and h.counts[-1] == 1
    assert 0.0 < h.percentile(0.5) <= 1.0
    assert h.percentile(0.99) >= 2.0
    assert h.percentile(1.0) <= 8.0  # overflow clamps to the last edge
    with pytest.raises(ValueError):
        Histogram("bad", bounds=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("empty", bounds=())


# ---------------------------------------------------------- recorder


def test_recorder_ring_wraparound_keeps_newest_in_order():
    rec = FlightRecorder(8)
    for i in range(20):
        rec.record(1000 * i, KIND_FULL, 1, i, 0, i, 0, 1, 2, 3, 0, 4, 5, 6)
    assert rec.total == 20
    snap = rec.snapshot()
    assert snap.shape == (8, 39)  # schema v9: + sub-phases, per-phase cpu
    # newest 8 rows, oldest-first (timestamps strictly increasing)
    np.testing.assert_array_equal(snap[:, 0],
                                  [1000 * i for i in range(12, 20)])
    assert (np.diff(snap[:, 0]) > 0).all()
    # `last` bounds the copy further
    assert len(rec.snapshot(last=3)) == 3
    np.testing.assert_array_equal(rec.snapshot(last=3)[:, 3], [17, 18, 19])
    with pytest.raises(ValueError):
        FlightRecorder(0)


def test_trace_export_all_four_regimes_validates():
    rec = FlightRecorder(64)
    t = 5_000_000_000
    for i, kind in enumerate([KIND_FULL, KIND_FUSED, KIND_NARROW,
                              KIND_IDLE_SKIP] * 4):
        t += 2_000_000
        # pipelined rows (every other) carry a hidden host wall
        rec.record(t, kind, 3 if kind == KIND_FUSED else 1, 8, 12,
                   100 + i, 2, 15, 30, 700, 250 if i % 2 else 0,
                   120, 90, 40)
    events = rec.to_events(pid=2)
    trace = chrome_trace(events)
    assert validate_chrome_trace(trace) == []
    json.dumps(trace)  # loadable = serializable first
    ticks = [e for e in events if e.get("cat") == "tick"]
    assert {e["args"]["kind"] for e in ticks} == set(KIND_NAMES)
    assert all(e["pid"] == 2 for e in events)
    # per-phase children exist for device ticks, not for idle skips
    # (schema v2: the blocking step_us is gone; the dispatch splits
    # into enqueue + readback, and the hidden host wall rides
    # overlap_us on the tick args + its own counter track)
    names = {e["name"] for e in events}
    assert {"enqueue", "readback", "persist", "dispatch", "reply"} <= names
    assert "device_step" not in names and "step_us" not in names
    assert {e["args"]["overlap_us"] for e in ticks} == {0, 250}
    # two-track rendering: dispatch phases on tid 0, host phases on
    # tid 1 (a deferred tick's host work then renders under the next
    # tick's dispatch slice instead of overlapping it on one track)
    phase_tid = {e["name"]: e["tid"] for e in events
                 if e.get("cat") == "phase"}
    assert phase_tid["enqueue"] == 0 and phase_tid["readback"] == 0
    assert phase_tid["persist"] == 1 and phase_tid["reply"] == 1
    skips = [e for e in ticks if e["args"]["kind"] == "idle_skip"]
    assert skips and all(e["args"]["k"] == 1 for e in ticks
                         if e["args"]["kind"] == "full")
    # counter events carry numeric args (what Perfetto graphs);
    # overlap_us is one of the counter tracks
    cs = [e for e in events if e["ph"] == "C"]
    assert cs and all(isinstance(v, int) for e in cs
                      for v in e["args"].values())
    assert any(e["name"] == "overlap_us" for e in cs)


def test_trace_schema_version_stamped_and_checked():
    """chrome_trace stamps the ring-layout revision; a trace from a
    different layout must fail validation instead of silently
    mislabeling phases in a viewer."""
    from minpaxos_tpu.obs.recorder import SCHEMA_VERSION

    tr = chrome_trace([])
    assert tr["otherData"]["paxmonSchemaVersion"] == SCHEMA_VERSION == 9
    assert validate_chrome_trace(tr) == []
    stale = chrome_trace([])
    stale["otherData"]["paxmonSchemaVersion"] = 4
    errs = validate_chrome_trace(stale)
    assert errs and "schema version mismatch" in errs[0]
    # traces without the stamp (e.g. hand-built fixtures) still pass
    assert validate_chrome_trace({"traceEvents": []}) == []


def test_trace_validator_rejects_malformed_events():
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 1.0, "pid": 0, "tid": 0},  # no dur
        {"name": "", "ph": "X", "ts": 1.0, "dur": 1, "pid": 0, "tid": 0},
        {"name": "c", "ph": "C", "ts": 1.0, "pid": 0, "tid": 0,
         "args": {"v": "NaN-ish string"}},
        {"name": "y", "ph": "??", "ts": 1.0},
    ]}
    errs = validate_chrome_trace(bad)
    assert len(errs) == 4, errs
    assert validate_chrome_trace([]) and validate_chrome_trace({})


# ---------------------------------------------------------------- dlog


def test_dlog_prefix_and_monotonic_deltas(capsys):
    import importlib

    # utils/__init__ re-exports the dlog FUNCTION under the module's
    # name; fetch the module itself
    dmod = importlib.import_module("minpaxos_tpu.utils.dlog")
    dmod.set_dlog_id("r7")
    try:
        dmod._dlog_enabled("hello %d", 42)
        dmod._dlog_enabled("again")
        err = capsys.readouterr().err
    finally:
        dmod.set_dlog_id("")
    lines = [ln for ln in err.splitlines() if ln.startswith("[dlog")]
    assert len(lines) == 2
    assert all(" r7 " in ln for ln in lines), lines
    assert "hello 42" in lines[0]
    # second line carries the delta since the first (+X.XXXms)
    assert "+" in lines[1].split("]")[0] and "ms]" in lines[1]
    # the disabled binding stays a bound no-op
    dmod._dlog_disabled("never %s", "printed")


# ----------------------------------------------- cluster integration


def _ctl(addr: tuple[str, int], req: dict) -> dict:
    """One control-socket round trip (the real TCP path paxtop uses)."""
    from minpaxos_tpu.utils.netutil import CONTROL_OFFSET

    host, port = addr
    with socket.create_connection((host, port + CONTROL_OFFSET),
                                  timeout=10) as s:
        f = s.makefile("rw")
        f.write(json.dumps(req) + "\n")
        f.flush()
        return json.loads(f.readline())


def test_stats_trace_verbs_master_fanout_and_paxtop(tmp_path):
    """End to end against a live 3-replica cluster: STATS/TRACE over
    the replica control socket, the master's cluster-wide fan-out,
    and tools/paxtop.py --once --json as a real subprocess. exec_batch
    is squeezed so commit backlogs force fused dispatches; a quiet
    window afterwards accumulates idle skips — both regimes must show
    up in the flight-recorder trace alongside full steps."""
    from test_distributed import Harness

    from minpaxos_tpu.runtime.client import gen_workload
    from minpaxos_tpu.runtime.master import cluster_stats, cluster_trace

    h = Harness(tmp_path, cfg_overrides=dict(exec_batch=16))
    try:
        cli = h.client()
        ops, keys, vals = gen_workload(400, seed=5)
        stats = cli.run_workload(ops, keys, vals, timeout_s=60)
        assert stats["acked"] == 400, stats
        # client-side paxmon rides the driver stats into bench records
        assert stats["client_metrics"]["proposed_rows"] >= 400
        cli.close_conn()

        # the old bug, pinned: `stats` is a snapshot, not the live dict
        s1 = h.servers[0].stats
        s1["dispatches"] = -999
        assert h.servers[0].stats["dispatches"] != -999

        # quiet window: the idle fast path must record skips
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if all(s.stats["idle_skips"] > 0 for s in h.servers.values()):
                break
            time.sleep(0.1)

        # STATS verb: typed snapshot + published scalar vector
        r = _ctl(h.addrs[0], {"m": "stats"})
        assert r["ok"] and r["id"] == 0 and r["protocol"] == "minpaxos"
        cnt = r["metrics"]["counters"]
        assert cnt["dispatches"] > 0 and cnt["proposals"] >= 400
        assert cnt["full_steps"] + cnt["fused_dispatches"] + \
            cnt["narrow_steps"] == cnt["dispatches"]
        assert r["metrics"]["gauges"]["committed"] >= 400
        assert r["metrics"]["gauges"]["net_frames_in"] > 0  # transport
        assert r["metrics"]["histograms"]["tick_wall_ms"]["count"] > 0
        assert r["scalars"]["frontier"] == r["frontier"]
        assert r["scalars"]["work_pending"] in (0, 1)

        # squeezed exec_batch guarantees backlog fusion somewhere
        fused = [s.stats["fused_dispatches"] for s in h.servers.values()]
        assert any(f > 0 for f in fused), fused

        # TRACE verb: schema-valid, regimes visible
        rid = max(h.servers, key=lambda i: h.servers[i].stats[
            "fused_dispatches"])
        tr = _ctl(h.addrs[rid], {"m": "trace", "last": 4096})
        assert tr["ok"] and tr["recorder"]
        trace = chrome_trace(tr["events"])
        assert validate_chrome_trace(trace) == []
        kinds = {e["args"]["kind"] for e in tr["events"]
                 if e.get("cat") == "tick"}
        assert {"full", "fused", "idle_skip"} <= kinds, kinds

        # master fan-out: one RPC, all replicas
        maddr = ("127.0.0.1", h.mport)
        ms = cluster_stats(maddr)
        assert ms["ok"] and len(ms["replicas"]) == 3
        assert all(rr["ok"] for rr in ms["replicas"]), ms["replicas"]
        assert {rr["id"] for rr in ms["replicas"]} == {0, 1, 2}
        mt = cluster_trace(maddr, last=256)
        assert validate_chrome_trace(mt["trace"]) == []
        from minpaxos_tpu.obs.recorder import WATCH_PID

        pids = {e["pid"] for e in mt["trace"]["traceEvents"]}
        assert pids == {0, 1, 2, WATCH_PID}, pids

        # paxwatch EVENTS fan-out (live cluster): replica 0 journaled
        # its boot election, every replica its peer-link installs, and
        # the collections carry the clock anchors the offline merge
        # aligns by — and the merged v6 trace above already carried
        # the journals as instant events on the reserved pid
        from minpaxos_tpu.obs import watch as W
        from minpaxos_tpu.runtime.master import cluster_events

        ev = cluster_events(maddr)
        assert ev["ok"] and len(ev["replicas"]) == 3
        assert all(rr["ok"] and rr["journal"]["anchor"]["mono_ns"] > 0
                   for rr in ev["replicas"]), ev["replicas"]
        rows = W.align_event_collections(
            [rr["journal"] for rr in ev["replicas"]])
        kinds = set(rows[:, W.EV_KIND].tolist())
        assert W.EV_ELECTION in kinds and W.EV_PEER_UP in kinds, kinds
        j0 = [rr for rr in ev["replicas"] if rr["id"] == 0][0]["journal"]
        r0 = np.asarray(j0["events"], np.int64)
        elecs = r0[r0[:, W.EV_KIND] == W.EV_ELECTION]
        assert len(elecs) >= 1 and int(elecs[0][W.EV_SUBJECT]) == 0
        # the journal total rides stats as an fn-gauge (paxtop's feed)
        assert cnt is not None  # (STATS leg above)
        st0 = _ctl(h.addrs[0], {"m": "stats"})
        assert st0["metrics"]["gauges"]["events"] >= j0["total"] > 0
        wevs = [e for e in mt["trace"]["traceEvents"]
                if e.get("cat") == "paxwatch"]
        assert wevs and all(e["pid"] == WATCH_PID and e["ph"] == "i"
                            for e in wevs)

        # the shipped live view, as a subprocess (no jax import there)
        out = subprocess.run(
            [sys.executable, str(REPO / "tools/paxtop.py"),
             "-mport", str(h.mport), "--once", "--json"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        payload = json.loads(out.stdout)
        rows = payload["derived"]
        assert len(rows) == 3 and all(rw["ok"] for rw in rows)
        lead = [rw for rw in rows if rw["role"] == "leader"]
        assert len(lead) == 1 and lead[0]["frontier"] >= 399
        assert all(rw["tick_p50_ms"] > 0 for rw in rows)

        # paxtop -dump-trace writes a Perfetto-loadable file
        tf = tmp_path / "cluster_trace.json"
        out = subprocess.run(
            [sys.executable, str(REPO / "tools/paxtop.py"),
             "-mport", str(h.mport), "-dump-trace", str(tf),
             "-last", "128"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert validate_chrome_trace(json.loads(tf.read_text())) == []
    finally:
        h.stop()


def test_norecorder_flag_disables_trace_not_stats(tmp_path):
    """RuntimeFlags(recorder=False) (the server's -norecorder A/B
    knob): TRACE answers empty-but-ok, STATS keeps full metrics."""
    from test_distributed import Harness

    from minpaxos_tpu.runtime.client import gen_workload

    h = Harness(tmp_path, n=1,
                flags_overrides={0: {"recorder": False}})
    try:
        cli = h.client()
        ops, keys, vals = gen_workload(50, seed=9)
        assert cli.run_workload(ops, keys, vals,
                                timeout_s=60)["acked"] == 50
        cli.close_conn()
        assert h.servers[0].recorder is None
        tr = _ctl(h.addrs[0], {"m": "trace"})
        assert tr["ok"] and tr["recorder"] is False and tr["events"] == []
        st = _ctl(h.addrs[0], {"m": "stats"})
        assert st["ok"] and st["metrics"]["counters"]["dispatches"] > 0
    finally:
        h.stop()
