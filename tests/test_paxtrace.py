"""paxtrace: per-command distributed tracing (obs/trace.py).

Unit half: context frame round-trip + v1 wire compat, deterministic
cross-process sampling agreement, span-ring wraparound, schema-v5
validator pins in both directions, clock-anchor monotonicity and the
stage-decomposition math. Integration half: a live 3-replica cluster
traced end to end — TRACESPANS replica verb + master fan-out + a
complete client -> replica -> commit -> reply span chain whose stage
sum equals the measured end-to-end latency, and tools/tail.py as a
real subprocess.
"""

from __future__ import annotations

import json
import socket
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

from minpaxos_tpu.obs import trace as T
from minpaxos_tpu.obs.recorder import (
    DEVICE_PID,
    SCHEMA_VERSION,
    TRACE_PID,
    chrome_trace,
    validate_chrome_trace,
)
from minpaxos_tpu.wire.codec import StreamDecoder, decode_frame, encode_frame
from minpaxos_tpu.wire.messages import MsgKind, make_batch

REPO = Path(__file__).resolve().parents[1]


# ------------------------------------------------------- wire context


def test_trace_ctx_frame_roundtrip():
    ids = np.arange(5, dtype=np.int32) * 7
    frame = make_batch(MsgKind.TRACE_CTX, cmd_id=ids,
                       trace_id=T.trace_id_for(ids.astype(np.int64)),
                       origin_wall_ns=987_654_321_000)
    buf = encode_frame(MsgKind.TRACE_CTX, frame)
    kind, rows, end = decode_frame(buf)
    assert kind == MsgKind.TRACE_CTX and end == len(buf)
    np.testing.assert_array_equal(rows["cmd_id"], ids)
    np.testing.assert_array_equal(rows["trace_id"], frame["trace_id"])
    assert (rows["origin_wall_ns"] == 987_654_321_000).all()
    # the ledger entry matches the live schema (append-only contract)
    from minpaxos_tpu.analysis.wire_golden import GOLDEN_KINDS

    val, size = GOLDEN_KINDS["TRACE_CTX"]
    assert val == int(MsgKind.TRACE_CTX)
    assert size == rows.dtype.itemsize == 20


def test_v1_frames_still_parse_and_disabled_tracing_is_transparent():
    """Old peers: a stream WITHOUT ctx frames (v1 client, or tracing
    off) decodes exactly as before; a v2 stream interleaving ctx
    frames decodes both kinds in order. A decoder that doesn't know
    TRACE_CTX (a v1 peer) never sees one when tracing is off — pinned
    by byte equality of the tracing-off propose path."""
    prop = make_batch(MsgKind.PROPOSE, cmd_id=np.arange(3, dtype=np.int32),
                      op=1, key=np.arange(3), val=7, timestamp=9)
    v1_stream = encode_frame(MsgKind.PROPOSE, prop)
    dec = StreamDecoder()
    frames = dec.feed(v1_stream)
    assert [k for k, _ in frames] == [MsgKind.PROPOSE]

    # v2 stream: ctx frame ahead of the propose, same connection
    ctx = make_batch(MsgKind.TRACE_CTX, cmd_id=np.int32(1),
                     trace_id=T.trace_id_for(1), origin_wall_ns=5)
    dec2 = StreamDecoder()
    frames2 = dec2.feed(encode_frame(MsgKind.TRACE_CTX, ctx) + v1_stream)
    assert [k for k, _ in frames2] == [MsgKind.TRACE_CTX, MsgKind.PROPOSE]

    # tracing disabled writes ONLY the propose frame (byte-transparent)
    from minpaxos_tpu.runtime.client import Client

    class _CapSock:
        def __init__(self):
            self.data = b""

        def sendall(self, b):
            self.data += b

    cli = Client.__new__(Client)  # no network: exercise propose() only
    cli.trace = None
    cli.metrics = None
    from minpaxos_tpu.obs.metrics import MetricsRegistry
    from minpaxos_tpu.wire.codec import FrameWriter

    cli._c_proposed = MetricsRegistry("t").counter("proposed_rows")
    off_sock = _CapSock()
    cli.writer = FrameWriter(off_sock)
    cli.propose([1], [1], [42], [7])
    k0, rows0, _ = decode_frame(off_sock.data)
    assert k0 == MsgKind.PROPOSE and len(off_sock.data) == \
        5 + rows0.dtype.itemsize  # header + one row, nothing else

    # tracing on (pow2=0): ctx frame precedes the propose
    cli.trace = T.TraceSink(enabled=True, sample_pow2=0)
    on_sock = _CapSock()
    cli.writer = FrameWriter(on_sock)
    cli.propose([1], [1], [42], [7])
    k1, rows1, end = decode_frame(on_sock.data)
    assert k1 == MsgKind.TRACE_CTX
    assert int(rows1["trace_id"][0]) == T.trace_id_for(1)
    k2, _, _ = decode_frame(on_sock.data, end)
    assert k2 == MsgKind.PROPOSE


# ---------------------------------------------------------- sampling


def test_sampling_deterministic_and_scalar_vector_agree():
    ids = np.arange(-512, 4096, dtype=np.int64)
    for pow2 in (0, 1, 4, 8):
        m = T.sampled_mask(ids, pow2)
        scal = np.array([T.is_sampled(int(i), pow2) for i in ids])
        np.testing.assert_array_equal(m, scal)
        # rate is roughly 1-in-2^k (deterministic, not random — just
        # sanity that the hash spreads)
        if pow2:
            assert 0.3 / 2 ** pow2 < m.mean() < 3.0 / 2 ** pow2
        else:
            assert m.all()
    # trace ids: nonzero, scalar == vectorized
    tids = T.trace_id_for(ids)
    assert (tids != 0).all()
    assert int(tids[0]) == T.trace_id_for(int(ids[0]))
    assert T.mix64_scalar(12345) == int(T.mix64(12345))


def test_sampling_agreement_across_processes():
    """The distributed contract: a SEPARATE python process computes the
    identical sample set and trace ids for the same command ids — no
    coordination, no shared state."""
    code = textwrap.dedent("""
        import sys, json, numpy as np
        sys.path.insert(0, %r)
        from minpaxos_tpu.obs import trace as T
        ids = np.arange(2000, dtype=np.int64)
        m = T.sampled_mask(ids, 4)
        print(json.dumps({
            "sampled": np.nonzero(m)[0].tolist(),
            "tids": T.trace_id_for(ids[m]).tolist()}))
    """) % str(REPO)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout)
    ids = np.arange(2000, dtype=np.int64)
    m = T.sampled_mask(ids, 4)
    assert got["sampled"] == np.nonzero(m)[0].tolist()
    assert got["tids"] == T.trace_id_for(ids[m]).tolist()


# ---------------------------------------------------------- span rings


def test_span_ring_wraparound_keeps_newest():
    r = T.SpanRing(8)
    for i in range(20):
        r.record(100 + i, T.ST_DRAIN, 1000 * i, 1000 * i + 1, i)
    assert r.total == 20 and r.dropped == 12
    snap = r.snapshot()
    assert snap.shape == (8, T.N_SPAN_FIELDS)
    np.testing.assert_array_equal(snap[:, T.SP_TRACE],
                                  [100 + i for i in range(12, 20)])
    assert (np.diff(snap[:, T.SP_T0]) > 0).all()
    with pytest.raises(ValueError):
        T.SpanRing(0)


def test_sink_per_thread_rings_and_collect():
    import threading

    sink = T.TraceSink(enabled=True, sample_pow2=0, ring_capacity=16)
    sink.stamp(T.ST_DRAIN, 1, 10, 10)

    def other():
        sink.stamp(T.ST_EXEC, 1, 20, 20)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    assert len(sink._rings) == 2  # one ring per writer thread
    # a NEW thread adopts the dead thread's ring instead of leaking a
    # fresh one (transport churns a reader thread per client
    # connection — an append-only registry would grow forever)
    t2 = threading.Thread(target=lambda: sink.stamp(T.ST_EXEC, 2, 30, 30))
    t2.start()
    t2.join()
    assert len(sink._rings) == 2
    c = sink.collect()
    assert c["total"] == 3 and c["dropped"] == 0
    assert {row[T.SP_STAGE] for row in c["spans"]} == {T.ST_DRAIN,
                                                       T.ST_EXEC}
    assert c["anchor"]["mono_ns"] > 0 and c["anchor"]["wall_ns"] > 0
    json.dumps(c)  # the TRACESPANS verb ships this as JSON


def test_clock_anchor_monotonicity_and_alignment():
    a1 = T.clock_anchor()
    time.sleep(0.002)
    a2 = T.clock_anchor()
    assert a2["mono_ns"] > a1["mono_ns"]
    assert a2["wall_ns"] >= a1["wall_ns"]
    # alignment: a collection whose clock runs 5 s "behind" (smaller
    # mono for the same wall) lands its spans 5 s later in the
    # reference domain — the wall anchors are the bridge
    ref = {"mono_ns": 1_000, "wall_ns": 10_000}
    skew = {"mono_ns": 1_000 - 5_000_000_000,
            "wall_ns": 10_000}
    spans = [[7, T.ST_DRAIN, 100 - 5_000_000_000,
              100 - 5_000_000_000, 0]]
    out = T.align_collections(
        [{"anchor": skew, "spans": spans}], ref_anchor=ref)
    assert out[0][T.SP_T0] == 100
    # empty collections survive
    assert len(T.align_collections([{"anchor": ref, "spans": []}])) == 0


# ------------------------------------------------- decomposition math


def _chain(cmd, t0, commit_ms=2.0, exec_ms=0.5, out_ms=1.0):
    tid = T.trace_id_for(cmd)
    ns = lambda ms: int(ms * 1e6)  # noqa: E731
    return [
        (tid, T.ST_SEND, t0, t0 + ns(0.1), cmd),
        (tid, T.ST_DECODE, t0 + ns(0.3), t0 + ns(0.4), cmd),
        (tid, T.ST_DRAIN, t0 + ns(0.9), t0 + ns(0.9), 10),
        (tid, T.ST_COMMIT, t0 + ns(0.9 + commit_ms),
         t0 + ns(0.9 + commit_ms), 5),
        (tid, T.ST_EXEC, t0 + ns(0.9 + commit_ms + exec_ms),
         t0 + ns(0.9 + commit_ms + exec_ms), 12),
        (tid, T.ST_REPLY_SER, t0 + ns(0.9 + commit_ms + exec_ms),
         t0 + ns(1.0 + commit_ms + exec_ms), cmd),
        (tid, T.ST_REPLY_RECV, t0 + ns(1.0 + commit_ms + exec_ms + out_ms),
         t0 + ns(1.0 + commit_ms + exec_ms + out_ms), cmd),
    ]


def test_stage_decomposition_sums_to_end_to_end():
    spans = np.array(_chain(1, 10**9) + _chain(2, 2 * 10**9, commit_ms=40.0),
                     np.int64)
    chains = T.span_chains(spans)
    decomp = T.stage_decomposition(chains)
    assert len(decomp) == 2
    for d in decomp:
        assert abs(sum(d["stages"].values()) - d["total_ms"]) < 1e-9
    tab = T.stage_table(decomp)
    assert tab["n_traced"] == 2
    assert tab["tail"]["worst_stage"] == "commit"
    assert "commit" in T.format_stage_table(tab)
    # round correlation: exec aux - drain aux = dispatches to commit
    assert all(d["commit_dispatches"] == 2 for d in decomp)
    # incomplete chains (no commit) are excluded, not crashed on
    partial = np.array(_chain(3, 10**9)[:2], np.int64)
    assert T.stage_decomposition(T.span_chains(partial)) == []
    # duplicate-stage resolution: a commit span from a NEWER life of a
    # reused cmd_id (43 ms, after this chain's exec at 3.4 ms) must
    # not splice into an impossible chain — the backwards walk keeps
    # the consistent 2.0 ms-commit life and the table stays sane
    rows = _chain(4, 10**9)
    tid4 = T.trace_id_for(4)
    ns = lambda ms: int(ms * 1e6)  # noqa: E731
    rows.append((tid4, T.ST_COMMIT, 10**9 + ns(43.0), 10**9 + ns(43.0), 5))
    mixed = T.stage_decomposition(T.span_chains(np.array(rows, np.int64)))
    assert len(mixed) == 1
    assert abs(mixed[0]["stages"]["commit"] - 2.0) < 1e-9
    # a deduped retry: the client re-stamps SEND/DECODE 3 s later but
    # the server admitted the FIRST attempt — the walk recovers the
    # first-attempt start, so the slow command keeps its true latency
    rows2 = _chain(5, 10**9)
    tid5 = T.trace_id_for(5)
    rows2.append((tid5, T.ST_SEND, 10**9 + ns(3000.0),
                  10**9 + ns(3000.1), 5))
    rows2.append((tid5, T.ST_DECODE, 10**9 + ns(3000.3),
                  10**9 + ns(3000.4), 5))
    retry = T.stage_decomposition(T.span_chains(np.array(rows2, np.int64)))
    assert len(retry) == 1
    assert abs(retry[0]["total_ms"] - 4.5) < 1e-9  # first-send anchored


def test_schema_v5_pins_both_directions():
    """Current-schema readers reject older-stamped traces; paxtrace
    events must ride the reserved pid (and nothing else may squat on
    it). (v6 bumped the stamp for paxwatch event tracks; the paxtrace
    pid reservation is unchanged.)"""
    assert SCHEMA_VERSION == 9
    spans = np.array(_chain(1, 10**9), np.int64)
    chains = T.span_chains(spans)
    decomp = T.stage_decomposition(chains)
    events = T.span_events(decomp, chains)
    assert events and all(e["pid"] == TRACE_PID for e in events)
    assert all(e["args"]["trace_id"] == decomp[0]["trace_id"]
               for e in events)
    tr = chrome_trace(events)
    assert validate_chrome_trace(tr) == []
    # older-stamped file fails against the current reader
    stale = chrome_trace(events)
    stale["otherData"]["paxmonSchemaVersion"] = 4
    errs = validate_chrome_trace(stale)
    assert errs and "mismatch" in errs[0]
    # a paxtrace event off the reserved pid fails
    bad = chrome_trace([dict(events[0], pid=3)])
    assert any("reserved pid" in e for e in validate_chrome_trace(bad))
    # a non-paxtrace event squatting on TRACE_PID fails
    squat = chrome_trace([{"name": "tick:full", "cat": "tick", "ph": "X",
                           "ts": 1.0, "dur": 1, "pid": TRACE_PID,
                           "tid": 0}])
    assert any("reserved for paxtrace" in e
               for e in validate_chrome_trace(squat))
    # device-pid reservation from v4 still enforced alongside
    dev_bad = chrome_trace([{"name": "device_frontier", "ph": "C",
                             "ts": 1.0, "pid": 1, "tid": 0,
                             "args": {"device_frontier": 1}}])
    assert any(str(DEVICE_PID) in e for e in validate_chrome_trace(dev_bad))


# ----------------------------------------------- cluster integration


def _ctl(addr, req):
    from minpaxos_tpu.utils.netutil import CONTROL_OFFSET

    host, port = addr
    with socket.create_connection((host, port + CONTROL_OFFSET),
                                  timeout=10) as s:
        f = s.makefile("rw")
        f.write(json.dumps(req) + "\n")
        f.flush()
        return json.loads(f.readline())


@pytest.mark.slow  # ~13 s cluster boot; tier-1's 870 s budget is
# within noise of the suite wall (PR 8 precedent) — the stage math,
# wire compat and v5 pins above stay tier-1, and obs_smoke gates the
# tail/TRACESPANS path against a control-plane stub every build
def test_live_cluster_tracespans_and_end_to_end_chain(tmp_path):
    """The tentpole, end to end: every op traced (pow2=0) on a live
    3-replica cluster; the TRACESPANS verb + master fan-out collect
    span rings cluster-wide; merged with the client's own spans, at
    least one command has a COMPLETE chain (send -> decode -> drain ->
    commit -> exec -> reply_ser -> reply_recv) whose stage sum equals
    its end-to-end latency; and tools/tail.py (a real subprocess, no
    JAX) prints the stage table from the same cluster."""
    from test_distributed import Harness

    from minpaxos_tpu.runtime.client import Client, gen_workload
    from minpaxos_tpu.runtime.master import cluster_tracespans

    h = Harness(tmp_path,
                flags_overrides={i: {"trace_pow2": 0} for i in range(3)})
    try:
        cli = Client(("127.0.0.1", h.mport), check=True, trace_pow2=0)
        ops, keys, vals = gen_workload(120, seed=11)
        stats = cli.run_workload(ops, keys, vals, timeout_s=60)
        assert stats["acked"] == 120, stats

        # replica-level verb
        r = _ctl(h.addrs[0], {"m": "tracespans"})
        assert r["ok"] and r["trace"]["enabled"]
        assert r["trace"]["sample_pow2"] == 0
        assert r["trace"]["total"] > 0
        assert r["trace"]["anchor"]["mono_ns"] > 0

        # trace counters ride the stats snapshot (paxtop TRACE
        # column); the gauge is read later than the verb's snapshot,
        # so it may only have grown
        st = _ctl(h.addrs[0], {"m": "stats"})
        assert st["metrics"]["gauges"]["trace_spans"] >= r["trace"]["total"]

        # master fan-out + client merge -> complete chains
        resp = cluster_tracespans(("127.0.0.1", h.mport))
        assert resp["ok"] and len(resp["replicas"]) == 3
        colls = [rr["trace"] for rr in resp["replicas"] if rr.get("ok")]
        assert len(colls) == 3
        colls.append(cli.trace_collect())
        chains = T.span_chains(T.align_collections(colls))
        decomp = T.stage_decomposition(chains)
        assert len(decomp) >= 100, len(decomp)  # nearly all 120 traced
        for d in decomp:
            assert abs(sum(d["stages"].values()) - d["total_ms"]) < 1e-9
            assert d["total_ms"] > 0
            # client-side receipt present => transport_out measured
            assert d["stages"]["transport_out"] >= 0
        tab = T.stage_table(decomp)
        assert tab["n_traced"] == len(decomp)
        assert tab["tail"]["worst_stage"] in T.DECOMP_STAGES

        # the shipped tool against the live cluster (no client spans:
        # chains still complete via the ctx ORIGIN echo)
        out = subprocess.run(
            [sys.executable, str(REPO / "tools/tail.py"),
             "-mport", str(h.mport), "--once", "--json"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        payload = json.loads(out.stdout)
        assert payload["stage_table"]["n_traced"] >= 100
        # cluster-only chains end at reply serialization
        assert all(d["stages"]["transport_out"] == 0
                   for d in payload["per_trace"])

        # tail -dump-trace merges a valid v5 file: recorder ticks from
        # replica pids + command spans on the reserved pid
        tf = tmp_path / "tail_trace.json"
        out = subprocess.run(
            [sys.executable, str(REPO / "tools/tail.py"),
             "-mport", str(h.mport), "-dump-trace", str(tf),
             "-last", "256"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        merged = json.loads(tf.read_text())
        assert validate_chrome_trace(merged) == []
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert TRACE_PID in pids and {0, 1, 2} <= pids
        cli.close_conn()
    finally:
        h.stop()


@pytest.mark.slow  # see the budget note above
def test_notrace_flag_is_silent_and_cheap(tmp_path):
    """trace=False: no spans collected, TRACESPANS answers empty-but-
    ok, and the client sends no ctx frames (wire transparency at the
    server: proposals are admitted exactly as before)."""
    from test_distributed import Harness

    from minpaxos_tpu.runtime.client import gen_workload

    h = Harness(tmp_path, n=1, flags_overrides={0: {"trace": False}})
    try:
        cli = h.client()
        ops, keys, vals = gen_workload(40, seed=2)
        assert cli.run_workload(ops, keys, vals,
                                timeout_s=60)["acked"] == 40
        cli.close_conn()
        r = _ctl(h.addrs[0], {"m": "tracespans"})
        assert r["ok"] and r["trace"]["enabled"] is False
        assert r["trace"]["total"] == 0
        assert h.servers[0].stats["trace_spans"] == 0
    finally:
        h.stop()
