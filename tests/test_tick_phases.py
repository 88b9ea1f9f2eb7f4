"""The tick loop's phases on two clocks (obs/recorder.py ``phase``,
recorder schema v8), the ``px.*`` kernel scopes, the stable store's own
counters, the protocol thread's span ring and ``obs.process_collection``.

One three-replica durable cluster, in this process, is driven under a
JAX profile once (module fixture); the tests read what it left behind.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import subprocess
import sys
import threading
from unittest import mock

import jax
import numpy as np
import pytest

from minpaxos_tpu import obs
from minpaxos_tpu.obs import recorder as R
from minpaxos_tpu.obs.trace import (
    ST_COMMIT,
    ST_DRAIN,
    TraceSink,
    protocol_ring_capacity,
    span_chains,
)

TICK_SPANS = (R.PH_WAIT, R.PH_DRAIN, R.PH_ENQUEUE, R.PH_READBACK,
              R.PH_PERSIST, R.PH_FSYNC, R.PH_EGRESS, R.PH_REPLY)
STEP_SCOPES = (
    "px.prepare", "px.phase1_reply", "px.accept", "px.slot_write_a",
    "px.accept_ack", "px.prepare_inst", "px.commit_rows",
    "px.prepare_reply", "px.propose", "px.slot_write_b", "px.vote_count",
    "px.commit_scan", "px.gossip", "px.catchup", "px.retry", "px.sweep",
    "px.outbox", "px.exec", "px.kv.sort", "px.kv.scan", "px.kv.lookup",
    "px.kv.output", "px.kv.insert", "px.window_slide", "px.pack")
POD_SCOPES = ("px.deliver", "px.route.plan", "px.route.gather",
              "px.workload", "px.lat_hist", "px.telemetry",
              "px.state_transfer")
COL = {name: i for i, name in enumerate(R.FIELD_NAMES)}
PHASES = ("wait_us", "drain_us", "enqueue_us", "readback_us", "persist_us",
          "dispatch_us", "reply_us")


@pytest.fixture(scope="module")
def profiled_cluster(tmp_path_factory):
    """What a durable three-replica cluster left behind after 3,000
    PUTs under a profile: the xplane's path, the process collection
    taken AFTER ``stop()``, and how many ``os.fsync`` calls a wrapper
    counted per store file."""
    from minpaxos_tpu.chaos.campaign import ChaosCluster
    from minpaxos_tpu.runtime.client import Client
    from minpaxos_tpu.wire.messages import Op

    tmp = tmp_path_factory.mktemp("phases")
    real_fsync, calls = os.fsync, []

    def counting_fsync(fd):
        calls.append(os.fstat(fd).st_ino)
        real_fsync(fd)

    n = 3000
    rng = np.random.default_rng(7)
    (tmp / "store").mkdir()
    with mock.patch.object(os, "fsync", counting_fsync):
        cluster = ChaosCluster(n=3, store_dir=str(tmp / "store"),
                               durable=True)
        try:
            client = Client(cluster.maddr)
            client.connect()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(tmp / "prof"),
                                     profiler_options=opts)
            try:
                stats = client.run_workload(
                    np.full(n, int(Op.PUT), np.uint8),
                    rng.integers(0, 500, n).astype(np.int64),
                    rng.integers(1, 1 << 20, n).astype(np.int64), batch=48)
            finally:
                jax.profiler.stop_trace()
            client.close_conn()
            inodes = {i: os.stat(tmp / "store" / f"stable-store-replica{i}"
                                 ).st_ino for i in range(3)}
        finally:
            cluster.stop()
    assert stats["acked"] == n, stats
    mine = [e for e in obs.process_collection()
            if e["metrics"]["namespace"] in {f"replica{i}" for i in range(3)}
            ][-3:]
    (xplane,) = glob.glob(str(tmp / "prof/plugins/profile/*/*.xplane.pb"))
    return {"xplane": xplane, "collection": mine,
            "fsyncs": {i: calls.count(ino) for i, ino in inodes.items()}}


def _leader(collection):
    return max(collection, key=lambda e: int(
        (e["rows"][:, COL["coal_occ"]] > 0).sum()))


def test_every_tick_span_is_in_the_profile_with_fsync_inside_persist(
        profiled_cluster):
    data = jax.profiler.ProfileData.from_file(profiled_cluster["xplane"])
    by_line: dict = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("paxos.tick."):
                    by_line.setdefault((plane.name, line.name), []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    seen = {name for evs in by_line.values() for name, *_ in evs}
    assert seen == set(TICK_SPANS)
    assert all(plane.startswith("/host") for plane, _ in by_line)
    replicas, nested, fsyncs = set(), 0, 0
    for evs in by_line.values():
        persists = [(a, b) for name, a, b, _ in evs if name == R.PH_PERSIST]
        for name, a, b, stats in evs:
            replicas.add(stats["replica"])
            if name == R.PH_FSYNC:
                fsyncs += 1
                nested += any(pa <= a and b <= pb for pa, pb in persists)
    assert replicas == {0, 1, 2}
    # every fsync of a flush lies inside that tick's persist span
    assert fsyncs > 100 and nested == fsyncs


def test_recorder_v8_rows_close_on_a_loaded_leader(profiled_cluster):
    """wait + drain + enqueue + readback + persist + egress + reply add
    up to the wall the rows span: the phases tile the protocol thread's
    time, so nothing a tick costs hides between them."""
    rows = _leader(profiled_cluster["collection"])["rows"]
    assert rows.shape[1] == R.N_FIELDS == 22 and R.SCHEMA_VERSION == 8
    loaded = np.nonzero(rows[:, COL["coal_occ"]] > 0)[0]
    assert len(loaded) >= 50
    first, last = loaded[0], loaded[-1]
    span = rows[first + 1:last + 1]
    wall_us = (rows[last, COL["t_ns"]] - rows[first, COL["t_ns"]]) / 1e3
    phases_us = sum(int(span[:, COL[f]].sum()) for f in PHASES)
    assert abs(phases_us - wall_us) <= 0.05 * wall_us, (phases_us, wall_us)
    # the fsync is inside persist, the CPU time inside the wall
    assert (span[:, COL["fsync_us"]] <= span[:, COL["persist_us"]] + 1).all()
    assert 0 < span[:, COL["cpu_us"]].sum() <= wall_us
    flushed = span[:, COL["fsync_bytes"]]
    assert (flushed[span[:, COL["fsync_us"]] > 0] > 0).all()


def test_store_counters_match_a_wrapped_os_fsync(profiled_cluster):
    for entry in profiled_cluster["collection"]:
        counters = entry["metrics"]["counters"]
        assert counters["store_fsyncs"] == \
            profiled_cluster["fsyncs"][entry["replica"]] > 100
        assert counters["store_fsync_us"] > 0
        # every row's bytes add up to the counter, and the log holds them
        rows = entry["rows"]
        assert rows[:, COL["fsync_bytes"]].sum() \
            == counters["store_flushed_bytes"] > 3000 * 30


def test_store_counts_snapshot_fsyncs_and_bytes_of_the_log(tmp_path):
    from minpaxos_tpu.obs.metrics import MetricsRegistry
    from minpaxos_tpu.runtime.stable import MAGIC, StableStore

    real, calls = os.fsync, []
    with mock.patch.object(os, "fsync",
                           lambda fd: (calls.append(fd), real(fd))[1]):
        reg = MetricsRegistry("t")
        path = tmp_path / "stable-store-replica0"
        store = StableStore(str(path), sync=True, metrics=reg)
        for i in range(5):
            store.append_slots([i], [1], [4], [1], [i], [i], [i], [0])
            store.append_frontier(i)
            store.flush()
        size = os.path.getsize(path)
        store.take_snapshot([1, 2], [3, 4], frontier=4)
        store.close()
    c = reg.snapshot()["counters"]
    assert c["store_fsyncs"] == len(calls) == 5 + 2 + 1
    assert c["store_flushed_bytes"] == size - len(MAGIC)
    # an unsynced store counts nothing: nothing was made durable
    reg2 = MetricsRegistry("t2")
    loose = StableStore(str(tmp_path / "loose"), sync=False, metrics=reg2)
    loose.append_frontier(3)
    loose.flush()
    loose.close()
    assert reg2.snapshot()["counters"]["store_fsyncs"] == 0
    assert reg2.snapshot()["counters"]["store_flushed_bytes"] == 0


def test_collection_answers_after_stop_with_chains_and_dispatches(
        profiled_cluster):
    """The fixture's servers are stopped: the collection still holds
    their rows, spans and registry, and the leader's sampled commands
    resolve to chains whose commit stamp is a recorder row's t_rb_ns."""
    coll = profiled_cluster["collection"]
    assert [e["replica"] for e in coll] == [0, 1, 2]
    lead = _leader(coll)
    assert lead["rows_total"] == len(lead["rows"]) <= lead["rows_capacity"]
    spans = np.asarray(lead["spans"]["spans"], np.int64)
    chains = [c for c in span_chains(spans).values()
              if ST_DRAIN in c and ST_COMMIT in c]
    assert len(chains) > 100
    t_rb = set(lead["rows"][:, COL["t_rb_ns"]].tolist())
    assert all(c[ST_COMMIT][1] in t_rb for c in chains)


def test_process_collection_is_bounded_to_the_newest_sixteen():
    from minpaxos_tpu.obs.metrics import MetricsRegistry

    for i in range(40):
        obs.register_replica(100 + i, MetricsRegistry(f"r{100 + i}"),
                             R.FlightRecorder(4), TraceSink())
    coll = obs.process_collection()
    assert len(coll) == 16
    assert [e["replica"] for e in coll] == list(range(124, 140))
    assert coll[-1]["rows"].shape == (0, R.N_FIELDS)
    assert coll[-1]["metrics"]["namespace"] == "r139"


def test_phase_accumulates_nests_and_annotates_only_under_a_profile():
    clock = R.PhaseClock(5)
    with R.phase(R.PH_PERSIST, clock) as outer:
        with R.phase(R.PH_FSYNC, clock) as inner:
            pass
    assert 0 < inner.ns <= outer.ns
    assert clock.ns[R.PH_FSYNC] == inner.ns
    assert clock.take_us(R.PH_PERSIST) == outer.ns // 1000
    assert clock.ns[R.PH_PERSIST] == 0
    assert clock.cpu_us() == 0 and clock.cpu_us() >= 0
    assert R._annotate(R.PH_WAIT, 1) is None  # no profile is running
    with R.phase(R.PH_POD_DISPATCH):  # no clock: annotation only
        pass


def test_obs_phase_works_without_jax():
    code = (
        "import sys\n"
        "from minpaxos_tpu.obs import PhaseClock, phase, recorder\n"
        "c = PhaseClock(0)\n"
        "with phase(recorder.PH_WAIT, c): pass\n"
        "assert c.ns[recorder.PH_WAIT] > 0\n"
        "import minpaxos_tpu.runtime.stable\n"
        "assert 'jax' not in sys.modules, 'obs pulled JAX in'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], check=True, cwd=root,
                   timeout=60)


def test_v8_rows_render_wait_and_fsync_slices():
    rec = R.FlightRecorder(8)
    rec.record(9_000_000, R.KIND_FULL, 1, 4, 4, 10, 0, 50, 100, 300, 0,
               400, 200, 30, t_rb_ns=8_000_000, wait_us=700, fsync_us=250,
               fsync_bytes=999, cpu_us=600)
    events = rec.to_events(pid=1)
    assert R.validate_chrome_trace(R.chrome_trace(events)) == []
    by_name = {e["name"]: e for e in events if e.get("ph") == "X"}
    tick, wait, fsync, persist = (by_name[n] for n in
                                  ("tick:full", "wait", "fsync", "persist"))
    assert wait["ts"] + wait["dur"] == tick["ts"] and wait["dur"] == 700
    assert persist["ts"] <= fsync["ts"] and \
        fsync["ts"] + fsync["dur"] == persist["ts"] + persist["dur"]
    assert tick["args"]["fsync_bytes"] == 999
    assert tick["args"]["cpu_us"] == 600


def test_protocol_thread_gets_the_sized_ring_and_keeps_it():
    assert protocol_ring_capacity(2048, 4, 4096) == 32768  # the benchmark's
    assert protocol_ring_capacity(1 << 14, 4, 4096) == 1 << 16  # capped
    assert protocol_ring_capacity(128, 4, 4096) == 4096  # never below flag
    sink = TraceSink(ring_capacity=8)
    got = {}

    def reader():
        got["reader"] = sink.ring()

    t = threading.Thread(target=reader)
    t.start()
    t.join(5)
    assert not t.is_alive()
    # the dead reader's 8-row ring is NOT adopted for the sized request
    big = sink.ring(capacity=64)
    assert big.capacity == 64 and big is not got["reader"]
    assert sink.ring() is big and sink.ring(capacity=8) is big


def _lowered_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _scopes(text: str) -> set:
    return set(re.findall(r"px\.[a-z_0-9.]*[a-z_0-9]", text))


def _small_cfg():
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig

    return MinPaxosConfig(n_replicas=3, window=128, inbox=16, exec_batch=8,
                          kv_pow2=8, catchup_rows=8, recovery_rows=8)


def _served_step():
    from minpaxos_tpu.models.minpaxos import (
        MsgBatch,
        init_replica,
        replica_step_impl,
    )
    from minpaxos_tpu.ops.substeps import scan_ticks

    cfg = _small_cfg()
    return (lambda s, i: scan_ticks(cfg, s, i, replica_step_impl, 1),
            init_replica(cfg, 0), MsgBatch.empty(cfg.inbox))


def test_lowered_served_step_carries_every_section_scope():
    assert _scopes(_lowered_text(*_served_step())) == set(STEP_SCOPES)


def test_lowered_pod_round_carries_step_route_and_scan_scopes():
    from minpaxos_tpu.obs.recorder import N_TEL_FIELDS
    from minpaxos_tpu.parallel import sharded

    cfg, g = _small_cfg(), 2
    ss = sharded.init_sharded(cfg, g)
    args = (cfg, g, 8, 2, ss, jax.numpy.full((g, cfg.window), -1, "int32"),
            jax.numpy.zeros(sharded.LATENCY_BINS, "int32"),
            jax.numpy.full((4, N_TEL_FIELDS), -1, "int32"),
            jax.numpy.zeros(3, "int32"),
            *(jax.numpy.int32(x) for x in (8, 0, 2, 1)))
    text = sharded.sharded_run_resident.lower(*args).as_text(debug_info=True)
    # the pod drops the step's exec results, so the sections that only
    # compute them (the GET lookup and the reply values) are not traced
    unused = {"px.pack", "px.kv.lookup", "px.kv.output"}
    assert _scopes(text) == set(STEP_SCOPES) - unused | set(POD_SCOPES)


def test_mencius_step_shares_the_section_names():
    from minpaxos_tpu.models.minpaxos import MsgBatch
    from minpaxos_tpu.models.mencius import init_mencius, mencius_step_impl

    cfg = _small_cfg()
    text = _lowered_text(lambda s, i: mencius_step_impl(cfg, s, i),
                         init_mencius(cfg, 0), MsgBatch.empty(cfg.inbox))
    got = _scopes(text)
    shared = {"px.propose", "px.accept", "px.vote_count", "px.commit_rows",
              "px.commit_scan", "px.outbox", "px.exec", "px.window_slide"}
    assert shared <= got & set(STEP_SCOPES)
    assert {"px.skip_cede", "px.takeover", "px.commit_bcast"} <= got


def _program(text: str) -> str:
    """Optimized HLO text without what only describes it: op metadata
    and the file / function / stack-frame tables."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    out, skip = [], False
    for line in text.split("\n"):
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip:
            skip = line != ""
        else:
            out.append(line)
    return "\n".join(out)


def test_scopes_are_metadata_the_compiled_step_is_the_same_program():
    named = jax.jit(_served_step()[0]).lower(*_served_step()[1:]).compile()
    with mock.patch.object(jax, "named_scope",
                           lambda name: contextlib.nullcontext()):
        fn, *args = _served_step()
        lowered = jax.jit(fn).lower(*args)
        assert _scopes(lowered.as_text(debug_info=True)) == set()
        plain = lowered.compile()
    assert "px.exec" in named.as_text()
    assert _program(named.as_text()) == _program(plain.as_text())
