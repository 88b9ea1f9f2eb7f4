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
              R.PH_PERSIST, R.PH_FSYNC, R.PH_EGRESS, R.PH_REPLY,
              R.PH_ASSEMBLE, R.PH_CALL, R.PH_PEERS, R.PH_FLUSH)
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
V8_FIELD_NAMES = (
    "t_ns", "kind", "k", "rows_in", "rows_out", "frontier", "exec_backlog",
    "drain_us", "enqueue_us", "readback_us", "overlap_us", "persist_us",
    "dispatch_us", "reply_us", "t_rb_ns", "chaos_faults", "coal_occ",
    "coal_wake", "wait_us", "fsync_us", "fsync_bytes", "cpu_us")
SUB_PHASES = {"assemble_us": "enqueue_us", "call_us": "enqueue_us",
              "peer_send_us": "dispatch_us", "flush_us": "dispatch_us"}


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
    assert rows.shape[1] == R.N_FIELDS == 39 and R.SCHEMA_VERSION == 9
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


def test_v9_fields_follow_the_old_twenty_two_in_their_places():
    assert R.SCHEMA_VERSION == 9
    assert R.FIELD_NAMES[:22] == V8_FIELD_NAMES
    assert R.N_FIELDS == len(R.FIELD_NAMES) == 39
    assert R.FIELD_NAMES[-1] == "cpu_sampled"
    # a wall field and a CPU field per phase; the CPU field is the wall
    # field's name with _cpu before the unit
    assert set(R.PHASE_FIELDS) == set(R.PHASE_CPU_FIELDS) == set(TICK_SPANS)
    for name, f in R.PHASE_FIELDS.items():
        assert R.FIELD_NAMES[R.PHASE_CPU_FIELDS[name]] == \
            R.FIELD_NAMES[f][:-3] + "_cpu_us"
    assert [R.FIELD_NAMES[R.PHASE_FIELDS[n]] for n in
            (R.PH_ASSEMBLE, R.PH_CALL, R.PH_PEERS, R.PH_FLUSH)] == \
        list(SUB_PHASES)
    assert set(R.TILING_PHASES) | set(R.NESTED_IN) == set(TICK_SPANS)
    assert {R.FIELD_NAMES[R.PHASE_FIELDS[n]] for n in R.TILING_PHASES} == \
        set(PHASES)


def _spin(ns: int) -> None:
    end = R._cpu_ns() + ns
    while R._cpu_ns() < end:
        pass


def test_sub_phases_nest_and_leave_their_parents_as_they_were():
    """A parent's interval is its own, children or not: the children
    are charged theirs beside it and never exceed it, on both clocks;
    the tiling phases' CPU adds up to ``cpu_us``, the nested ones' does
    not count twice."""
    clock = R.PhaseClock(3)
    clock.adopt()
    with R.phase(R.PH_ENQUEUE, clock) as enq:
        with R.phase(R.PH_ASSEMBLE, clock) as asm:
            _spin(300_000)
        with R.phase(R.PH_CALL, clock) as call:
            _spin(200_000)
    with R.phase(R.PH_EGRESS, clock) as eg1:
        with R.phase(R.PH_PEERS, clock) as peers:
            _spin(100_000)
    with R.phase(R.PH_REPLY, clock):
        pass
    with R.phase(R.PH_EGRESS, clock) as eg2:
        _spin(100_000)  # _host_catchup: egress's self time
        with R.phase(R.PH_FLUSH, clock) as flush:
            _spin(100_000)
    ns, cpu = dict(clock.ns), dict(clock.cpu)
    assert ns[R.PH_ENQUEUE] == enq.ns  # the first phase: no glue before it
    assert ns[R.PH_ASSEMBLE] == asm.ns and ns[R.PH_CALL] == call.ns
    assert asm.ns + call.ns <= enq.ns
    assert ns[R.PH_PEERS] == peers.ns and ns[R.PH_FLUSH] == flush.ns
    assert peers.ns + flush.ns <= eg1.ns + eg2.ns <= ns[R.PH_EGRESS]
    # CPU: what was spun, where it was spun
    assert cpu[R.PH_ASSEMBLE] >= 300_000 and cpu[R.PH_CALL] >= 200_000
    assert cpu[R.PH_ASSEMBLE] + cpu[R.PH_CALL] <= cpu[R.PH_ENQUEUE]
    assert cpu[R.PH_PEERS] >= 100_000 and cpu[R.PH_FLUSH] >= 100_000
    assert cpu[R.PH_EGRESS] >= 300_000 + cpu[R.PH_FLUSH] - 100_000
    for name in TICK_SPANS:
        assert 0 <= cpu[name] <= ns[name] + 50_000, name  # clock grain
    # a sampled row's cpu_us is what was taken of its TILING phases
    taken = {n: clock.take_cpu_us(n) for n in TICK_SPANS}
    assert taken == {n: cpu[n] // 1000 for n in TICK_SPANS}
    assert clock.cpu_us(sampled=True) == sum(taken[n]
                                             for n in R.TILING_PHASES)
    assert clock.cpu_us(sampled=True) == 0 and not any(clock.cpu.values())
    # with ``sample`` off no phase reads the thread's clock; the row's
    # cpu_us is then the one read at its cut, since the cut before
    clock.sample = False
    with R.phase(R.PH_PERSIST, clock):
        with R.phase(R.PH_FSYNC, clock):
            _spin(200_000)
    assert not any(clock.cpu.values()) and clock.ns[R.PH_FSYNC] > 0
    assert clock.cpu_us() >= 200
    # and a phase that opens after one which read nothing reads for itself
    clock.sample = True
    with R.phase(R.PH_REPLY, clock):
        _spin(100_000)
    assert 100_000 <= clock.cpu[R.PH_REPLY] < 100_000_000


def test_v9_rows_of_a_loaded_leader_nest_and_their_cpu_adds_up(
        profiled_cluster):
    lead = _leader(profiled_cluster["collection"])
    rows = lead["rows"]
    loaded = rows[(rows[:, COL["coal_occ"]] > 0)
                  & (rows[:, COL["kind"]] != R.KIND_IDLE_SKIP)]
    assert len(loaded) >= 50
    # children inside their parents, row by row (a microsecond of
    # rounding a field), and covering enqueue but for a line of glue
    for child_a, child_b, parent in (("assemble_us", "call_us", "enqueue_us"),
                                     ("peer_send_us", "flush_us",
                                      "dispatch_us")):
        both = loaded[:, COL[child_a]] + loaded[:, COL[child_b]]
        assert (both <= loaded[:, COL[parent]] + 2).all(), parent
        assert (loaded[:, COL[child_a]] > 0).all(), child_a
        assert (loaded[:, COL[child_b]] > 0).all(), child_b
    enq = loaded[:, COL["enqueue_us"]].sum()
    assert (loaded[:, COL["assemble_us"]].sum()
            + loaded[:, COL["call_us"]].sum()) >= 0.9 * enq
    # one row in CPU_SAMPLE_EVERY carries the per-phase CPU times (a
    # read of the thread's clock is a system call); the others none
    sampled = rows[:, COL["cpu_sampled"]] == 1
    assert set(rows[:, COL["cpu_sampled"]].tolist()) == {0, 1}
    assert abs(int(sampled.sum()) - len(rows) // R.CPU_SAMPLE_EVERY) <= 1
    every_cpu = rows[:, list(R.PHASE_CPU_FIELDS.values())]
    assert not every_cpu[~sampled].any() and every_cpu[sampled].any()
    # every phase's CPU lies inside its wall (the thread clock's grain
    # and a microsecond of rounding apart)
    for name, f in R.PHASE_FIELDS.items():
        cpu = rows[sampled, R.PHASE_CPU_FIELDS[name]]
        assert (cpu >= 0).all() and cpu.sum() <= \
            rows[sampled, f].sum() * 1.02 + 10_000, name
    # on those rows the seven tiling phases' CPU is the row's cpu_us,
    # and all rows' cpu_us is the counter's
    tiling = sum(rows[:, R.PHASE_CPU_FIELDS[n]] for n in R.TILING_PHASES)
    assert (tiling[sampled] == rows[sampled, COL["cpu_us"]]).all()
    assert rows[:, COL["cpu_us"]].sum() > 0
    counters = lead["metrics"]["counters"]
    assert counters["proto_cpu_us"] == rows[:, COL["cpu_us"]].sum()


def test_reader_threads_cpu_and_the_thread_count_are_in_the_registry(
        profiled_cluster):
    """Every replica's connection readers decoded frames: their CPU time
    is a plain counter (it answers after ``stop()``), next to the
    protocol thread's and the number of threads on the one GIL."""
    for entry in profiled_cluster["collection"]:
        counters = entry["metrics"]["counters"]
        gauges = entry["metrics"]["gauges"]
        assert counters["ingress_cpu_us"] > 0
        assert counters["proto_cpu_us"] > 0
        # three replicas in this process, each a protocol thread, an
        # accept loop, a control thread and a reader a connection
        assert gauges["threads_alive"] >= 9
        for gone in ("device_step_ms", "pipelined_ticks",
                     "store_truncated_bytes", "coalesce_pending_rows"):
            assert gone not in counters and gone not in gauges
            assert gone not in entry["metrics"]["histograms"]


def test_the_four_sub_phase_spans_lie_inside_their_parents(profiled_cluster):
    data = jax.profiler.ProfileData.from_file(profiled_cluster["xplane"])
    checked = {name: 0 for name in R.NESTED_IN}
    orphans = 0
    for plane in data.planes:
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name in TICK_SPANS]
            for child, parent in R.NESTED_IN.items():
                parents = [(a, b) for n, a, b in evs if n == parent]
                for n, a, b in evs:
                    if n == child:
                        checked[child] += 1
                        orphans += not any(pa <= a and b <= pb
                                           for pa, pb in parents)
    assert all(n > 100 for n in checked.values()), checked
    # a phase that was open when the profile began has no span of its
    # own, and its child has: at most one such a replica
    assert orphans <= 3, orphans


def test_v9_rows_render_the_sub_phases_inside_their_parents():
    rec = R.FlightRecorder(8)
    rec.record(9_000_000, R.KIND_FULL, 1, 4, 4, 10, 0, 50, 100, 300, 0,
               400, 200, 30, t_rb_ns=8_000_000, wait_us=700, fsync_us=250,
               fsync_bytes=999, cpu_us=600, assemble_us=60, call_us=38,
               peer_send_us=90, flush_us=70, enqueue_cpu_us=80)
    events = rec.to_events(pid=1)
    assert R.validate_chrome_trace(R.chrome_trace(events)) == []
    by_name = {e["name"]: e for e in events if e.get("ph") == "X"}

    def span(name):
        return by_name[name]["ts"], by_name[name]["ts"] + by_name[name]["dur"]

    enq, egress = span("enqueue"), span("dispatch")
    assert span("assemble") == (enq[0], enq[0] + 60)
    assert span("call") == (enq[1] - 38, enq[1])
    assert span("peers") == (egress[0], egress[0] + 90)
    assert span("flush") == (egress[1] - 70, egress[1])
    assert by_name["peers"]["tid"] == by_name["dispatch"]["tid"] == 1
    assert by_name["call"]["tid"] == by_name["enqueue"]["tid"] == 0
    # a v8 row (no sub-phase recorded) draws none
    rec.record(19_000_000, R.KIND_FULL, 1, 4, 4, 10, 0, 50, 100, 300, 0,
               400, 200, 30, t_rb_ns=18_000_000)
    names = [e["name"] for e in rec.to_events(pid=1, last=1)]
    assert not {"assemble", "call", "peers", "flush"} & set(names)


def test_pod_ring_restarts_at_begin_resident_and_takes_one_entry_a_dispatch():
    from minpaxos_tpu.parallel import sharded

    sc = sharded.ShardedCluster(_small_cfg(), 2, ext_rows=8, key_space=64,
                                seed=3)
    sc.elect(0)
    sc.begin_resident()
    assert obs.process_pods()[-1]["dispatches"] == 0
    assert len(obs.process_pods()[-1]["dispatch_ns"]) == 0
    for _ in range(3):
        sc.run_resident(2, 4)
    pod = obs.process_pods()[-1]
    assert pod["dispatches"] == 3
    assert pod["dispatch_ns"].shape == pod["readback_ns"].shape == (3,)
    assert (pod["dispatch_ns"] > 0).all() and (pod["readback_ns"] > 0).all()
    # copies: the next dispatch does not write into what was handed out
    before = pod["dispatch_ns"].copy()
    sc.run_resident(2, 4)
    assert (pod["dispatch_ns"] == before).all()
    assert obs.process_pods()[-1]["dispatches"] == 4
    sc.begin_resident()
    assert obs.process_pods()[-1]["dispatches"] == 0
    sc.run_resident(2, 4)
    assert len(obs.process_pods()[-1]["readback_ns"]) == 1
    # the ring wraps at its capacity, oldest first
    sc._pod["dispatches"] = sharded.POD_HOST_RING + 2
    sc._host_ns[0, :] = np.arange(sharded.POD_HOST_RING)
    ring = obs.process_pods()[-1]["dispatch_ns"]
    assert len(ring) == sharded.POD_HOST_RING
    assert ring[0] == 2 and ring[-1] == 1
    sc.end_resident()


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
