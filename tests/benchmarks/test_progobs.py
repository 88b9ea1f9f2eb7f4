"""The per-layer readers of the program's own recorder rows, paxtrace
spans and store counters (``benchmarks/lib/progobs.py`` and the fourteen
``layer_metrics/*.served.py`` files that call it), on a hand-made
collection whose answers are known, and on nothing at all."""

from __future__ import annotations

import importlib.util
import pathlib

import numpy as np
import pytest

from benchmarks.lib import manifest as mf
from benchmarks.lib import progobs
from minpaxos_tpu.obs import recorder as R
from minpaxos_tpu.obs import trace as T

COL = {name: i for i, name in enumerate(R.FIELD_NAMES)}
N = 240  # loaded leader dispatches, one every 10 ms
TICK_NS = 10_000_000
PHASE_US = {"wait_us": 4000, "drain_us": 500, "enqueue_us": 1000,
            "readback_us": 1500, "persist_us": 1200, "dispatch_us": 1300,
            "reply_us": 500, "fsync_us": 400}


def _rows(n, t0_ns, frontier0, *, loaded, lag_ns=0):
    rows = np.zeros((n, R.N_FIELDS), np.int64)
    t_rb = t0_ns + lag_ns + TICK_NS * np.arange(1, n + 1)
    rows[:, COL["t_rb_ns"]] = t_rb
    rows[:, COL["t_ns"]] = t_rb + 3_000_000  # host phases end 3 ms on
    rows[:, COL["kind"]] = R.KIND_FULL
    rows[:, COL["frontier"]] = frontier0 + 4 * np.arange(1, n + 1)
    if loaded:
        rows[:, COL["coal_occ"]] = 4
        for f, us in PHASE_US.items():
            rows[:, COL[f]] = us
        rows[:, COL["cpu_us"]] = 3000  # of the 6 ms a tick is not waiting
        rows[:, COL["fsync_bytes"]] = 100
    return rows


def _spans(leader_rows):
    """One sampled command per tick: decoded 2 ms before the tick's
    readback, drained 1.5 ms before it, committed two dispatches later,
    reply serialized 1 ms after the commit's readback."""
    t_rb = leader_rows[:, COL["t_rb_ns"]]
    out = []
    for i in range(len(t_rb) - 2):
        tid = T.trace_id_for(1000 + i)
        out += [(tid, T.ST_DECODE, t_rb[i] - 2_100_000, t_rb[i] - 2_000_000,
                 1000 + i),
                (tid, T.ST_DRAIN, t_rb[i] - 1_500_000, t_rb[i] - 1_500_000,
                 i),
                (tid, T.ST_COMMIT, t_rb[i + 2], t_rb[i + 2], 7),
                (tid, T.ST_REPLY_SER, t_rb[i + 2] + 900_000,
                 t_rb[i + 2] + 1_000_000, 1000 + i)]
    return [list(map(int, s)) for s in out]


def _entry(replica, rows, spans=(), counters=None, committed=0):
    return {"replica": replica, "rows": rows,
            "rows_total": 0 if rows is None else len(rows),
            "rows_capacity": 4096,
            "spans": {"spans": list(spans), "total": len(spans),
                      "dropped": 0, "enabled": True, "sample_pow2": 4},
            "metrics": {"namespace": f"replica{replica}",
                        "counters": counters or {},
                        "gauges": {"committed": committed},
                        "histograms": {}}}


@pytest.fixture
def collection():
    t0 = 5_000_000_000
    lead = _rows(N, t0, 0, loaded=True)
    # idle skips before the load began: not loaded dispatches
    idle = _rows(20, t0 - 20 * TICK_NS, -4 * 20, loaded=False)
    idle[:, COL["kind"]] = R.KIND_IDLE_SKIP
    idle[:, COL["frontier"]] = 0
    idle[:, COL["t_rb_ns"]] = 0
    lead = np.concatenate([idle, lead])
    return [
        _entry(0, lead, _spans(lead[20:]),
               {"store_flushed_bytes": 70 * 4 * N}, committed=4 * N),
        # follower 1 reads each frontier back 2 ms after the leader,
        # follower 2 (the slower) 7 ms after
        _entry(1, _rows(N, t0, 0, loaded=False, lag_ns=2_000_000)),
        _entry(2, _rows(N, t0, 0, loaded=False, lag_ns=7_000_000)),
    ]


EXPECTED = {
    "tick_wait_ms.served": 4.0, "tick_drain_ms.served": 0.5,
    "tick_enqueue_ms.served": 1.0, "tick_readback_ms.served": 1.5,
    "tick_persist_ms.served": 1.2, "tick_fsync_ms.served": 0.4,
    "tick_egress_ms.served": 1.3, "tick_reply_ms.served": 0.5,
    # 3 ms of CPU in a 10 ms tick that waited 4 ms
    "tick_cpu_share.served": 50.0,
    "req_queue_wait_ms.served": 0.5,
    # drained 1.5 ms before dispatch i's readback, learned committed at
    # dispatch i+2's: dispatches i, i+1, i+2
    "req_commit_ticks.served": 3.0,
    "req_reply_ticks.served": 0.0,
    "store_bytes_per_commit.served": 70.0,
    "follower_lag_ms.served": 7.0,
}


def _reader(name):
    path = mf.layer_metric_file(name)
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_made_collection(name, collection, monkeypatch):
    monkeypatch.setattr(progobs, "collection", lambda: collection)
    assert _reader(name).read({}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_raising(name, monkeypatch):
    """No collection (the parent program has none), an empty one, and
    one with too few loaded dispatches and chains all read None."""
    few = [_entry(0, _rows(progobs.MIN_SAMPLES - 1, 0, 0, loaded=True))]
    for coll in (None, [], [_entry(0, None)], few):
        monkeypatch.setattr(progobs, "collection", lambda c=coll: c)
        if name == "store_bytes_per_commit.served" and coll is few:
            continue  # a ratio of two counters needs no sample
        assert _reader(name).read({}) is None


def test_every_new_metric_has_its_manifest_entry_and_docstring():
    manifest = mf.load()
    assert mf.validate(manifest) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    served = ["served3_open_knee80", "served3_open_floor"]
    for name in EXPECTED:
        m = by_name[name]
        assert m["workloads"] == served
        assert m["layer"] == ("served path, host (runtime/replica.py, "
                              "transport.py, stable.py)")
        want = ("program_counter" if name.startswith("store_")
                else "program_span")
        assert m["source"] == want
        assert m["better"] == ("higher" if name.startswith("tick_cpu")
                               else "lower")
        doc = _reader(name).__doc__
        assert doc and len(doc.split()) > 12
    # appended, nothing before them moved
    assert [m["name"] for m in manifest["per_layer"]][-14:] == [
        "tick_wait_ms.served", "tick_drain_ms.served",
        "tick_enqueue_ms.served", "tick_readback_ms.served",
        "tick_persist_ms.served", "tick_fsync_ms.served",
        "tick_egress_ms.served", "tick_reply_ms.served",
        "tick_cpu_share.served", "req_queue_wait_ms.served",
        "req_commit_ticks.served", "req_reply_ticks.served",
        "store_bytes_per_commit.served", "follower_lag_ms.served"]


def test_leader_is_the_replica_with_client_rows_and_a_live_process_collects(
        collection):
    assert progobs.leader(collection)["replica"] == 0
    assert progobs.leader(collection[1:]) is None
    mask = progobs.loaded(collection[0]["rows"])
    assert int(mask.sum()) == N and not mask[:20].any()
    # the real call: whatever this process registered, it answers a list
    assert isinstance(progobs.collection(), list)
    assert pathlib.Path(progobs.__file__).name == "progobs.py"
