"""The ten per-layer readers of PR 37 (the four sub-phase medians, the
three per-phase CPU shares, the reader threads' CPU beside the protocol
threads', the pod loop's host interval: ``benchmarks/lib/progcpu.py``
and ten ``layer_metrics`` files), on hand-made rows, counters and a
hand-made ``process_pods()`` entry whose answers are known, and on
nothing at all."""

from __future__ import annotations

import numpy as np
import pytest
import test_progobs as base

from benchmarks.lib import manifest as mf
from benchmarks.lib import progcpu, progobs
from minpaxos_tpu import obs
from minpaxos_tpu.obs import recorder as R

COL = base.COL
FIELD_NAMES = R.FIELD_NAMES
# a loaded dispatch of the hand-made leader, microseconds: the sub-phase
# walls inside test_progobs's enqueue 1,000 and egress 1,300, and the
# thread's CPU inside three phases' walls
SUB_US = {"assemble_us": 600, "call_us": 380, "peer_send_us": 500,
          "flush_us": 300, "enqueue_cpu_us": 250, "dispatch_cpu_us": 325,
          "persist_cpu_us": 900}
SERVED = ["served3_open_knee80", "served3_open_floor", "mencius3_open_knee80"]
# not pod128_kill_recover: tests/benchmarks/test_kill_recover_cell.py
# pins the set of per-layer entries that list it
PODS = ["pod128_steady", "mencius64k_steady", "mencius64k_one_owner"]
EXPECTED = {
    "tick_assemble_ms.served": 0.6, "tick_call_ms.served": 0.38,
    "tick_peer_send_ms.served": 0.5, "tick_flush_ms.served": 0.3,
    "tick_enqueue_cpu_share.served": 25.0,   # 250 of 1,000 us
    "tick_egress_cpu_share.served": 25.0,    # 325 of 1,300
    "tick_persist_cpu_share.served": 75.0,   # 900 of 1,200
    # readers 30 + 20 + 10 ms of CPU, protocol threads 50 + 40 + 30
    "ingress_cpu_per_proto_cpu.served": 0.5,
    "pod_dispatch_host_ms.pod": 4.0, "pod_dispatch_host_max_ms.pod": 90.0,
}
COUNTERS = [{"ingress_cpu_us": 30_000, "proto_cpu_us": 50_000},
            {"ingress_cpu_us": 20_000, "proto_cpu_us": 40_000},
            {"ingress_cpu_us": 10_000, "proto_cpu_us": 30_000}]
# eleven dispatches of 4 ms and one stall of 90
RING_NS = np.array([4_000_000] * 11 + [90_000_000], np.int64)


def _collection(counters=COUNTERS, width=R.N_FIELDS, n=base.N):
    lead = base._rows(n, 5_000_000_000, 0, loaded=True)
    for f, us in SUB_US.items():
        lead[:, COL[f]] = us
    # the program measures the CPU times of one row in eight: the
    # others carry nothing there, and say so
    if "cpu_sampled" in COL and width > COL["cpu_sampled"]:
        lead[::8, COL["cpu_sampled"]] = 1
        unsampled = lead[:, COL["cpu_sampled"]] == 0
        for f in SUB_US:
            if f.endswith("_cpu_us"):
                lead[unsampled, COL[f]] = 0
    entries = [base._entry(0, lead[:, :width], counters=counters[0])]
    for i in (1, 2):
        rows = base._rows(n, 5_000_000_000, 0, loaded=False)
        entries.append(base._entry(i, rows[:, :width], counters=counters[i]))
    return entries


def _pod(ring=RING_NS):
    return {"protocol": "minpaxos", "dispatches": 0 if ring is None
            else len(ring), "dispatch_ns": ring, "readback_ns": ring}


@pytest.fixture
def program(monkeypatch):
    """The program as this PR leaves it: a collection and a pod."""
    monkeypatch.setattr(progobs, "collection", _collection)
    monkeypatch.setattr(obs, "process_pods", lambda: [_pod(RING_NS[:2]),
                                                      _pod()])


# what the harness hands a reader after a traced run on the chip, and
# after a rehearsal on the CPU, as far as these readers look
ON_CHIP = {"trace": {"devices": ["/device:TPU:0"]}}
ON_CPU = {"trace": {"devices": []}}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_made_rows_counters_and_ring(name, program):
    assert base._reader(name).read(ON_CHIP) == pytest.approx(EXPECTED[name])
    if name.endswith(".pod"):
        # the gap a DEVICE waits for: a CPU rehearsal has none to report
        assert base._reader(name).read(ON_CPU) is None
        assert base._reader(name).read({"trace": None}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_without_raising(name, monkeypatch):
    """No collection and no pod (nothing ran), empty ones, the PARENT's
    shape (22 columns, no such counter, a pod entry without a ring) and
    too few samples all read None."""
    parent_counters = [{"store_flushed_bytes": 1}] * 3
    few = progobs.MIN_SAMPLES - 1
    cases = [
        (None, []), ([], []), ([base._entry(0, None)], [_pod(None)]),
        (_collection(parent_counters, width=22),
         [{"protocol": "minpaxos", "tiers": None}]),
        (_collection(parent_counters, n=few),
         [_pod(RING_NS[:progcpu.MIN_POD_DISPATCHES - 1])])]
    for i, (coll, pods) in enumerate(cases):
        # the parent's rows come with the parent's names: 22 of each
        monkeypatch.setattr(R, "FIELD_NAMES", R.FIELD_NAMES[:22] if i == 3
                            else FIELD_NAMES)
        monkeypatch.setattr(progobs, "collection", lambda c=coll: c)
        monkeypatch.setattr(obs, "process_pods", lambda p=pods: p)
        assert base._reader(name).read(ON_CHIP) is None, (
            name, coll and len(coll))


def test_a_cpu_share_is_a_ratio_of_sums_and_the_ratio_needs_every_replica():
    coll = _collection()
    rows = coll[0]["rows"]
    sampled = np.nonzero(rows[:, COL["cpu_sampled"]])[0]
    assert len(sampled) == base.N // 8 >= progcpu.MIN_CPU_ROWS
    # the clock's steps: 0 or 500 in the measured rows, mean 250
    rows[sampled[::2], COL["enqueue_cpu_us"]] = 0
    rows[sampled[1::2], COL["enqueue_cpu_us"]] = 500
    assert progcpu.phase_cpu_share_pct("enqueue_us", coll) == \
        pytest.approx(25.0)
    # too few measured rows give no share
    rows[sampled[progcpu.MIN_CPU_ROWS - 1:], COL["cpu_sampled"]] = 0
    assert progcpu.phase_cpu_share_pct("enqueue_us", coll) is None
    assert progcpu.phase_cpu_share_pct("no_such_us", coll) is None
    # one replica without the counter: the process's ratio is unknown
    del coll[2]["metrics"]["counters"]["ingress_cpu_us"]
    assert progcpu.ingress_cpu_per_proto_cpu(coll) is None


def test_the_real_program_feeds_the_pod_readers_their_ring():
    """The newest pod of this process, after three dispatches: too few
    for a reading, and the ring is there for one."""
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig
    from minpaxos_tpu.parallel.sharded import ShardedCluster

    cfg = MinPaxosConfig(n_replicas=3, window=128, inbox=16, exec_batch=8,
                         kv_pow2=8, catchup_rows=8, recovery_rows=8)
    sc = ShardedCluster(cfg, 2, ext_rows=8, key_space=64, seed=5)
    sc.elect(0)
    sc.begin_resident()
    for _ in range(3):
        sc.run_resident(2, 4)
    assert progcpu.pod_dispatch_ms(ON_CHIP, np.median) is None
    for _ in range(progcpu.MIN_POD_DISPATCHES - 3):
        sc.run_resident(2, 4)
    ring = obs.process_pods()[-1]["dispatch_ns"]
    assert progcpu.pod_dispatch_ms(ON_CHIP, np.max) == pytest.approx(
        ring.max() / 1e6)
    assert 0 < progcpu.pod_dispatch_ms(ON_CHIP, np.median) <= ring.max() / 1e6
    assert progcpu.pod_dispatch_ms(ON_CPU, np.max) is None
    sc.end_resident()


def test_the_ten_entries_are_appended_after_the_thirty_six_that_stood():
    manifest = mf.load()
    assert mf.validate(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    assert set(names[36:46]) == set(EXPECTED) and len(names) >= 46
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in EXPECTED:
        m = by_name[name]
        pod = name.endswith(".pod")
        assert m["workloads"] == (PODS if pod else SERVED)
        assert m["layer"] == (
            "composition (parallel/sharded.py, replica.py _packed_step)"
            if pod else "served path, host (runtime/replica.py, "
                        "transport.py, stable.py)")
        assert m["source"] == ("program_counter" if name.startswith("ingress")
                               else "program_span")
        assert m["better"] == ("higher" if "cpu_share" in name else "lower")
        assert m["moves"] in ("reply_p50_ms", "reply_p95_ms",
                              "pod_commits_per_s")
        doc = base._reader(name).__doc__
        assert doc and len(doc.split()) > 12
