"""Configuration ``mencius3_durable`` and its cell,
``mencius3_open_knee80``: served Mencius, three owners, clients spread
over all of them. What the manifest lists for them (by name, wherever it
stands), the configuration's file against ``minpaxos_tpu/deployments.py``
and against ``minpaxos3_durable``'s guarantees, the cell's traffic and
placement, and the four new readers on hand-made counters. The runner
end to end is in ``test_served_mencius_check.py``."""

from __future__ import annotations

import numpy as np
import pytest

from benchmarks import run as harness
from benchmarks.lib import manifest as mf
from benchmarks.lib import ownerload
from minpaxos_tpu import deployments

CELL, CONFIG, LEADER_CELL, LEADER_CONFIG = (
    "mencius3_open_knee80", "mencius3_durable", "served3_open_knee80",
    "minpaxos3_durable")
HOST_LAYER = "served path, host (runtime/replica.py, transport.py, stable.py)"
NEW_METRICS = {
    "noop_slot_pct.served": ("%", "lower", "program_counter", "reply_p50_ms"),
    "owner_proposal_share_max.served": ("%", "lower", "program_counter",
                                        "reply_p95_ms"),
    "merge_wait_ms.served": ("ms", "lower", "program_span", "reply_p50_ms"),
    "owner_dispatches_per_s.served": ("1/s", "higher", "program_counter",
                                      "reply_p50_ms")}


PINNED = {
    "tick_wait_ms.served", "tick_drain_ms.served", "tick_enqueue_ms.served",
    "tick_readback_ms.served", "tick_persist_ms.served",
    "tick_fsync_ms.served", "tick_egress_ms.served", "tick_reply_ms.served",
    "tick_cpu_share.served", "req_queue_wait_ms.served",
    "req_commit_ticks.served", "req_reply_ticks.served",
    "store_bytes_per_commit.served", "follower_lag_ms.served"}


@pytest.fixture(scope="module")
def config():
    return mf.read_json(mf.BENCH_DIR / "configs" / f"{CONFIG}.json")


@pytest.fixture(scope="module")
def cell():
    return mf.read_json(mf.workload_file(CELL))


# ------------------------------------------------------ the manifest

def test_manifest_lists_configuration_cell_and_metrics_by_name():
    manifest = mf.load()
    by_config = {c["name"]: c for c in manifest["configs"]}
    entry = by_config[CONFIG]
    assert entry["reduced"] == []
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] != by_config[LEADER_CONFIG]["source"]
    assert "-m" in entry["source"] and "-e" in entry["source"]
    assert len(entry["source"]) <= 200 and entry["source"].isascii()
    w = mf.workload_entry(manifest, CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "open_knee80", 1)
    assert "one owner of three" in w["why"]
    by_name = {m["name"]: m for kind in ("end_to_end", "per_layer")
               for m in manifest[kind]}
    # appended after the single-leader cell, once, to every list the
    # single-leader cell is on — but for PR 26's fourteen program-span
    # metrics, whose lists tests/benchmarks/test_progobs.py pins to the
    # two served3 cells (a benchmark PR's to loosen)
    for name, m in by_name.items():
        listed = m.get("workloads", [])
        if LEADER_CELL in listed and name not in PINNED:
            assert listed.count(CELL) == 1, name
            assert listed.index(LEADER_CELL) < listed.index(CELL), name
        elif name in PINNED:
            assert CELL not in listed, name
    for name, (unit, better, source, moves) in NEW_METRICS.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            unit, better, source, moves)
        assert m["workloads"] == [CELL] and m["layer"] == HOST_LAYER
    assert {m["name"] for m in mf.metrics_of_cell(
        manifest, CELL, "end_to_end")} == {
            "reply_p50_ms", "reply_p95_ms", "setup_s"}
    assert {m["name"] for m in mf.metrics_of_cell(
        manifest, CELL, "per_layer")} == ({m["name"] for m in mf.metrics_of_cell(
            manifest, LEADER_CELL, "per_layer")} - PINNED) | set(NEW_METRICS)
    # and no other cell reports the new ones
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & {m["name"] for m in mf.metrics_of_cell(
                manifest, other["name"], "per_layer")}


# ------------------------------------------------ the configuration

def test_server_shape_is_deployments_and_compiles_to_it(config):
    flags = config["server_flags"]
    assert flags[:2] == ["-m", "-durable"]
    shape = deployments.MENCIUS_SERVER_SHAPE
    assert flags[2:2 + len(shape)] == shape
    assert flags[2 + len(shape):] == ["-keyhint", str(config["key_range"])]
    from minpaxos_tpu.cli import server as server_cli

    args = server_cli.build_parser().parse_args(flags)
    assert server_cli.protocol_from_args(args) == "mencius"
    cfg = server_cli.config_from_args(args, config["n_replicas"])
    assert (cfg.n_replicas, cfg.window, cfg.inbox, cfg.exec_batch,
            cfg.kv_pow2) == (3, 4096, 2048, 512, 18)
    for size in ("window", "inbox", "exec_batch", "kv_pow2"):
        assert config["assumed"][size] == getattr(cfg, size)
        assert "PR 35" in config["assumed"][f"{size}_reading"] \
            or size == "kv_pow2"
    run = server_cli.flags_from_args(args)
    assert run.durable and run.dreply and run.warm_variants
    # the rehearsal is the single-leader configuration's toy, with -m
    toy = mf.read_json(mf.BENCH_DIR / "configs" / f"{LEADER_CONFIG}.json")[
        "rehearsal"]["server_flags"]
    assert config["rehearsal"]["server_flags"] == ["-m", *toy[1:]]


def test_deployment_is_the_single_leader_ones_but_for_the_protocol(config):
    leader = mf.read_json(mf.BENCH_DIR / "configs" / f"{LEADER_CONFIG}.json")
    for same in ("chips", "n_replicas", "key_range", "record_count",
                 "key_bytes", "value_bytes", "message_delay_injected_ms",
                 "store_medium", "reduced"):
        assert config[same] == leader[same], same
    assert config["runner"] == "served_mencius" and config["reduced"] == []
    assert "little, by nature" in config["device_holds"]
    assert "three durable logs" in config["device_holds"]


def test_guarantees_are_none_weaker_than_the_single_leader_ones(config):
    leader = mf.read_json(mf.BENCH_DIR / "configs" / f"{LEADER_CONFIG}.json")
    mine, theirs = config["guarantees"], leader["guarantees"]
    assert set(theirs) <= set(mine)
    for word_for_word in ("quorum", "durability", "reply", "replication"):
        assert mine[word_for_word] == theirs[word_for_word]
    assert mine["exactly_once"].startswith(theirs["exactly_once"])
    reads = mine["reads"]
    for clause in ("linearizable", "latest PUT before it", "ONE merged order",
                   "respects real time ACROSS owners"):
        assert clause in reads, clause
    assert set(mine) - set(theirs) == {"merged_log"}


def test_knee_and_rate(config, cell):
    knee = config["sustained_rate_hz"]
    fine = {r["rate_hz"]: r for r in config["sweeps"]["fine_seed_21"]}
    assert fine[knee]["sustained"] and not fine[knee + 100]["sustained"]
    assert all(r["sustained"] for r in fine.values() if r["rate_hz"] <= knee)
    assert all(r["unanswered"] == 0 for r in fine.values())
    assert cell["rate_hz"] == int(0.8 * knee) // 50 * 50 == 2150


# ---------------------------------------------------------- the cell

def test_traffic_is_the_single_leader_cells_but_for_the_rate(cell):
    leader = mf.read_json(mf.workload_file(LEADER_CELL))
    for same in ("sessions", "workers", "write_pct", "zipf_s", "burst_x",
                 "warm_s", "drain_timeout_s", "quiesce_timeout_s",
                 "rehearsal"):
        assert cell[same] == leader[same], same
    assert (cell["write_pct"], cell["zipf_s"], cell["sessions"],
            cell["workers"], cell["burst_x"], cell["warm_s"]) == (
                50, 0.99, 64, 4, 1.0, 2.0)
    assert {"placement", "who_sends_it", "rate"} <= set(cell["assumed"])
    assert "YCSB core workload A" in cell["source"]


def test_placement_is_22_21_21(cell, config):
    n = config["n_replicas"]
    assert ownerload.sessions_per_owner(cell["sessions"], n) == [22, 21, 21]
    # as the workers number them: worker w's j-th session is w * 16 + j
    per_worker = cell["sessions"] // cell["workers"]
    owners = [ownerload.owner_of_session(w * per_worker + j, n)
              for w in range(cell["workers"]) for j in range(per_worker)]
    assert np.bincount(owners).tolist() == [22, 21, 21]
    assert owners[:7] == [0, 1, 2, 0, 1, 2, 0]
    # requests follow their home session, so in blocks of 8 per session
    # each owner is offered its sessions' share: 34.4 % at the most
    assert max(np.bincount(owners)) / len(owners) == pytest.approx(0.34375)


# ------------------------------------------------------- the readers

def _read(name, counters):
    reader = harness.load_module(mf.layer_metric_file(name), "sm_reader")
    return reader.read({"counters": counters, "config": {}, "workload": {},
                        "trace": None, "device_kind": "cpu"})


def test_counter_readers_on_hand_made_counters():
    assert _read("noop_slot_pct.served",
                 {"noop_slots": 30, "command_slots": 970}) == 3.0
    assert _read("noop_slot_pct.served",
                 {"noop_slots": 0, "command_slots": 50}) == 0.0
    assert _read("noop_slot_pct.served",
                 {"noop_slots": 0, "command_slots": 0}) is None
    assert _read("owner_proposal_share_max.served",
                 {"owner_client_proposals": [22, 21, 21]}) == 34.375
    assert _read("owner_proposal_share_max.served",
                 {"owner_client_proposals": [90, 0, 0]}) == 100.0
    assert _read("owner_proposal_share_max.served",
                 {"owner_client_proposals": [0, 0, 0]}) is None
    assert _read("owner_dispatches_per_s.served",
                 {"owner_dispatches": [900, 600, 750],
                  "leader_window_s": 30.0}) == 20.0
    # a program, or a runner, without them (the parent, the single-
    # leader cells): nothing, and no raise
    for name in NEW_METRICS:
        if name != "merge_wait_ms.served":
            assert _read(name, {"leader_dispatches": 9,
                                "leader_window_s": 30.0}) is None
    # the pod's reader of the same quantity keeps its own counters
    assert _read("noop_slot_pct.pod",
                 {"noop_slots": 1, "command_commits": 3}) == 25.0


def test_merge_wait_reader_on_hand_made_spans():
    from minpaxos_tpu.obs.trace import ST_COMMIT, ST_DRAIN, ST_OWN_COMMIT

    reader = harness.load_module(
        mf.layer_metric_file("merge_wait_ms.served"), "mw_reader")
    ms = 1_000_000

    def spans(waits_ms, first_id):
        rows = []
        for i, w in enumerate(waits_ms):
            t = (10 + i) * ms
            rows += [[first_id + i, ST_DRAIN, t - ms, t - ms, 0],
                     [first_id + i, ST_OWN_COMMIT, t, t, 7],
                     [first_id + i, ST_COMMIT, t + w * ms, t + w * ms, 7]]
        # a command the frontier passed before its COMMIT row left
        rows.append([first_id + 999, ST_COMMIT, 5 * ms, 5 * ms, 1])
        return {"spans": rows}

    stale = {"replica": 1, "spans": spans([500] * 9, 5000)}  # an older cluster's
    coll = [stale,
            {"replica": 0, "spans": spans([2, 4, 6], 1)},
            {"replica": 1, "spans": spans([8, 10], 2001)},
            {"replica": 2, "spans": spans([12], 4001)}]
    assert sorted(reader.merge_waits_ms(coll)) == [2, 4, 6, 8, 10, 12]
    assert len(reader.merge_waits_ms(None)) == 0
    assert len(reader.merge_waits_ms([{"replica": 0, "spans": {"spans": []}}])) == 0
