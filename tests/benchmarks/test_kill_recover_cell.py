"""Configuration ``minpaxos5_pod_kill_recover`` and its cell,
``pod128_kill_recover``: BASELINE config 5 WITH the kill / recover leg
its source names. What the manifest lists for them (by name, wherever
it stands), the configuration's sizes against ``minpaxos5_pod_share``'s,
the two readers and ``lib/transfer_bytes.py`` on hand-made numbers, and
the runner end to end on the CPU at the files' toy ``rehearsal`` shape
(no number there is a measurement)."""

from __future__ import annotations

import json
import os
import re

import pytest

from benchmarks import run as harness
from benchmarks.lib import manifest as mf
from benchmarks.lib.transfer_bytes import transfer_bytes_per_install

CELL, CONFIG, STEADY = ("pod128_kill_recover", "minpaxos5_pod_kill_recover",
                        "minpaxos5_pod_share")
NEW_METRICS = {"recovery_rounds.pod": ("rounds", "lower"),
               "state_transfer_mb.pod": ("MB", "lower")}
POD_LISTS = ["pod_commits_per_s", "pod_commit_p50_ms",
             "compiles_in_window.pod", "round_device_ms.pod",
             "pod_round_hbm_roofline", "device_idle_pct.pod",
             "inbox_small_tier_pct.pod", "gate_open_pct.pod"]
#: what a deployment's file says of its sizes, as the steady one does
SIZES = ["chips", "groups", "groups_in_deployment", "chips_in_deployment",
         "n_replicas", "window", "concurrent_instances_in_deployment",
         "proposals_per_round", "inbox", "exec_batch", "kv_pow2",
         "catchup_rows", "recovery_rows", "key_space", "key_bytes",
         "value_bytes", "rounds_per_dispatch", "reduced"]


@pytest.fixture(scope="module")
def config():
    return mf.read_json(mf.BENCH_DIR / "configs" / f"{CONFIG}.json")


# ------------------------------------------------------ the manifest

def test_manifest_lists_configuration_cell_and_metrics_by_name():
    manifest = mf.load()
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == ["groups"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    steady_source = {c["name"]: c for c in manifest["configs"]}[STEADY][
        "source"]
    assert entry["source"] != steady_source  # two deployments, one benchmark
    assert "kill/recover" in entry["source"] and len(entry["source"]) <= 200
    cell = mf.workload_entry(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "kill_recover", 1)
    by_name = {m["name"]: m for kind in ("end_to_end", "per_layer")
               for m in manifest[kind]}
    for name in POD_LISTS:  # appended after the steady cell, once
        listed = by_name[name]["workloads"]
        assert listed.count(CELL) == 1
        assert listed.index("pod128_steady") < listed.index(CELL)
    for name, (unit, better) in NEW_METRICS.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            unit, better, "program_counter", "pod_commits_per_s")
        assert m["workloads"] == [CELL]
        assert m["layer"] == by_name["gate_open_pct.pod"]["layer"]
    assert {m["name"] for m in mf.metrics_of_cell(
        manifest, CELL, "end_to_end")} == {
            "pod_commits_per_s", "pod_commit_p50_ms", "setup_s"}
    assert {m["name"] for m in mf.metrics_of_cell(
        manifest, CELL, "per_layer")} == set(POD_LISTS[2:]) | set(NEW_METRICS)


def test_sizes_are_the_steady_configurations_key_for_key(config):
    """No width cut and no size changed: the deployment is config 5's,
    with its fault leg in place of a footnote."""
    steady = mf.read_json(mf.BENCH_DIR / "configs" / f"{STEADY}.json")
    for key in SIZES:
        assert config[key] == steady[key], key
    assert set(steady) - set(config) == set() == set(config) - set(steady)
    assert config["runner"] == "pod_fault" and steady["runner"] == "pod"
    assert config["guarantees"] == {
        **steady["guarantees"], "recovery": config["guarantees"]["recovery"]}
    assert "any length" in config["guarantees"]["recovery"]
    assert {"victim", "outage", "recovery_path"} <= set(config["assumed"])
    assert "left_out" not in config["assumed"]  # the leg is in
    # the toy keeps the chip shape's ratio of catch-up to proposals (4p:
    # at 2p the gap never closes under load) and the inbox that
    # deployments.headline_config's rule gives for it
    toy = {**config, **config["rehearsal"]}
    assert toy["catchup_rows"] == 4 * toy["proposals_per_round"]
    assert config["catchup_rows"] == 4 * config["proposals_per_round"]
    for c in (config, toy):
        assert c["inbox"] == (c["proposals_per_round"]
                              + 2 * c["catchup_rows"] + 128)


def test_cell_file_states_the_schedule(config):
    file = mf.read_json(mf.workload_file(CELL))
    assert {k: file[k] for k in (
        "proposals_per_round", "victim", "healthy_rounds", "dead_rounds",
        "warm_dispatches", "max_drain_dispatches", "reference_groups")} == {
            "proposals_per_round": 128, "victim": 4, "healthy_rounds": 16,
            "dead_rounds": 32, "warm_dispatches": 2,
            "max_drain_dispatches": 12, "reference_groups": 4}
    # the outage is 4 windows of the log, 8 x retention: beyond what
    # catch-up rows can ever heal
    slots = file["dead_rounds"] * file["proposals_per_round"]
    assert slots == 4 * config["window"] == 8 * (config["window"] // 2)
    # and the schedule falls on dispatch boundaries
    k = config["rounds_per_dispatch"]
    assert file["healthy_rounds"] % k == 0 == file["dead_rounds"] % k
    assert {"schedule", "load", "who_sends_it"} <= set(file["assumed"])


# ------------------------------------------- readers and the yardstick

def _read(name, counters, config=None):
    reader = harness.load_module(mf.layer_metric_file(name), "kr_reader")
    return reader.read({"counters": counters, "config": config or {},
                        "workload": {}, "trace": None, "device_kind": "cpu"})


def test_transfer_bytes_against_a_hand_count(config):
    # 2^16 entries x (key hi, key lo, value hi, value lo, live mark) x
    # 4 B, read at the donor and written at the laggard
    assert transfer_bytes_per_install(config) == 2 * 65_536 * 5 * 4 \
        == 2_621_440
    assert transfer_bytes_per_install({"kv_pow2": 3}) == 2 * 8 * 20
    # what the program says one install copies is half of it (it counts
    # the copy once) and the table's drop counter, one word
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig, transfer_bytes
    assert 2 * (transfer_bytes(MinPaxosConfig(kv_pow2=16)) - 4) == 2_621_440


def test_readers_on_hand_made_counters(config):
    assert _read("recovery_rounds.pod", {"lagging_rounds": 7}) == 7
    assert _read("recovery_rounds.pod", {"lagging_rounds": 0}) == 0
    assert _read("state_transfer_mb.pod", {"state_transfers": 128},
                 config) == pytest.approx(335.54432)
    assert _read("state_transfer_mb.pod", {"state_transfers": 0},
                 config) == 0.0
    # a program without the counters (the parent): nothing, no raise
    assert _read("recovery_rounds.pod", {"rounds": 80}) is None
    assert _read("state_transfer_mb.pod", {"rounds": 80}, config) is None
    # the open gate of the transfer joins the gates gate_open_pct reads
    assert _read("gate_open_pct.pod", {
        "gates": {"px.retry": 0, "px.state_transfer": 2},
        "tier_rounds": 80}) == 2.5


def test_control_swaps_the_victims_table_alone():
    control = harness.load_module(
        harness.ROOT / "benchmarks" / "controls" / "pod_victim_frozen.py",
        "kr_control")
    evidence = {"tables": {3: [{1: 9}] * 5}, "victim": 4,
                "rounds_before_kill": [2, 3],
                "replay": lambda rounds: {3: {1: -1, 2: len(rounds)}}}
    got = control.apply(evidence)["tables"][3]
    assert got[:4] == [{1: 9}] * 4
    assert got[4] == {1: 0xFFFFFFFF, 2: 2}


# ----------------------------------------- the runner, end to end (CPU)

def _run(capsys, *args) -> tuple[dict, str]:
    assert harness.main(["--workload", CELL, "--seed", "3000033777",
                         "--seconds", "1.5", "--rehearse-cpu", *args]) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def test_traced_rehearsal_is_correct_and_counts_its_recovery(capsys):
    line, err = _run(capsys, "--trace", "1")
    assert line["correct"] is True, line["checks"]
    assert {k: c["limit"] for k, c in line["checks"].items()} == {
        "uncommitted": 0, "in_flight_after_drain": 0,
        "frontier_disagreements": 0, "kv_dropped": 0, "table_mismatch": 0,
        "outage_rounds_off": 0, "victim_behind_at_close": 0,
        "transfers_off": 0}  # groups / 8 of the toy's 4 groups
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    value = {k: m["value"] for k, m in line["metrics"].items()}
    # the CPU has no device plane: the trace's readers report nothing
    assert set(value) == {
        "compiles_in_window.pod", "inbox_small_tier_pct.pod",
        "gate_open_pct.pod", "recovery_rounds.pod", "state_transfer_mb.pod"}
    assert value["compiles_in_window.pod"] == 0.0
    text = re.search(r"counters: (\{.*\})", err)[1]
    counters = dict(re.findall(r"'([\w.]+)': (\d+)[,}]", text))
    rounds = int(counters["rounds"])
    # one install a group, in ONE round; the first cell in which a gate
    # opens and the full tier is taken
    assert int(counters["state_transfers"]) == 4
    assert int(counters["px.state_transfer"]) == 1
    assert value["gate_open_pct.pod"] == pytest.approx(100.0 / rounds)
    assert value["inbox_small_tier_pct.pod"] < 100.0
    assert value["state_transfer_mb.pod"] == pytest.approx(
        4 * 2 * 4096 * 20 / 1e6)
    assert 0 < value["recovery_rounds.pod"] < rounds - 48
    assert int(counters["dead_rounds"]) == 32
    # the profile began with the revive: 16 + 32 rounds lie before it
    assert 0 < int(counters["traced_rounds"]) <= rounds - 48


def test_control_reads_not_correct(capsys):
    line, _ = _run(capsys, "--trace", "0", "--control", "pod_victim_frozen")
    assert line["correct"] is False and line["control"]
    bad = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert bad == {"table_mismatch"}
    assert set(line["metrics"]) == {"pod_commits_per_s", "pod_commit_p50_ms",
                                    "setup_s"}


def test_a_program_without_the_transfer_is_refused_at_once(monkeypatch):
    """Against the parent's program the cell ends before anything is
    built: a clean non-zero exit, not a frozen victim read as a wrong
    answer and not a hang."""
    from minpaxos_tpu.parallel import sharded

    monkeypatch.delattr(sharded, "transfer_round")
    with pytest.raises(SystemExit) as exit_:
        harness.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                      "--rehearse-cpu"])
    assert exit_.value.code not in (0, None)
    assert not (harness.ROOT / ".bench_scratch" / f"{CELL}-{os.getpid()}"
                ).exists()
