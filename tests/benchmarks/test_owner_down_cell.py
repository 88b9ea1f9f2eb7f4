"""Configuration ``mencius5_pod_owner_down`` and its cell,
``mencius64k_owner_down``: the Mencius pod of BASELINE config 4 with one
owner of every group down. What the manifest lists for them (by name,
wherever it stands), the configuration's sizes against
``mencius5_pod_64k``'s, the two readers and the control on hand-made
numbers, and the runner end to end on the CPU at the files' toy
``rehearsal`` shape (no number there is a measurement)."""

from __future__ import annotations

import json
import re

import pytest

from benchmarks import run as harness
from benchmarks.lib import manifest as mf

CELL, CONFIG, STEADY = ("mencius64k_owner_down", "mencius5_pod_owner_down",
                        "mencius5_pod_64k")
NEW_METRICS = {"revoked_slot_pct.pod": ("%", "pod_commits_per_s"),
               "frontier_stall_rounds.pod": ("rounds", "pod_commit_p50_ms")}
#: the lists the cell joins, in the order of the manifest
POD_LISTS = ["pod_commits_per_s", "pod_commit_p50_ms",
             "compiles_in_window.pod", "round_device_ms.pod",
             "device_idle_pct.pod", "inbox_small_tier_pct.pod",
             "gate_open_pct.pod", "noop_slot_pct.pod"]
#: lists the cell does NOT join: the host-gap pair's three cells are
#: pinned by tests/benchmarks/test_tick_subphases.py; the roofline share
#: would overstate the cell (necessary_bytes counts five replicas)
NOT_LISTED = ["pod_dispatch_host_ms.pod", "pod_dispatch_host_max_ms.pod",
              "pod_round_hbm_roofline"]
SIZES = ["chips", "groups", "n_replicas", "window", "concurrent_instances",
         "proposals_per_owner", "proposals_per_round", "inbox", "exec_batch",
         "kv_pow2", "catchup_rows", "recovery_rows", "noop_delay",
         "key_space", "keys_per_owner", "key_bytes", "value_bytes",
         "rounds_per_dispatch", "reduced", "rehearsal", "latency"]


@pytest.fixture(scope="module")
def config():
    return mf.read_json(mf.BENCH_DIR / "configs" / f"{CONFIG}.json")


# ------------------------------------------------------ the manifest

def test_manifest_lists_configuration_cell_and_metrics_by_name():
    manifest = mf.load()
    assert mf.validate(manifest) == []
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    steady = {c["name"]: c for c in manifest["configs"]}[STEADY]
    assert entry["source"] != steady["source"]
    cell = mf.workload_entry(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "owner_down", 1)
    by_name = {m["name"]: m for kind in ("end_to_end", "per_layer")
               for m in manifest[kind]}
    for name in POD_LISTS:  # appended at the end, once
        assert by_name[name]["workloads"][-1] == CELL, name
        assert by_name[name]["workloads"].count(CELL) == 1, name
    for name in NOT_LISTED:
        assert CELL not in by_name[name]["workloads"], name
    for name, (unit, moves) in NEW_METRICS.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["moves"]) == (
            unit, "program_counter", moves)
        assert m["workloads"] == [CELL]
        assert m["layer"] == by_name["gate_open_pct.pod"]["layer"]
    assert {m["name"] for m in mf.metrics_of_cell(
        manifest, CELL, "end_to_end")} == {
            "pod_commits_per_s", "pod_commit_p50_ms", "setup_s"}
    assert {m["name"] for m in mf.metrics_of_cell(
        manifest, CELL, "per_layer")} == set(POD_LISTS[2:]) | set(NEW_METRICS)


def test_sizes_are_the_steady_configurations_key_for_key(config):
    steady = mf.read_json(mf.BENCH_DIR / "configs" / f"{STEADY}.json")
    for key in SIZES:
        assert config[key] == steady[key], key
    assert set(steady) == set(config)
    assert config["runner"] == "pod_mencius_fault"
    assert config["guarantees"] == {
        **steady["guarantees"], **{k: config["guarantees"][k] for k in (
            "agreement", "replication", "revocation")}}
    assert "with their values" in config["guarantees"]["revocation"]
    assert {"revoke_turns", "refusals", "who_dies",
            "memory"} <= set(config["assumed"])


def test_cell_file_states_the_schedule():
    file = mf.read_json(mf.workload_file(CELL))
    steady = mf.read_json(mf.workload_file("mencius64k_steady"))
    assert {k: file[k] for k in (
        "victim", "healthy_rounds", "warm_dispatches",
        "max_drain_dispatches", "reference_groups")} == {
            "victim": 4, "healthy_rounds": 16, "warm_dispatches": 2,
            "max_drain_dispatches": 12, "reference_groups": 4}
    for key in ("proposals_per_owner", "proposals_per_round", "rehearsal"):
        assert file[key] == steady[key], key
    assert {"schedule", "load", "who_sends_it"} <= set(file["assumed"])


# ------------------------------------------- readers and the control

def _read(name, counters):
    reader = harness.load_module(mf.layer_metric_file(name), "od_reader")
    return reader.read({"counters": counters, "config": {}, "workload": {},
                        "trace": None, "device_kind": "cpu"})


@pytest.mark.parametrize("counters, share", [
    ({"revoked_slots": 1024, "noop_slots": 1024, "command_commits": 4096},
     20.0),
    ({"revoked_slots": 0, "noop_slots": 0, "command_commits": 4096}, 0.0),
    ({"revoked_slots": 10, "noop_slots": 40, "command_commits": 60}, 10.0),
    ({"noop_slots": 40, "command_commits": 60}, None),  # no counter
    ({"revoked_slots": 0, "noop_slots": 0, "command_commits": 0}, None),
])
def test_revoked_share_on_hand_made_counters(counters, share):
    got = _read("revoked_slot_pct.pod", counters)
    assert got == (None if share is None else pytest.approx(share))


def test_stall_rounds_on_hand_made_counters():
    assert _read("frontier_stall_rounds.pod", {"stalled_rounds": 11}) == 11
    assert _read("frontier_stall_rounds.pod", {"stalled_rounds": 0}) == 0
    assert _read("frontier_stall_rounds.pod", {"rounds": 80}) is None


def test_control_drops_the_victims_uncommitted_rounds_alone():
    control = harness.load_module(
        harness.ROOT / "benchmarks" / "controls"
        / "mencius_revoke_unaudited.py", "od_control")
    seen = {}

    def replay(rounds_of_owner):
        seen["rounds"] = rounds_of_owner
        # key 4 * 8 + 1 is the victim's; its last kept write is round 7
        return {3: {1: (5, 9), 33: (-1, max(rounds_of_owner[4]))}}

    evidence = {"tables": {3: [{1: 5, 33: 9}] * 2}, "victim": 4,
                "kill_round": 10, "uncommitted_at_kill": 2,
                "keys_per_owner": 8, "replay": replay,
                "rounds_of_owner": [[8, 9, 10]] * 4 + [[6, 7, 8, 9]]}
    got = control.apply(evidence)
    assert seen["rounds"][4] == [6, 7] and seen["rounds"][0] == [8, 9, 10]
    assert got["tables"][3] == [{1: 5, 33: 0xFFFFFFFF}] * 2


# ----------------------------------------- the runner, end to end (CPU)

def _run(capsys, *args) -> tuple[dict, str]:
    assert harness.main(["--workload", CELL, "--seed", "3000039777",
                         "--seconds", "1.0", "--rehearse-cpu", *args]) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def test_traced_rehearsal_is_correct_and_counts_the_revocation(capsys):
    line, err = _run(capsys, "--trace", "1")
    assert line["correct"] is True, line["checks"]
    checks = line["checks"]
    assert {k for k in checks} == {
        "uncommitted", "in_flight_after_drain", "frontier_disagreements",
        "kv_dropped", "table_mismatch", "slots_unaccounted",
        "victim_values_lost", "keys_undecided", "refused",
        "revoked_slots_off"}
    for k, c in checks.items():
        if k not in ("refused", "revoked_slots_off"):
            assert c["value"] == 0 == c["limit"], k
    # the window fills while the first revocation waits; it costs a
    # few rounds of the live owners' load, then nothing more
    assert 0 < checks["refused"]["value"] <= checks["refused"]["limit"]
    assert line["failed"] == 0  # a refusal is an answer, held by its check
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert set(value) == {
        "compiles_in_window.pod", "inbox_small_tier_pct.pod",
        "gate_open_pct.pod", "noop_slot_pct.pod", "revoked_slot_pct.pod",
        "frontier_stall_rounds.pod"}
    assert value["compiles_in_window.pod"] == 0.0
    # every no-op slot is a revoked turn: with the live owners loaded
    # none is ceded
    assert 10.0 < value["revoked_slot_pct.pod"] <= 20.0
    assert value["revoked_slot_pct.pod"] == value["noop_slot_pct.pod"]
    assert 8 <= value["frontier_stall_rounds.pod"] <= 16
    text = re.search(r"counters: (\{.*\})", err)[1]
    counters = dict(re.findall(r"'([\w.]+)': (\d+)[,}]", text))
    rounds = int(counters["rounds"])
    # the gates open for the episode and the renewals, not for good
    assert 0 < value["gate_open_pct.pod"] < 25.0
    assert int(counters["takeover_episodes"]) >= 2  # one a group at least
    assert rounds >= 16 + 96
    # committed: at least 90 % of what the window offered
    assert int(counters["commits_in_window"]) >= 0.9 * int(
        counters["offered_in_window"])


def test_control_rehearsal_is_not_correct(capsys):
    line, _ = _run(capsys, "--control", "mencius_revoke_unaudited")
    assert line["correct"] is False
    bad = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert bad == {"victim_values_lost", "table_mismatch"}
