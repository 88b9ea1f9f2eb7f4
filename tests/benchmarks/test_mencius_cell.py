"""Configuration ``mencius5_pod_64k`` and cell ``mencius64k_steady``:
the reference's streams against the program's, the configuration's
sizes, what the manifest gained and where, and the runner end to end
on the CPU at the file's toy ``rehearsal`` shape (no number there is a
measurement)."""

from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

from benchmarks import run as harness
from benchmarks.lib import manifest as mf
from benchmarks.lib import menciusstream
from benchmarks.lib.necessary_bytes import necessary_bytes_per_round

CELL, CONFIG = "mencius64k_steady", "mencius5_pod_64k"


@pytest.fixture(scope="module")
def config():
    return mf.read_json(mf.BENCH_DIR / "configs" / f"{CONFIG}.json")


# ------------------------------------------------------ the reference

def test_reference_streams_are_the_programs():
    """``lib/menciusstream.py`` was written from the description; it
    draws what the program's host injector draws, owner by owner."""
    from minpaxos_tpu.ops.workload import propose_batch_host

    groups, owners, rows, per_owner = [0, 2, 3], 5, 16, 64
    for seed, rnd in ((7, 0), (3000000019 % 0x7FFFFFFF, 41)):
        keys, vals = menciusstream.owner_rows(seed, rnd, groups, owners, rows,
                                              per_owner)
        b = propose_batch_host(owners, 4, rows, rows, -1, rnd, seed,
                               key_space=512)
        np.testing.assert_array_equal(keys, b.key_lo[groups])
        np.testing.assert_array_equal(vals, b.val_lo[groups])


def test_replay_is_each_owners_last_write_per_key():
    groups, owners, rows, per_owner = [1], 3, 4, 8
    rounds = list(range(9))
    got = menciusstream.replay(5, [rounds, rounds[:-1], []], groups, rows,
                               per_owner)[1]
    want = {}
    for r in rounds:
        keys, vals = menciusstream.owner_rows(5, r, groups, owners, rows,
                                              per_owner)
        for o, offered in enumerate((rounds, rounds[:-1], [])):
            if r in offered:
                want.update(zip(keys[0, o].tolist(), vals[0, o].tolist()))
    assert got == want
    # owners never share a key; the idle owner wrote nothing
    assert all(k // per_owner in (0, 1) for k in got)
    assert len(got) == 2 * per_owner  # 36 and 32 draws from 8 keys each


# -------------------------------------------------- the configuration

def test_config_is_benchs_mencius_64k(config):
    """The file's sizes are ``bench.side_shapes``' mencius_64k tuple,
    the one statement of BASELINE config 4 the program has; nothing is
    cut and the guarantees are stated."""
    import bench

    cfg, groups, per_owner, _, protocol = bench.side_shapes(True)[
        "mencius_64k"]
    assert protocol == "mencius"
    for k in ("n_replicas", "window", "inbox", "exec_batch", "kv_pow2",
              "catchup_rows", "recovery_rows", "noop_delay"):
        assert config[k] == getattr(cfg, k), k
    assert config["groups"] == groups
    assert config["proposals_per_owner"] == per_owner
    assert config["proposals_per_round"] == per_owner * cfg.n_replicas
    assert groups * cfg.window == config["concurrent_instances"] == 1 << 16
    assert config["catchup_rows"] > config["proposals_per_owner"]
    assert config["reduced"] == [] and config["runner"] == "pod_mencius"
    assert set(config["guarantees"]) == {
        "quorum", "agreement", "exactly_once", "replication", "no_table_drop"}
    assert "latency" in config and "rounds_per_dispatch" in config["assumed"]


@pytest.mark.parametrize("overlay", [{}, "rehearsal"])
def test_key_ranges_fit_table_and_stream(config, overlay):
    from minpaxos_tpu.ops.workload import owner_key_range

    c = {**config, **(config[overlay] if overlay else {})}
    # bench.py's side config draws from half the table, as here
    assert c["key_space"] == 1 << (c["kv_pow2"] - 1)
    assert c["keys_per_owner"] == owner_key_range(c["key_space"],
                                                  c["n_replicas"])
    assert c["keys_per_owner"] * c["n_replicas"] <= c["key_space"]
    assert c["proposals_per_owner"] <= c["keys_per_owner"]
    assert c["exec_batch"] >= c["proposals_per_round"]


def test_cell_file_and_necessary_bytes(config):
    cell = mf.read_json(mf.workload_file(CELL))
    assert cell["proposals_per_owner"] == config["proposals_per_owner"]
    assert cell["proposals_per_round"] == config["proposals_per_round"]
    # 16 groups x 78,720 lanes x 4 B a round
    assert necessary_bytes_per_round(
        config, cell["proposals_per_round"]) == 16 * 78_720 * 4
    manifest = mf.load()
    assert [m["name"] for m in mf.metrics_of_cell(manifest, CELL,
                                                  "end_to_end")] == [
        "pod_commits_per_s", "pod_commit_p50_ms", "setup_s"]
    assert [m["name"] for m in mf.metrics_of_cell(manifest, CELL,
                                                  "per_layer")] == [
        "compiles_in_window.pod", "round_device_ms.pod",
        "pod_round_hbm_roofline", "device_idle_pct.pod"]


def test_manifest_gained_entries_at_the_end_of_their_lists_only():
    """One configuration, one cell, the cell's name at the end of each
    ``workloads`` list it reports under; no per-layer entry (appended
    ones fail ``test_progobs.py``'s ``[-14:]`` pin, inserted ones read
    as a change to what was there: PERF.md section 7 row 6)."""
    manifest = mf.load()
    assert mf.validate(manifest) == []
    assert manifest["configs"][-1]["name"] == CONFIG
    assert [c["name"] for c in manifest["configs"]].count(CONFIG) == 1
    assert manifest["workloads"][-1] == {
        **manifest["workloads"][-1], "name": CELL, "config": CONFIG,
        "traffic": "steady", "chips": 1}
    listed = [m for kind in ("end_to_end", "per_layer")
              for m in manifest[kind] if CELL in m.get("workloads", ())]
    assert len(listed) == 6
    assert all(m["workloads"][-1] == CELL and "pod128_steady" in
               m["workloads"] for m in listed)
    assert len(manifest["per_layer"]) == 27
    assert not [m for m in manifest["per_layer"] if "mencius" in m["name"]
                or m["name"].startswith(("noop_", "inbox_"))]
    # and no reader without an entry was left behind
    names = {m["name"] for m in manifest["per_layer"]}
    for f in (mf.BENCH_DIR / "layer_metrics").glob("*.py"):
        assert f.stem in names or any(n.startswith(f.stem + ".")
                                      for n in names), f.name


# ----------------------------------------- the runner, end to end (CPU)

def _run(capsys, *args) -> tuple[dict, str]:
    assert harness.main(["--workload", CELL, "--seed", "3000028777",
                         "--seconds", "1.5", "--rehearse-cpu", *args]) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def test_traced_rehearsal_is_correct_and_logs_the_pods_own_counts(capsys):
    line, err = _run(capsys, "--trace", "1")
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {
        "uncommitted", "in_flight_after_drain", "frontier_disagreements",
        "kv_dropped", "table_mismatch", "slots_unaccounted"}
    assert all(c["limit"] == 0 for c in line["checks"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    # the CPU has no device plane, so the trace's readers report nothing
    assert set(line["metrics"]) == {"compiles_in_window.pod"}
    assert line["metrics"]["compiles_in_window.pod"]["value"] == 0.0
    # tier counts and no-op slots have no accepted metric yet: the
    # runner's counters carry them to the log. Every owner loaded:
    # nothing ceded, and every command of the window's rounds counted
    counters = dict(re.findall(r"'(\w+)': (\d+)[,}]",
                               re.search(r"counters: (\{.*\})", err)[1]))
    assert counters["noop_slots"] == "0"
    assert int(counters["command_commits"]) > 0
    assert int(counters["tier_rounds"]) == int(counters["rounds"]) > 0
    assert 0 <= int(counters["kernel_small_rounds"]) <= int(
        counters["tier_rounds"])


def test_control_reads_not_correct(capsys):
    line, _ = _run(capsys, "--trace", "0", "--control",
                   "mencius_owner_round_lost")
    assert line["correct"] is False and line["control"]
    bad = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert bad == {"table_mismatch"}


def test_a_program_without_the_owner_streams_is_refused_at_once(monkeypatch):
    """Against the parent's program the cell ends before anything is
    built: a clean non-zero exit, not a wrong answer and not a hang."""
    from minpaxos_tpu.parallel import sharded

    monkeypatch.delattr(sharded, "N_COUNTS")
    with pytest.raises(SystemExit) as exit_:
        harness.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                      "--rehearse-cpu"])
    assert exit_.value.code not in (0, None)
    # this process's scratch (another worker's run of the cell has its own)
    assert not (harness.ROOT / ".bench_scratch" / f"{CELL}-{os.getpid()}"
                ).exists()
