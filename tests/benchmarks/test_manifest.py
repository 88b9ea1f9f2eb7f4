"""BENCHMARK.json and every file it names keep to the driver's rules."""

import json

import pytest

from benchmarks import run as harness
from benchmarks.lib import manifest as mf


@pytest.fixture(scope="module")
def manifest():
    return mf.load()


def test_manifest_validates(manifest):
    assert mf.validate(manifest) == []


def test_sources_are_short_ascii(manifest):
    # PR 22 was refused before any run for one over-long source
    for c in manifest["configs"]:
        assert 1 <= len(c["source"]) <= 200, c["name"]
        assert c["source"].isascii() and c["source"].isprintable()
        assert "\t" not in c["source"] and "\n" not in c["source"]


@pytest.mark.parametrize("break_it, fault", [
    (lambda m: m["configs"][0].update(source="x" * 201), "source"),
    (lambda m: m["configs"][0].update(source="café"), "source"),
    (lambda m: m["workloads"][0].update(name="a b"), "not a name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda m: m["end_to_end"][0].update(why="x"), "keys"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: m["configs"][1].update(reduced=["value_bytes"]), "shape"),
])
def test_validator_refuses(manifest, break_it, fault):
    broken = json.loads(json.dumps(manifest))
    break_it(broken)
    assert any(fault in f for f in mf.validate(broken)), mf.validate(broken)


def test_a_reader_with_nothing_to_read_returns_nothing(manifest):
    for m in manifest["per_layer"]:
        reader = harness.load_module(mf.layer_metric_file(m["name"]),
                                     "lm_test")
        assert reader.read({"trace": None, "counters": {}, "config": {},
                            "workload": {}, "device_kind": ""}) is None


def test_every_cell_reports_what_the_contract_asks(manifest):
    for w in manifest["workloads"]:
        e2e = [m["name"] for m in
               mf.metrics_of_cell(manifest, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert mf.metrics_of_cell(manifest, w["name"], "per_layer")


def test_pod_config_is_the_headline_config():
    """The file's sizes are what bench.headline_config derives (the one
    definition the program has), at an eighth of the source's 1024 shards, with the source's 1M
    instances as 1024 slots a shard."""
    import bench

    cfg, key_space = bench.headline_config(True, 1024, 128)
    file = mf.read_json(mf.BENCH_DIR / "configs" / "minpaxos5_pod_share.json")
    for k in ("n_replicas", "window", "inbox", "exec_batch", "kv_pow2",
              "catchup_rows", "recovery_rows"):
        assert file[k] == getattr(cfg, k), k
    assert file["key_space"] == key_space
    assert file["groups"] * file["chips_in_deployment"] == 1024
    assert file["groups_in_deployment"] * file["window"] == 1 << 20
    assert file["proposals_per_round"] * 8 == file["window"]


def test_served_config_is_bench_tcp_shape():
    import bench_tcp

    file = mf.read_json(mf.BENCH_DIR / "configs" / "minpaxos3_durable.json")
    flags = file["server_flags"]
    assert flags[:2] == ["-min", "-durable"]
    assert flags[2:2 + len(bench_tcp.SERVER_SHAPE)] == bench_tcp.SERVER_SHAPE
