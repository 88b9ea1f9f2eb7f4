"""The one command, end to end on the CPU at the toy rehearsal shapes:
it refuses to measure without the chip, each runner's rehearsal ends in
one well-formed line, and a timed path broken underneath comes out not
correct. No number here is a measurement."""

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks import run as harness
from benchmarks.lib import manifest as mf

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in mf.load()["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cli(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_without_the_chip_nothing_is_measured():
    proc = run_cli("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs" in proc.stderr


def test_unknown_cell_is_an_error():
    proc = run_cli("--workload", "no_such_cell", "--seed", "1",
                   "--seconds", "1", "--trace", "0", "--rehearse-cpu")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def last_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def check_line(line: dict, metric_names: set) -> None:
    assert RESULT_KEYS <= set(line)
    assert list(line)[-1] == "checks"  # the numbers compared come last
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) <= metric_names and line["metrics"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert line["attempted"] > 0 and line["failed"] == 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_one_well_formed_line(cell):
    manifest = mf.load()
    proc = run_cli("--workload", cell, "--seed", "3000000019", "--seconds",
                   "2", "--trace", "0", "--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = last_line(proc.stdout)
    check_line(line, {m["name"] for m in mf.metrics_of_cell(
        manifest, cell, "end_to_end")})
    assert line["correct"] is True, line["checks"]
    assert "setup_s" in line["metrics"]
    # the numbers compared are the last lines of stderr too
    assert proc.stderr.strip().splitlines()[-1] == "correct: True"
    assert not list((ROOT / ".bench_scratch").glob(f"{cell}-*"))


def run_in_process(capsys, *args) -> dict:
    assert harness.main([*args, "--seed", "12", "--seconds", "1.5",
                         "--rehearse-cpu"]) == 0
    return last_line(capsys.readouterr().out)


def test_traced_rehearsal_reports_layer_metrics(capsys):
    manifest = mf.load()
    line = run_in_process(capsys, "--workload", "pod128_steady", "--trace", "1")
    check_line(line, {m["name"] for m in mf.metrics_of_cell(
        manifest, "pod128_steady", "per_layer")})
    assert line["correct"] is True
    # no device plane on the CPU: the trace's readers report nothing
    assert "compiles_in_window.pod" in line["metrics"]
    assert "pod_round_hbm_roofline" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


# ---- the timed path broken underneath: `correct` has to read false ----

def _pod_step_leaves_state_unchanged(monkeypatch):
    from minpaxos_tpu.parallel.sharded import ShardedCluster

    real = ShardedCluster.run_resident
    calls = {"n": 0}

    def stuck(self, k_rounds, n_proposals, substeps=1):
        calls["n"] += 1
        if calls["n"] == 4:  # one dispatch inside the window does nothing
            self._seed += k_rounds
            return self.committed()[0], 0
        return real(self, k_rounds, n_proposals, substeps)

    monkeypatch.setattr(ShardedCluster, "run_resident", stuck)


def _pod_half_the_batch_left_out(monkeypatch):
    from minpaxos_tpu.parallel.sharded import ShardedCluster

    real = ShardedCluster.run_resident
    monkeypatch.setattr(
        ShardedCluster, "run_resident",
        lambda self, k, n, substeps=1: real(self, k, n // 2, substeps))


def _pod_answer_altered(monkeypatch):
    from minpaxos_tpu.parallel.sharded import ShardedCluster

    real = ShardedCluster.run_resident

    def alter(self, k_rounds, n_proposals, substeps=1):
        out = real(self, k_rounds, n_proposals, substeps)
        if n_proposals == 0:  # the drain: one replica's values go wrong
            kv = self.ss.states.kv
            self.ss = self.ss._replace(states=self.ss.states._replace(
                kv=kv._replace(val=kv.val.at[:, 4].add(1))))
        return out

    monkeypatch.setattr(ShardedCluster, "run_resident", alter)


@pytest.mark.parametrize("fault, number", [
    (_pod_step_leaves_state_unchanged, "uncommitted"),
    (_pod_half_the_batch_left_out, "uncommitted"),
    (_pod_answer_altered, "table_mismatch"),
])
def test_pod_fault_reads_not_correct(capsys, monkeypatch, fault, number):
    fault(monkeypatch)
    line = run_in_process(capsys, "--workload", "pod128_steady", "--trace", "0")
    assert line["correct"] is False
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]


def _served_answer_altered(monkeypatch):
    from minpaxos_tpu.runtime import replica

    real = replica.join_i64
    # where the reply's value is produced: every value comes out one off
    monkeypatch.setattr(replica, "join_i64", lambda hi, lo: real(hi, lo) + 1)


def _served_step_leaves_state_unchanged(monkeypatch):
    import jax

    from benchmarks.lib.loadgen import OpenLoopLoad
    from minpaxos_tpu.runtime import replica

    real_step, real_begin = replica._packed_step, OpenLoopLoad.begin_phase
    phases = []

    def begin(self, *a, **kw):
        phases.append(real_begin(self, *a, **kw))  # warm-up, then window
        return phases[-1]

    def stuck(cfg, state, inbox, *rest):
        # from the window's middle on the step takes nothing in
        if len(phases) >= 2 and time.monotonic() > phases[1] + 0.75:
            inbox = jax.tree.map(np.zeros_like, inbox)
        return real_step(cfg, state, inbox, *rest)

    monkeypatch.setattr(OpenLoopLoad, "begin_phase", begin)
    monkeypatch.setattr(replica, "_packed_step", stuck)


def _served_fsync_left_out(monkeypatch):
    from minpaxos_tpu.runtime.stable import StableStore

    # the log still reaches the file, but nothing waits for the disk
    monkeypatch.setattr(StableStore, "flush", lambda self: self._f.flush())


def _served_log_written_behind(monkeypatch):
    from minpaxos_tpu.runtime.stable import StableStore

    # replies go out while the log sits in the process's buffer; only
    # the stop writes it (after the check has read the disk)
    monkeypatch.setattr(StableStore, "flush", lambda self: None)


@pytest.mark.parametrize("fault, number", [
    (_served_answer_altered, "wrong_replies"),
    (_served_step_leaves_state_unchanged, "never_answered"),
    (_served_fsync_left_out, "acked_before_durable"),
    (_served_log_written_behind, "not_logged_once"),
])
def test_served_fault_reads_not_correct(capsys, monkeypatch, fault, number):
    fault(monkeypatch)
    line = run_in_process(capsys, "--workload", "served3_open_floor",
                          "--trace", "0")
    assert line["correct"] is False
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]


def test_the_load_follows_a_leadership_that_moves(capsys, monkeypatch):
    """Not a fault: mid-window the master's RPC makes replica 1 the
    leader. The deposed leader refuses with a hint, the workers move,
    and every guarantee still holds."""
    import threading

    from benchmarks.lib.loadgen import OpenLoopLoad
    from minpaxos_tpu.runtime import master

    real_begin, phases = OpenLoopLoad.begin_phase, []

    def promote(maddr):
        host, port = master.get_replica_list(maddr)[1]
        master._rpc((host, port + master.CONTROL_OFFSET),
                    {"m": "be_the_leader"}, timeout=5.0)

    def begin(self, *a, **kw):
        phases.append(real_begin(self, *a, **kw))
        if len(phases) == 2:  # the window: a second and a half into it
            threading.Timer(phases[1] - time.monotonic() + 1.5, promote,
                            [self.maddr]).start()
        return phases[-1]

    monkeypatch.setattr(OpenLoopLoad, "begin_phase", begin)
    assert harness.main(["--workload", "served3_open_floor", "--trace", "0",
                         "--seed", "14", "--seconds", "4",
                         "--rehearse-cpu"]) == 0
    captured = capsys.readouterr()
    line = last_line(captured.out)
    assert line["correct"] is True, line["checks"]
    assert "'failovers': 2" in captured.err  # both workers moved
