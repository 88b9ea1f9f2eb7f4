"""TCP-runtime benchmarks: the reference's own deployment shape, measured.

Boots master + 3 replica servers as REAL processes on localhost — the
bareminrun.sh topology (reference bareminrun.sh:16-21) — then runs the
closed-loop client with ``-check`` (simpletest.sh:1) plus a per-op
serial-latency pass. Two configs:

* ``-min -durable``  — BASELINE config 1 (bareminpaxos, the shape the
  reference's scripts measure); this is the record's top level.
* ``-m -durable``    — the same deployment running Mencius (the
  reference compiled it but never wired it into its server binary),
  driven by the leaderless round-robin MultiClient (client.go -e);
  recorded under ``"mencius_tcp"``.

Methodology (round 5): each throughput number is the MEDIAN of
``BENCH_TCP_K`` trials (default 5) against one warm cluster, with the
min/max spread recorded alongside — single-shot numbers on a shared
host are noise (round-4 verdict weak #2: a -28% swing shipped as a
regression record). Every trial uses a FRESH client connection, which
also gives it a fresh exactly-once reply book and a fresh server-side
pending set (re-proposal dedup is per connection).

Server shapes are tuned for the measured step cost, not defaults:
window 2048 / inbox 1024 / kv 2^18 — the protocol step is
window-linear with a table-sized floor, and serial latency is ~3 steps
end-to-end (tools/profile_step.py: 1.7 ms/step at this shape vs 6.5 ms
at the old window-4096/kv-2^20 shape). kv 2^18 holds the 100k-key
workload at 0.38 load, comfortable for the two-choice table.

Writes one JSON object to BENCH_TCP.json. Run: ``python bench_tcp.py``
(``BENCH_TCP_Q`` overrides the per-trial request count). One process
owns the chip, so the N server PROCESSES this file boots run on the CPU
JAX backend: it measures the HOST runtime (framed TCP wire, batched
column packing, durable store). Replicas whose steps run on the chip
live in one process — chip_smoke.py phase B serves this file's
``SERVER_SHAPE`` that way.
"""

from __future__ import annotations

import contextlib
import json
import os
import pathlib
import signal
import statistics
import subprocess
import sys
import time

import numpy as np

from minpaxos_tpu.utils.netutil import CONTROL_OFFSET, free_ports

REPO = pathlib.Path(__file__).resolve().parent

SERVER_SHAPE = ["-window", "2048", "-inbox", "1024", "-kvpow2", "18",
                "-execbatch", "128"]
# Mencius fills ~2x the slots per client op (idle owners cede SKIPs
# that are committed no-op rows too) and serves three concurrent
# proposers, so it wants the wider window/inbox and a full-size exec
# drain — the tight minpaxos shape starved it (325 vs ~1.3k ops/s)
MENCIUS_SHAPE = ["-window", "4096", "-inbox", "2048", "-kvpow2", "18",
                 "-execbatch", "512"]
# Serial latency wants the OPPOSITE sizing from throughput: one op in
# flight needs ~3 protocol ticks end-to-end and every tick is
# window-linear with a KV-capacity floor, so the latency leg boots its
# own small cluster (a 512-slot window holds the ~500 warm+serial
# slots; kv 2^12 holds their distinct keys at ~0.1 load). At the
# throughput shape the same path measured p50 ~20-22 ms; the reference
# measures latency with a separate client the same way
# (clientlat/client.go:134-160).
SERIAL_SHAPE = ["-window", "512", "-inbox", "256", "-kvpow2", "12",
                "-execbatch", "64"]

# Round-6 runtime knobs (fused burst ticks / idle fast path / narrow
# view — runtime/replica.py RuntimeFlags), env-overridable for A/B
# runs; every record carries the values used so a number can never be
# misread as measured under different knobs.
RUNTIME_KNOBS = {
    "fuse_ticks": os.environ.get("BENCH_TCP_FUSE", "3"),
    "idle_fastpath": os.environ.get("BENCH_TCP_IDLEFAST", "1") != "0",
    "narrow_window": os.environ.get("BENCH_TCP_NARROW", "0"),
    # depth-2 pipelined tick loop (default ON, the production shape);
    # BENCH_TCP_PIPELINE=0 runs the -nopipeline leg for the paired
    # serial-vs-pipelined A/B (PERF.md methodology: interleaved legs)
    "pipeline": os.environ.get("BENCH_TCP_PIPELINE", "1") != "0",
    # paxmon flight recorder (default ON, the production shape);
    # BENCH_TCP_RECORDER=0 runs -norecorder for the overhead A/B
    # (acceptance: p50 + closed-loop within 3% of disabled)
    "recorder": os.environ.get("BENCH_TCP_RECORDER", "1") != "0",
    # paxtrace (default ON): sampled per-command stage spans; the
    # throughput legs trace 1-in-2^BENCH_TCP_TRACEPOW2, the serial
    # leg overrides to pow2=0 (every op traced — that IS the
    # measurement). BENCH_TCP_TRACE=0 runs -notrace for the overhead
    # A/B (tracing off is byte-transparent on the wire).
    "trace": os.environ.get("BENCH_TCP_TRACE", "1") != "0",
    "trace_pow2": os.environ.get("BENCH_TCP_TRACEPOW2", "4"),
    # ISSUE-15 event-driven ingress (default ON, the production
    # shape); BENCH_TCP_COALESCE=0 / BENCH_TCP_OVERLAP=0 run the
    # cadence-driven legs for the paired serial A/B, and main()
    # records that pairing itself under "serial_cadence_baseline"
    "coalesce": os.environ.get("BENCH_TCP_COALESCE", "1") != "0",
    "coalesce_wait_us": os.environ.get("BENCH_TCP_COALESCE_WAIT_US",
                                       "200"),
    "overlap_exec": os.environ.get("BENCH_TCP_OVERLAP", "1") != "0",
    # ISSUE-16 flexible quorums: replica count and the (q1, q2) pair
    # compiled into every server ("0" = simple majority — the
    # byte-identical default). The flex A/B legs flip these via
    # _knobs; the server refuses a non-intersecting pair at boot.
    "n_replicas": os.environ.get("BENCH_TCP_N", "3"),
    "q1": os.environ.get("BENCH_TCP_Q1", "0"),
    "q2": os.environ.get("BENCH_TCP_Q2", "0"),
    # paxdur snapshot/truncation policy (runtime/replica.py): inert on
    # the default non-durable bench servers, but stamped so a
    # durability A/B can never be misread against a record whose
    # snapshot cadence (and its fsync/segment-swap pauses) differed
    "snapshots": os.environ.get("BENCH_TCP_SNAP", "1") != "0",
    "snap_every_bytes": os.environ.get("BENCH_TCP_SNAP_EVERY",
                                       str(8 << 20)),
}


def _knob_args(keyhint: int, trace_pow2: str | None = None) -> list:
    args = ["-fuseticks", RUNTIME_KNOBS["fuse_ticks"],
            "-narrow", RUNTIME_KNOBS["narrow_window"],
            "-keyhint", str(keyhint),
            "-tracepow2", trace_pow2 or RUNTIME_KNOBS["trace_pow2"]]
    if not RUNTIME_KNOBS["idle_fastpath"]:
        args.append("-noidlefast")
    if not RUNTIME_KNOBS["pipeline"]:
        args.append("-nopipeline")
    if not RUNTIME_KNOBS["recorder"]:
        args.append("-norecorder")
    if not RUNTIME_KNOBS["trace"]:
        args.append("-notrace")
    args += ["-coalesce-wait-us", RUNTIME_KNOBS["coalesce_wait_us"]]
    if not RUNTIME_KNOBS["coalesce"]:
        args.append("-nocoalesce")
    if not RUNTIME_KNOBS["overlap_exec"]:
        args.append("-nooverlapexec")
    args += ["-q1", RUNTIME_KNOBS["q1"], "-q2", RUNTIME_KNOBS["q2"]]
    args += ["-snap-every", RUNTIME_KNOBS["snap_every_bytes"]]
    if not RUNTIME_KNOBS["snapshots"]:
        args.append("-nosnap")
    return args


@contextlib.contextmanager
def _knobs(**over):
    """Temporarily override RUNTIME_KNOBS entries — the paired-A/B
    legs flip coalesce/overlap_exec without touching the environment
    (every record still carries the values it actually ran under)."""
    old = {k: RUNTIME_KNOBS[k] for k in over}
    RUNTIME_KNOBS.update(over)
    try:
        yield
    finally:
        RUNTIME_KNOBS.update(old)


def _client_trace_pow2(serial: bool = False) -> int | None:
    """Client-side sampling exponent matching the cluster's knobs
    (sampling is deterministic on cmd_id, so both sides must use the
    same exponent to see the same commands)."""
    if not RUNTIME_KNOBS["trace"]:
        return None
    return 0 if serial else int(RUNTIME_KNOBS["trace_pow2"])


def _traced_latency(maddr, client_colls: list[dict]) -> dict:
    """The paxtrace record for one leg: cluster TRACESPANS fan-out +
    the driver's own span collections -> full traced latency
    distribution (p50/p90/p99/p999) and the per-stage decomposition
    table (obs/trace.py), embedded in the artifact so the tail story
    is attributable without rerunning the bench."""
    try:
        from minpaxos_tpu.obs.trace import analyze_collections
        from minpaxos_tpu.runtime.master import cluster_tracespans

        resp = cluster_tracespans(maddr)
        colls = [r["trace"] for r in resp.get("replicas", [])
                 if r.get("ok") and isinstance(r.get("trace"), dict)]
        colls += [c for c in client_colls if c]
        table, _, _ = analyze_collections(colls)
        return table
    except Exception as e:  # noqa: BLE001 — obs must not fail a bench
        return {"error": repr(e)[:200]}


def _progress(msg: str) -> None:
    print(f"[bench_tcp] {msg}", file=sys.stderr, flush=True)


def _metrics_snapshot(maddr) -> dict:
    """End-of-run paxmon snapshot through the master's stats fan-out:
    dispatch-regime mix, tick-latency histograms and per-replica
    counters ride the artifact, so a number can be decomposed after
    the fact (OBSERVABILITY.md) without rerunning the bench."""
    try:
        from minpaxos_tpu.runtime.master import cluster_stats

        return cluster_stats(maddr)
    except Exception as e:  # noqa: BLE001 — obs must not fail a bench
        return {"error": repr(e)[:200]}


def _boot(proto_flag: str, env, tmp, shape) -> tuple[list, int]:
    n = int(RUNTIME_KNOBS["n_replicas"])
    mport = free_ports(1)[0]
    dports = free_ports(n, sibling_offset=CONTROL_OFFSET)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "minpaxos_tpu.cli.master",
         "-port", str(mport), "-N", str(n)],
        env=env, cwd=tmp, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)]
    time.sleep(1.5)
    for p in dports:
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "minpaxos_tpu.cli.server",
             proto_flag, "-durable", "-port", str(p),
             "-mport", str(mport), *shape,
             "-storedir", str(tmp)],
            env=env, cwd=tmp, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))
    return procs, mport


@contextlib.contextmanager
def _cluster(proto_flag: str, shape, keyhint: int = 100000,
             trace_pow2: str | None = None):
    """Boot master + 3 servers with a fresh store dir; yield the master
    address; tear everything down (SIGTERM, then kill) and wipe the
    stores on exit — the one copy of the lifecycle both the throughput
    and serial legs use. ``keyhint``: the workload's distinct-key
    count, forwarded so servers log projected KV load at boot.
    ``trace_pow2`` overrides the paxtrace sampling knob (the serial
    leg traces every command)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    tmp = REPO / ".bench_tcp_store"
    tmp.mkdir(exist_ok=True)
    for f in tmp.glob("stable-store-replica*"):
        f.unlink()
    procs, mport = _boot(proto_flag, env, tmp,
                         list(shape) + _knob_args(keyhint, trace_pow2))
    try:
        yield ("127.0.0.1", mport)
    finally:
        for p in procs:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
        time.sleep(1.0)
        for p in procs:
            try:
                p.kill()
            except OSError:
                pass
        for f in tmp.glob("stable-store-replica*"):
            f.unlink()


def _connect_client(maddr, deadline_s: float = 90.0):
    from minpaxos_tpu.runtime.client import Client

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            return Client(maddr, check=True)
        except (ConnectionError, OSError, TimeoutError):
            time.sleep(1.0)
    raise RuntimeError("cluster never came up")


def _warm(maddr) -> None:
    """Drive the servers through their first jit compiles."""
    from minpaxos_tpu.runtime.client import gen_workload

    ops, keys, vals = gen_workload(300, seed=1)
    deadline = time.monotonic() + 300
    while True:
        cli = _connect_client(maddr)
        try:
            if cli.run_workload(ops, keys, vals,
                                timeout_s=60)["acked"] == 300:
                return
            _progress("warmup incomplete, retrying")
        except (ConnectionError, OSError, TimeoutError) as e:
            _progress(f"warmup retry ({e!r})")
            time.sleep(2.0)
        finally:
            try:
                cli.close_conn()
            except Exception:
                pass
        if time.monotonic() > deadline:
            raise RuntimeError("warmup never completed")


def run_config(proto_flag: str, label: str, ref_shape: str,
               q: int, k: int, multi_rr: bool = False) -> dict:
    """Boot a fresh 3-replica cluster with ``proto_flag``; measure k
    closed-loop throughput trials (-check) + 200 serial ops; tear
    down. ``multi_rr``: drive throughput with the leaderless
    round-robin MultiClient (reference client.go -e) — the Mencius
    deployment's intended workload: all owners serve concurrently."""
    shape = MENCIUS_SHAPE if multi_rr else SERVER_SHAPE
    with _cluster(proto_flag, shape) as maddr:
        from minpaxos_tpu.runtime.client import (
            Client,
            MultiClient,
            gen_workload,
        )

        _progress(f"{label}: cluster booting")
        _warm(maddr)
        _progress(f"{label}: warm; {k} throughput trials of q={q}")

        ops, keys, vals = gen_workload(q, seed=42)
        tp2 = _client_trace_pow2()
        rates, trial_stats = [], []
        traced = {}
        for t in range(k):
            # fresh connection per trial: fresh reply book, fresh
            # server-side pending set, no cross-trial cmd_id reuse
            drv = (MultiClient(maddr, check=True, mode="rr",
                               trace_pow2=tp2)
                   if multi_rr else Client(maddr, check=True,
                                           trace_pow2=tp2))
            try:
                t0 = time.perf_counter()
                # batch 512 on purpose: 1024 (== SERVER_SHAPE's inbox)
                # measured +14% in-process but went bimodal against
                # real processes — proposals plus ack/catch-up traffic
                # share the inbox, and any overflow drop costs a 3 s
                # retry timeout (subprocess trials split 13.9k best /
                # 2.5k worst); 2048 collapsed outright (12.2k -> 0.7k)
                stats = drv.run_workload(ops, keys, vals, timeout_s=120,
                                         batch=512)
                wall = time.perf_counter() - t0
                if t == k - 1 and tp2 is not None:
                    # span collection for the LAST trial only: rings
                    # keep newest spans, and cross-trial cmd_id reuse
                    # makes per-trial collection the honest window
                    colls = (drv.trace_collect() if multi_rr else
                             [drv.trace_collect()])
                    traced = _traced_latency(maddr, colls)
            finally:
                try:
                    drv.close() if multi_rr else drv.close_conn()
                except Exception:
                    pass
            ok = stats["acked"] == q and stats["duplicates"] == 0
            # rate from ACKED ops, not q: a timed-out trial must not
            # publish throughput for work it never completed
            rates.append(round(stats["acked"] / wall, 1))
            trial_stats.append("ok" if ok else f"FAILED {stats}")
            _progress(f"{label}: trial {t}: {rates[-1]} ops/s"
                      f" ({trial_stats[-1]})")

        metrics_snap = _metrics_snapshot(maddr)

        # the headline median is over CLEAN trials only; if none
        # survived, the record keeps the all-trial median but its
        # "check" field carries every failure, so it cannot read as
        # a green number
        ok_rates = [r for r, s in zip(rates, trial_stats) if s == "ok"]
        return {
            "config": label,
            "client_mode": "rr_all_owners" if multi_rr else "single_conn",
            "ops_per_sec": statistics.median(ok_rates or rates),
            "ops_per_sec_trials": rates,
            "ops_per_sec_spread": [min(rates), max(rates)],
            "check": ("ok" if all(s == "ok" for s in trial_stats)
                      else trial_stats),
            "server_shape": " ".join(shape),
            "runtime_knobs": dict(RUNTIME_KNOBS),
            "reference_shape": ref_shape,
            "metrics_snapshot": metrics_snap,
            # full traced latency distribution (p50/p90/p99/p999 +
            # per-stage decomposition) for the last -check trial —
            # the ISSUE-12 satellite: the artifact carries the whole
            # distribution, not just scalar percentiles
            "traced_latency": traced,
        }


def run_serial(proto_flag: str, label: str) -> dict:
    """Serial-latency leg on its own SERIAL_SHAPE cluster: 200
    one-at-a-time ops with UNIQUE cmd_ids (clientlat shape,
    clientlat/client.go:134-160), failover-robust (a rejection or dead
    socket re-routes instead of crashing the record)."""
    tp2 = _client_trace_pow2(serial=True)
    with _cluster(proto_flag, SERIAL_SHAPE, keyhint=520,
                  trace_pow2="0" if tp2 is not None else None) as maddr:
        from minpaxos_tpu.cli.client import _propose_until_acked
        from minpaxos_tpu.runtime.client import Client

        _progress(f"{label}: serial cluster booting")
        _warm(maddr)
        # the serial leg traces EVERY op (pow2=0): 200 one-at-a-time
        # commands is exactly the sample the tail story needs, and the
        # per-op tracing cost is bounded by the obs_smoke guard
        cli = Client(maddr, check=True, trace_pow2=tp2)
        cli.connect()
        lats = []
        for i in range(200):
            cid = np.asarray([1_000_000 + i])
            t1 = time.perf_counter()
            if _propose_until_acked(cli, cid, np.asarray([1]),
                                    np.asarray([7000 + i]),
                                    np.asarray([i]), timeout_s=10.0):
                lats.append((time.perf_counter() - t1) * 1e3)
        traced = ({} if tp2 is None else
                  _traced_latency(maddr, [cli.trace_collect()]))
        cli.close_conn()
        metrics_snap = _metrics_snapshot(maddr)
        lats.sort()

        def _pct(q):
            return (round(lats[min(int(len(lats) * q), len(lats) - 1)], 3)
                    if lats else None)

        return {
            "serial_p50_ms": _pct(0.50),
            "serial_p99_ms": _pct(0.99),
            # the full client-measured distribution (not just two
            # scalars) + the paxtrace stage decomposition of the same
            # ops — "p99 is X ms" and WHERE those ms went, in one record
            "serial_latency": {"p50_ms": _pct(0.50), "p90_ms": _pct(0.90),
                               "p99_ms": _pct(0.99), "p999_ms": _pct(0.999),
                               "max_ms": _pct(1.0)},
            "serial_traced": traced,
            "n_serial": len(lats),
            "serial_shape": " ".join(SERIAL_SHAPE),
            "runtime_knobs": dict(RUNTIME_KNOBS),
            "serial_metrics_snapshot": metrics_snap,
        }


def _lat_pcts(lats_sorted: list) -> dict:
    """p50/p90/p99/p999/max from an already-sorted ms list (the swarm
    leg's full-distribution report — same keys as serial_latency)."""

    def _pct(q):
        return (round(lats_sorted[min(int(len(lats_sorted) * q),
                                      len(lats_sorted) - 1)], 3)
                if lats_sorted else None)

    return {"p50_ms": _pct(0.50), "p90_ms": _pct(0.90),
            "p99_ms": _pct(0.99), "p999_ms": _pct(0.999),
            "max_ms": _pct(1.0)}


def run_swarm(proto_flag: str, label: str, sessions: int,
              ops_per_session: int = 20,
              timeout_s: float = 180.0) -> dict:
    """Concurrent-client leg: ``sessions`` closed-loop TCP sessions
    through the ingress coalescer (runtime/client.py ClientSwarm),
    reporting the full per-command latency distribution, the paxtrace
    stage table, and the coalescer/admission tallies. Overload is
    expected to degrade to bounded queueing + retransmit (the
    admission gate keyed off exec backlog and the paxwatch burn-rate
    detector), so ``retransmits``/``rejects`` are part of the record,
    not failures."""
    with _cluster(proto_flag, SERVER_SHAPE) as maddr:
        from minpaxos_tpu.runtime.client import ClientSwarm, gen_workload

        _progress(f"{label}: cluster booting")
        _warm(maddr)
        n = sessions * ops_per_session
        ops, keys, vals = gen_workload(n, seed=7)
        tp2 = _client_trace_pow2()
        _progress(f"{label}: warm; {sessions} sessions x "
                  f"{ops_per_session} ops")
        swarm = ClientSwarm(maddr, sessions=sessions, trace_pow2=tp2)
        try:
            res = swarm.run(ops, keys, vals, ops_per_session,
                            timeout_s=timeout_s)
            traced = ({} if tp2 is None else
                      _traced_latency(maddr, [swarm.trace_collect()]))
        finally:
            swarm.close()
        metrics_snap = _metrics_snapshot(maddr)
        lats = res.pop("lat_ms_sorted")
        res.update({
            "config": label,
            "latency": _lat_pcts(lats),
            "traced_latency": traced,
            "server_shape": " ".join(SERVER_SHAPE),
            "runtime_knobs": dict(RUNTIME_KNOBS),
            "metrics_snapshot": metrics_snap,
        })
        _progress(f"{label}: {res['acked']}/{res['sent']} acked, "
                  f"p50 {res['latency']['p50_ms']} ms, "
                  f"p99 {res['latency']['p99_ms']} ms, "
                  f"{res['retransmits']} retransmits")
        return res


def main() -> None:
    q = int(os.environ.get("BENCH_TCP_Q", "20000"))
    k = int(os.environ.get("BENCH_TCP_K", "5"))
    out_path = REPO / "BENCH_TCP.json"
    # opportunistic native build: every server/client process then
    # loads the C++ frame scan off disk (pure-Python fallback if no g++)
    from minpaxos_tpu.native.build import try_build

    try_build()

    rec = run_config(
        "-min", "bareminpaxos_tcp_3rep_durable (BASELINE config 1)",
        "bareminrun.sh:16-21 + simpletest.sh:1", q, k)
    # persist the headline immediately: an abort during the minutes-long
    # later legs (Ctrl-C, SIGTERM) must not discard a finished run
    out_path.write_text(json.dumps(rec) + "\n")
    try:
        rec.update(run_serial("-min", "bareminpaxos serial"))
    except Exception as e:  # noqa: BLE001
        rec["serial_error"] = repr(e)[:200]
    out_path.write_text(json.dumps(rec) + "\n")
    # paired A/B (ISSUE 15): the headline serial leg above ran with
    # the event-driven ingress ON (production knobs); this leg is the
    # SAME shape, same host, coalescer+overlapped-exec forced OFF —
    # the cadence-driven before. Skip with BENCH_TCP_AB=0.
    if os.environ.get("BENCH_TCP_AB", "1") != "0":
        try:
            with _knobs(coalesce=False, overlap_exec=False):
                rec["serial_cadence_baseline"] = run_serial(
                    "-min", "bareminpaxos serial (coalesce+overlap OFF)")
        except Exception as e:  # noqa: BLE001
            rec["serial_cadence_baseline"] = {"error": repr(e)[:200]}
        out_path.write_text(json.dumps(rec) + "\n")
    # flexible-quorum paired A/B (ISSUE 16): two serial legs at N=5,
    # same shape, same host, interleaved in one run — simple majority
    # (q1=q2=3) vs the certified (q1=4, q2=2) ledger point. A commit
    # barrier at q2=2 waits for ONE follower ack instead of two, so
    # the traced <commit> stage p99 is the claim (tools/tail.py
    # renders the stage tables). Skip with BENCH_TCP_FLEX=0.
    if os.environ.get("BENCH_TCP_FLEX", "1") != "0":
        ab = {}
        for leg, kn in (("majority_q2_3", {"n_replicas": "5"}),
                        ("flex_q1_4_q2_2", {"n_replicas": "5",
                                            "q1": "4", "q2": "2"})):
            try:
                with _knobs(**kn):
                    ab[leg] = run_serial("-min", f"serial N=5 {leg}")
            except Exception as e:  # noqa: BLE001
                ab[leg] = {"error": repr(e)[:200]}
        ab["commit_p99_ms"] = {
            leg: (ab[leg].get("serial_traced") or {})
            .get("stages", {}).get("commit", {}).get("p99")
            for leg in ("majority_q2_3", "flex_q1_4_q2_2")}
        rec["flex_quorum_ab"] = ab
        out_path.write_text(json.dumps(rec) + "\n")
    # concurrent-client leg through the coalescer (BENCH_TCP_SWARM
    # sessions; 0 skips — CI runs 64, the full bench 256, the slow
    # suite 1024)
    swarm_n = int(os.environ.get("BENCH_TCP_SWARM", "256"))
    if swarm_n > 0:
        try:
            rec["swarm"] = run_swarm(
                "-min", f"swarm_{swarm_n}_sessions", swarm_n,
                ops_per_session=int(
                    os.environ.get("BENCH_TCP_SWARM_OPS", "20")))
        except Exception as e:  # noqa: BLE001
            rec["swarm"] = {"error": repr(e)[:200]}
        out_path.write_text(json.dumps(rec) + "\n")
    try:
        rec["mencius_tcp"] = run_config(
            "-m", "mencius_tcp_3rep_durable (beyond reference: its "
            "server never shipped mencius)",
            "mencius.go:83-897 over the bareminrun.sh topology", q, k,
            multi_rr=True)
    except Exception as e:  # noqa: BLE001 — config 1 is the headline
        rec["mencius_tcp"] = {"error": repr(e)[:200]}
    # persist the finished throughput leg before the serial leg: a
    # serial-cluster warmup failure must not discard the 10-minute run
    out_path.write_text(json.dumps(rec) + "\n")
    if "error" not in rec["mencius_tcp"]:
        try:
            rec["mencius_tcp"].update(run_serial("-m", "mencius serial"))
        except Exception as e:  # noqa: BLE001
            rec["mencius_tcp"]["serial_error"] = repr(e)[:200]
    out_path.write_text(json.dumps(rec) + "\n")
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
