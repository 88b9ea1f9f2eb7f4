"""Headline benchmark: batched sharded-Paxos commit throughput + quorum
decision latency on one chip, at the north-star shape (>= 1M concurrent
instances, N=5, f=2), with a kill/recover fault leg.

Design (round 3): protocol rounds are FUSED — ``sharded_run`` executes k
rounds per dispatch inside one ``lax.scan`` with device-generated
proposals, recording per-round (committed_upto, crt_inst) cursor
histories as scan outputs. One dispatch therefore costs one host round
trip for k rounds of protocol, so the record reports device throughput
instead of dispatch latency.

Round 6, PR 8: the measured loop is DEVICE-RESIDENT by default
(``sharded_run_resident``): workload rows come from the counter-based
on-device generator (ops/workload.py, Threefry keyed on seed x round x
shard), round state and latency bookkeeping live in donated buffers,
and each measured dispatch reads back only two scalars (committed
frontier + in-flight count) — per-slot quorum latency accumulates in
an on-device histogram read once after the measured window, so the
steady state performs zero per-round host->device transfers.
``BENCH_RESIDENT=0`` restores the host-in-the-loop legacy phases
(per-dispatch [k, G] cursor-history readback + host-side latency
reconstruction) for A/B; both paths draw the same proposal stream, so
their committed results are identical at a pinned shape
(tests/test_workload.py). ``--ladder`` sweeps
tools/shape_ladder.py's (shards x window x proposals x k) grid first
and measures at the throughput-optimal point instead of the
hand-picked shape; the sweep and winner land in the artifact.

Reported timing is split honestly:
* ``device_ms_per_round`` — median dispatch wall / k (the chip's rate);
* ``dispatch_overhead_ms`` — wall of a k=1 dispatch minus one round at
  the fused rate (the host tax the fusion amortizes);
* latency percentiles are measured in ROUNDS from the cursor histories
  (slot injected at round t_in, committed at round t_c — exact, per
  slot) and converted to ms at the fused per-round rate. The drain
  phase runs until the log is fully committed, so late-injected slots
  are not censored from the tail.

Fault leg (BASELINE config 5): mid-measurement one follower is masked
dead for ``dead_dispatches`` dispatches, then revived; the record
reports the throughput dip and the rounds-to-reheal (revived replica's
min frontier catching the leader's frontier at revive time).

Round 6, PR 9 (paxray): the resident loop is observable again —
``BENCH_TELEMETRY=1`` (default) arms an on-device telemetry ring (one
row per round: committed delta, in-flight, injected/inbox/claim rows,
election flag) read back once after the measured window; ``--trace
out.json`` merges the per-dispatch host walls with the device rounds
into one validated Perfetto file; ``--xprof DIR`` is the CLI alias
for ``MP_BENCH_PROFILE`` (jax.profiler capture around the measured
phase). Per-substep cost attribution lives in
``tools/profile_substeps.py``.

ONE process: ``measure()`` runs on the backend JAX finds, and a record
always names it (``platform``, ``device_kind``, ``device_count``). It
runs on a TPU, or on the CPU when ``JAX_PLATFORMS=cpu`` says so
explicitly (tiny shape, for debugging the harness — a CPU timing is
not a device metric). Anything else — no chip, an exception anywhere in
the run, a mesh that cannot be built — exits non-zero and prints no
record. ``MP_BENCH_CHILD="g,w,p,k"`` selects the shape.

The reference publishes no numbers (BASELINE.md), so ``vs_baseline``
is against the driver's north star: 1M concurrent instances at <10ms
p50 on a v5e-8 == 12.5M committed inst/s/chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import os
import json
import subprocess
import sys
import time


# MP_BENCH_SUBSTEPS=2 appends a drain-only delivery sub-step per fused
# round: commits land in fewer rounds (commit-on-quorum in the round
# the quorum forms) at ~1.5-2x the round wall. SHAPE-DEPENDENT on the
# CPU mesh: at the headline shape (g=8, w=4096, p=512) quorum p50
# measured 2134 -> 1640 ms wall (-23%) with commits +5%, but at the
# small reference shape it LOST both ways (p50 50 -> 75 ms,
# throughput 31k -> 14k inst/s). Default 1; the record carries the
# value used, so any substeps>1 number is labeled as such.
SS_N = int(os.environ.get("MP_BENCH_SUBSTEPS", "1"))

# BENCH_RESIDENT=0 restores the host-in-the-loop measured phases
# (per-dispatch [k, G] cursor-history readback + host latency
# reconstruction — the PR-7 loop, verbatim) for A/B against the
# device-resident default. Both loops draw the identical proposal
# stream (ops/workload.py), so committed results match byte-for-byte
# at a pinned shape; only the loop structure differs.
RESIDENT = os.environ.get("BENCH_RESIDENT", "1") != "0"

# workload PRNG base key — the whole proposal stream is a pure
# function of (seed, round), bit-reproducible across runs/hosts
WORKLOAD_SEED = int(os.environ.get("MP_BENCH_SEED", "0"))

# BENCH_TELEMETRY=0 disables the paxray on-device telemetry ring
# (ISSUE 9): with it on (default), the resident scan accumulates one
# int32 row per round (committed delta, in-flight, injected/inbox/
# claim rows, election-vs-steady flag — obs/recorder.py layout) in a
# donated device buffer read back ONCE after the measured window, so
# the two-scalars-per-dispatch residency contract is untouched.
# Telemetry never writes protocol state — committed results are
# byte-identical on/off (tests/test_paxray.py) and the dispatch wall
# must agree within 2% (tools/obs_smoke.py --resident gate).
TELEMETRY = os.environ.get("BENCH_TELEMETRY", "1") != "0"


def _progress(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


NORTH_STAR_PER_CHIP = 100_000_000 / 8  # 1M inst / 10ms / 8 chips


def _emit(result: dict) -> None:
    print(json.dumps(result))


def _die(stage: str, err: str) -> None:
    """No record on failure: say why on stderr, exit non-zero."""
    print(f"[bench] FAILED {stage}: {err}", file=sys.stderr, flush=True)
    sys.exit(1)


def _latency_rounds(uptos, crts, round_ms):
    """Per-slot quorum-decision latency from cursor histories.

    uptos/crts: [T, G] leader cursors AFTER each round (round r is row
    r). Slot s of shard sh is injected during the round t_in where crt
    first exceeds s, and committed during the round t_c where upto
    first reaches s. Latency = (t_c - t_in + 1) rounds (inject + commit
    in the same round = 1 round), converted to ms at the fused rate.
    Only slots committed by the end are counted — the caller drains the
    log so that is ALL injected slots (no tail censoring)."""
    import numpy as np

    T, G = uptos.shape
    lats = []
    # slots assigned but never committed by the end of the run (drain
    # cap hit): these are the SLOWEST slots and are necessarily absent
    # from the sample, so report their count instead of pretending the
    # tail is complete
    uncommitted = int(np.maximum(crts[-1] - 1 - uptos[-1], 0).sum())
    for sh in range(G):
        first = int(crts[0, sh])  # assigned before measurement began
        last = int(uptos[-1, sh])
        slots = np.arange(first, last + 1)
        if len(slots) == 0:
            continue
        t_in = np.searchsorted(crts[:, sh], slots, side="right")
        t_c = np.searchsorted(uptos[:, sh], slots, side="left")
        ok = (t_in < T) & (t_c < T)
        lats.append((t_c[ok] - t_in[ok] + 1).astype(np.float64))
    if not lats:
        return float("nan"), float("nan"), 0, uncommitted
    lat = np.concatenate(lats) * round_ms
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 99)),
            int(lat.size), uncommitted)


def cpu_catchup_rows(p: int, fault: bool) -> int:
    """CPU catch-up sizing, the ONE definition bench.py and
    tools/shape_ladder.py share (a silent divergence would re-measure
    a ladder winner at a different inbox shape than the one that won
    the sweep). Fault-viable sizing must OUTPACE the live commit
    stream while a revived victim's frontier is pinned at its hole
    (measured: cu >= 2p reheals, cu <= p/2 never does — PERF.md);
    throughput shapes skip the fault leg and use economy sizing
    (inbox rows cost ~50 us/row/round on the measured host)."""
    return max(64, min(512, 2 * p)) if fault else max(32, min(256, p // 4))


def cpu_key_space(p: int) -> int:
    """Workload key-space sizing for CPU shapes, shared with the shape
    ladder: the smallest power of two >= max(256, p). The stride-walk
    key schedule (ops/workload.py) is duplicate-free within a round
    only while rows <= key_space — an undersized space at big p would
    re-introduce the KV claim-loop serialization the generator exists
    to avoid, and would do it unevenly across ladder points, crowning
    the wrong winner."""
    return 1 << max(8, (p - 1).bit_length())


def cpu_kv_pow2(p: int) -> int:
    """KV capacity to go with ``cpu_key_space``: 4x the key space, the
    same saturation headroom the fixed (2^8 keys, 2^10 table) CPU
    default always had."""
    return max(10, (cpu_key_space(p) - 1).bit_length() + 2)


def overflow_warning(overflow: int) -> str | None:
    """The loud-stdout message for a saturated latency histogram
    (None when clean). A nonzero overflow bin means the tail was
    CLIPPED: every slot slower than the histogram range was counted
    at the last bin, so the reported percentiles understate the true
    tail — a record whose stamp alone carried this got trusted once
    too often. Printed to STDOUT next to the JSON record (consumers
    filter on lines starting with '{', so the warning can't corrupt
    parsing) and echoed to stderr progress."""
    if not overflow:
        return None
    return (f"WARNING: latency_hist_overflow={overflow} — {overflow} "
            f"committed slots exceeded the histogram range; the "
            f"reported p50/p99 come from a SATURATED histogram and "
            f"understate the true tail. Raise lat_bins or shrink the "
            f"measured window.")


def _latency_from_hist(hist, round_ms):
    """Exact percentiles from the device-accumulated round-latency
    histogram (resident loop). Latencies are integers in ROUNDS (bin b
    = b+1 rounds), so the full per-slot sample is reconstructible with
    ``np.repeat`` and the percentiles match ``_latency_rounds`` on the
    same run bit-for-bit (pinned by tests/test_workload.py). Returns
    (p50_ms, p99_ms, n_samples, overflow_count) — overflow is the last
    bin's population (latency >= LATENCY_BINS rounds), reported so a
    clipped tail can never silently pass as a complete sample."""
    import numpy as np

    n = int(hist.sum())
    overflow = int(hist[-1])
    if n == 0:
        return float("nan"), float("nan"), 0, overflow
    if n <= (1 << 22):
        # reconstruct the sample outright: matches np.percentile of
        # the host path to the bit (the equivalence tests' contract)
        lat = np.repeat(np.arange(1, hist.size + 1, dtype=np.int64),
                        hist) * round_ms
        return (float(np.percentile(lat, 50)),
                float(np.percentile(lat, 99)), n, overflow)
    # at accelerator scale (north-star runs commit tens of millions)
    # materializing the sample is hundreds of MB — take the exact
    # order statistics from the cumulative counts instead. Latencies
    # are integers, so sample[i] is just the first bin whose cumsum
    # exceeds i; linear interpolation between the two bracketing
    # order statistics mirrors np.percentile's default.
    cum = np.cumsum(hist.astype(np.int64))

    def pct(q):
        pos = (n - 1) * q / 100.0
        lo, hi = int(np.floor(pos)), int(np.ceil(pos))
        v_lo = (int(np.searchsorted(cum, lo, side="right")) + 1) * round_ms
        v_hi = (int(np.searchsorted(cum, hi, side="right")) + 1) * round_ms
        return float(v_lo + (v_hi - v_lo) * (pos - lo))

    return pct(50), pct(99), n, overflow


def _side_config(cfg, g, p, k, protocol, dispatches=2):
    """One BASELINE side config: small fused run, returns a record.

    configs 2-4 (BASELINE.md): classic paxos sequential / classic paxos
    64k concurrent / mencius 64k. Each uses the same fused runner as
    the headline so the numbers are comparable."""
    import numpy as np

    from minpaxos_tpu.parallel.sharded import ShardedCluster, shard_cursors

    # key_space at half KV capacity: same saturation guard as the
    # headline (long runs would otherwise fill the table mid-measure)
    sc = ShardedCluster(cfg, g, ext_rows=max(p, 1), protocol=protocol,
                        key_space=1 << (cfg.kv_pow2 - 1))
    if protocol != "mencius":
        sc.elect(0)
    sc.run_fused(k, p, substeps=SS_N)  # compile + warm
    start = sc.committed()[0]
    u0, c0 = shard_cursors(cfg, max(sc.leader, 0), sc.ss)
    # pre-phase cursor row: without it round-1 injections are censored
    U, C = [np.asarray(u0)[None].copy()], [np.asarray(c0)[None].copy()]
    t0 = time.perf_counter()
    for _ in range(dispatches):
        u, c = sc.run_fused(k, p, substeps=SS_N)
        U.append(u)
        C.append(c)
    wall = time.perf_counter() - t0
    committed = sc.committed()[0] - start
    rounds = dispatches * k
    round_ms = wall / rounds * 1e3
    # drain so the slowest (late-injected) slots enter the sample
    drain_rounds = 0
    for _ in range(6):
        u, c = sc.run_fused(k, 0, substeps=SS_N)
        U.append(u)
        C.append(c)
        drain_rounds += k
        if (u[-1] >= c[-1] - 1).all():
            break
    p50, p99, n_lat, unc = _latency_rounds(
        np.concatenate(U), np.concatenate(C), round_ms)
    return {
        "protocol": protocol if protocol == "mencius" else (
            "paxos" if cfg.explicit_commit else "minpaxos"),
        "throughput_inst_per_sec": round(committed / wall, 1),
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "latency_samples": n_lat,
        "uncommitted_after_drain": unc,
        "drain_rounds": drain_rounds,
        "concurrent_instances": g * cfg.window,
        "proposals_per_round": g * p * (cfg.n_replicas
                                        if protocol == "mencius" else 1),
        "rounds": rounds,
        "device_ms_per_round": round(round_ms, 3),
    }


#: (g, w, p, k) — g shards x w-slot windows = concurrent instances
#: resident on the device. The on-chip shape is the north star's
#: 1,048,576 concurrent instances; the CPU shape is a harness check.
TPU_SHAPE = (256, 4096, 512, 32)
CPU_SHAPE = (8, 512, 64, 8)


def side_shapes(on_tpu: bool) -> dict:
    """BASELINE side configs 2-4 as ``name -> (cfg, shards, proposals
    per round [per owner under mencius], rounds per dispatch,
    protocol)`` — the one statement of them the program has (the
    benchmark's ``mencius5_pod_64k`` is held to the last)."""
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig
    from minpaxos_tpu.models.paxos import classic_config

    return {
        # cfg2: classic paxos, 1 client, sequential instances
        # (1 proposal per round — pipelined-sequential)
        "paxos_sequential": (
            classic_config(n_replicas=5, window=1024, inbox=256,
                           exec_batch=32, kv_pow2=12,
                           catchup_rows=32, recovery_rows=32),
            1, 1, 128 if on_tpu else 32, "classic"),
        # cfg3: classic paxos, 16 clients (=16 shards), 64k
        # concurrent instances (inbox: p + appendices — acks are
        # run-length compressed)
        "paxos_64k": (
            classic_config(n_replicas=5, window=4096,
                           inbox=256 + 2 * 64 + 128, exec_batch=256,
                           kv_pow2=14, catchup_rows=64,
                           recovery_rows=64),
            16, 256, 32 if on_tpu else 8, "classic"),
        # cfg4: mencius, 5 rotating owners, 64k instances
        # catchup_rows = the per-step COMMIT-broadcast chunk in the
        # mencius kernel; must exceed the per-owner proposal rate
        # (64/round) or the frontier can never drain its backlog
        "mencius_64k": (
            MinPaxosConfig(n_replicas=5, window=4096,
                           inbox=2048, exec_batch=320,
                           kv_pow2=14, catchup_rows=128,
                           recovery_rows=64, noop_delay=8),
            16, 64, 32 if on_tpu else 8, "mencius"),
    }


def headline_config(on_tpu: bool, w: int, p: int, do_fault: bool = True,
                    inbox: int = 0, q1: int = 0, q2: int = 0):
    """(cfg, key_space) of the headline MinPaxos N=5 run at window
    ``w`` and ``p`` proposals per round — the ONE definition bench.py
    and chip_smoke.py share.

    KV capacity is 4x the workload key_space on both platforms (2^16
    entries vs 16k keys on the chip). The greedy two-choice table has
    no relocation, so 2x headroom was not enough: at 2^15 the first
    checked runs at g=256 (PR 21, chip and CPU) lost inserts — 16 in 3
    of 256 shards (kv.dropped: acknowledged writes missing from the table),
    which nothing in this file had ever looked at. The KV is the
    dominant allocation (~1.7 of ~1.95 GiB resident at g=256).

    Inbox sizing (round 4): acks are run-length compressed in the
    kernel, so a follower's inbox holds p ACCEPT rows plus the
    catch-up/retry/sweep appendices (2*catchup + recovery + gossip),
    and the leader's holds ~R compressed ack rows. Every [M]-shaped
    step computation and routed array shrinks with it.

    Catch-up sizing (PR 8, measured): while a revived victim still
    has a hole, its commit FRONTIER is pinned at the hole, so catch-up
    must outpace the live commit stream, not just clear the gap —
    empirically cu >= 2p reheals in ~one dispatch and cu <= p/2 never
    reheals: the leader serves one peer per round, so the hole closes
    at ~cu/2 per round while the retained window (w//2 slots) slides
    away from it at p per round. The on-chip sizing was a flat 512,
    which is 2p at the old p=256 rung and only 1p at p=512 — there the
    victim reached the leader's frontier-at-revive and then froze
    behind the window for good (first checked run, PR 21; the record's
    ``recover_rounds_upper_bound`` cannot see that). On the CPU, when
    the fault leg is OFF (ladder-chosen throughput shapes), cu drops
    to economy sizing instead.

    ``inbox`` (PR 11) and ``q1``/``q2`` (PR 16): a --ladder winner
    may carry an occupancy-derived inbox capacity and a non-default
    quorum pair; 0 = the default sizing / majority."""
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig

    cu_rows = max(512, 2 * p) if on_tpu else cpu_catchup_rows(p, do_fault)
    cfg = MinPaxosConfig(
        n_replicas=5, window=w, inbox=inbox or (p + 2 * cu_rows + 64 + 64),
        exec_batch=p, kv_pow2=16 if on_tpu else cpu_kv_pow2(p),
        catchup_rows=cu_rows, recovery_rows=64, q1=q1, q2=q2)
    return cfg, (1 << 14) if on_tpu else cpu_key_space(p)


def measure(shape: tuple[int, int, int, int] | None = None,
            ladder: dict | None = None) -> None:
    """One full measurement pass (headline + fault leg + side configs)
    at the given (g, w, p, k) shape — default: TPU_SHAPE on a TPU,
    CPU_SHAPE on the CPU — emitting the JSON record. ``ladder`` is the
    ``--ladder`` mode's sweep record, stamped into the artifact. Any
    exception propagates: a failed run exits non-zero with no record.
    """
    import jax
    import numpy as np

    from minpaxos_tpu.models.minpaxos import MinPaxosConfig
    from minpaxos_tpu.parallel.sharded import (
        DONATION,
        ShardedCluster,
        shard_cursors,
    )

    devices = jax.devices()
    platform = devices[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and not (platform == "cpu"
                           and os.environ.get("JAX_PLATFORMS") == "cpu"):
        _die("backend", f"found platform {platform!r}; bench.py runs on "
             f"a tpu, or on the cpu only when JAX_PLATFORMS=cpu asks "
             f"for it explicitly")
    # k_dead: rounds the victim stays masked dead (ONE small fused
    # dispatch). Pod-mode healing serves from the leader's retained
    # window (retention = w//2 slots); the dead gap k_dead*p must stay
    # below it (here 2*512 = 1024 < 2048) or the victim can never
    # reheal on-device (beyond-retention resync is the TCP runtime's
    # stable-store path, exercised in tests/test_distributed.py).
    g, w, p, k = shape or (TPU_SHAPE if on_tpu else CPU_SHAPE)
    healthy_d, k_dead, rec_d = (4, 2, 2) if shape or on_tpu else (2, 2, 2)
    do_fault = os.environ.get("MP_BENCH_FAULT", "1") != "0"
    # --ladder winners thread their capacity / quorum pair to this
    # process via env exactly like the shape, so the measured record
    # runs what won the sweep
    cfg, key_space = headline_config(
        on_tpu, w, p, do_fault,
        inbox=int(os.environ.get("MP_BENCH_INBOX", "0") or 0),
        q1=int(os.environ.get("MP_BENCH_Q1", "0") or 0),
        q2=int(os.environ.get("MP_BENCH_Q2", "0") or 0))
    cu_rows = cfg.catchup_rows
    t_boot = time.perf_counter()
    # --ladder winners may mesh the shard axis over virtual CPU
    # devices (the sweep measured them that way); default 1 = the
    # classic single-device layout. A mesh that cannot be built is an
    # error: the record must never stamp a layout the run did not use.
    shard_devices = int(os.environ.get("MP_BENCH_SHARD_DEVICES", "1"))
    mesh = None
    if shard_devices > 1:
        if len(devices) < shard_devices:
            _die("mesh", f"MP_BENCH_SHARD_DEVICES={shard_devices} but "
                 f"only {len(devices)} {platform} device(s) are visible")
        from minpaxos_tpu.parallel import make_mesh

        mesh = make_mesh(n_shard_devices=shard_devices,
                         n_replica_devices=1)
    sc = ShardedCluster(cfg, g, ext_rows=p, mesh=mesh,
                        key_space=key_space, seed=WORKLOAD_SEED)
    _progress(f"init {time.perf_counter() - t_boot:.1f}s")
    sc.elect(0)
    _progress(f"elect {time.perf_counter() - t_boot:.1f}s")

    # -- warmup / compile (k, k_dead and k=1 variants of whichever
    # loop this run measures) --
    # paxray telemetry ring capacity: every round the measured
    # window can run (healthy + dead + recovery + full drain
    # budget), so the post-window readback never wraps. Sized at
    # warmup too: the telemetry buffer's shape is part of the
    # compiled dispatch, and the measured phase must reuse the
    # warmed compilation.
    tel_cap = ((healthy_d + rec_d + 8) * k + k_dead + 8) if TELEMETRY \
        else 0
    if RESIDENT:
        sc.begin_resident(telemetry_rounds=tel_cap)
        sc.run_resident(k, p, substeps=SS_N)
        sc.run_resident(k_dead, p, substeps=SS_N)
        sc.run_resident(1, p, substeps=SS_N)
    else:
        sc.run_fused(k, p, substeps=SS_N)
        sc.run_fused(k_dead, p, substeps=SS_N)
        sc.run_fused(1, p, substeps=SS_N)
    _progress(f"warmup/compile {time.perf_counter() - t_boot:.1f}s")

    # -- dispatch overhead probe: k=1 dispatches, blocked --
    t0 = time.perf_counter()
    for _ in range(3):
        if RESIDENT:
            sc.run_resident(1, p, substeps=SS_N)  # scalar read blocks
        else:
            sc.run_fused(1, p, substeps=SS_N)  # np.asarray blocks
    k1_ms = (time.perf_counter() - t0) / 3 * 1e3

    # -- optional device profile: MP_BENCH_PROFILE=<dir> wraps the
    # measured phase in a jax.profiler trace so device compute can
    # be split from the host's dispatch tax offline --
    import contextlib

    prof_dir = os.environ.get("MP_BENCH_PROFILE")
    prof_cm = (jax.profiler.trace(prof_dir) if prof_dir
               else contextlib.nullcontext())

    # paxmon registry for the bench itself (obs/metrics.py): the
    # artifact carries a typed end-of-run snapshot — dispatch
    # walls as a histogram next to the medians, so a skewed run
    # (one 30 s straggler dispatch) is visible in the record
    from minpaxos_tpu.obs.metrics import MetricsRegistry

    mx = MetricsRegistry(namespace="bench")
    mx_disp = mx.counter("dispatches")
    mx_rounds = mx.counter("rounds")
    mx_committed = mx.gauge("committed_healthy")
    mx_wall = mx.histogram(
        "dispatch_wall_ms",
        bounds=(50.0, 100.0, 200.0, 500.0, 1000.0, 2000.0, 5000.0,
                15000.0, 60000.0))

    # -- unified timeline capture (--trace / MP_BENCH_TRACE,
    # paxray): per-dispatch monotonic_ns walls + a host flight
    # recorder row per dispatch, so the post-window telemetry
    # readback can be rendered as device-round slices on the SAME
    # clock the TCP runtime's recorder stamps — one merged,
    # validated Perfetto file. Two clock reads per dispatch; the
    # resident path itself is untouched.
    trace_path = os.environ.get("MP_BENCH_TRACE")
    disp_log: list = []
    host_rec = None
    if trace_path:
        from minpaxos_tpu.obs.recorder import KIND_FUSED, FlightRecorder

        host_rec = FlightRecorder(4096)

    def _run_res(k_r: int, p_r: int):
        r0 = sc._seed
        t0 = time.monotonic_ns()
        c, f = sc.run_resident(k_r, p_r, substeps=SS_N)
        t1 = time.monotonic_ns()
        disp_log.append({"t0_ns": t0, "t1_ns": t1, "round0": r0,
                         "k": k_r})
        if host_rec is not None:
            host_rec.record(
                t1, KIND_FUSED, k_r, rows_in=g * p_r * k_r,
                rows_out=0, frontier=c, backlog=f, drain_us=0,
                enqueue_us=0, readback_us=(t1 - t0) // 1000,
                overlap_us=0, persist_us=0, dispatch_us=0,
                reply_us=0, t_rb_ns=t1)
        return c, f

    # -- measured phase 1: healthy, healthy_d fused dispatches --
    start_committed, _, _ = sc.committed()
    U, C = [], []
    if RESIDENT:
        # fresh bookkeeping: warmup-injected slots are excluded
        # from the latency sample exactly as the legacy path's
        # pre-phase cursor row excludes them
        sc.begin_resident(telemetry_rounds=tel_cap)
        committed_cursor = start_committed
    else:
        u0, c0 = shard_cursors(cfg, sc.leader, sc.ss)
        # pre-phase cursor row so round-1 injections aren't censored
        U, C = [np.asarray(u0)[None].copy()], [np.asarray(c0)[None].copy()]
    walls = [time.perf_counter()]
    with prof_cm:
        for i in range(healthy_d):
            if RESIDENT:
                # back-to-back dispatches; the only per-dispatch
                # host sync is the two-scalar cursor readback
                committed_cursor, _ = _run_res(k, p)
            else:
                u, c = sc.run_fused(k, p, substeps=SS_N)
                U.append(u)
                C.append(c)
            walls.append(time.perf_counter())
            mx_disp.inc()
            mx_rounds.inc(k)
            mx_wall.observe((walls[-1] - walls[-2]) * 1e3)
            _progress(f"healthy dispatch {i}: "
                      f"{(walls[-1] - walls[-2]) * 1e3:.0f}ms / {k} rounds")
    healthy_wall = walls[-1] - walls[0]
    healthy_rounds = healthy_d * k
    if RESIDENT:
        committed_healthy = committed_cursor - start_committed
    else:
        committed_healthy = int((U[-1][-1] + 1).sum()) - start_committed
    mx_committed.set(committed_healthy)
    throughput = committed_healthy / healthy_wall
    round_ms = healthy_wall / healthy_rounds * 1e3

    # -- fault leg: kill follower 2 (not the leader: BASELINE
    # config-5's checklog shape), run dead, revive, recover.
    # MP_BENCH_FAULT=0 skips it (--ladder throughput shapes use
    # economy catch-up sizing that cannot reheal); the record labels
    # what ran. --
    if do_fault:
        victim = 2
        sc.kill(victim)
        t0 = time.perf_counter()
        DU, DC = [], []
        if RESIDENT:
            cd, _ = _run_res(k_dead, p)
            committed_dead = cd - committed_cursor
            committed_cursor = cd
        else:
            du, dc = sc.run_fused(k_dead, p, substeps=SS_N)
            DU, DC = [du], [dc]
            committed_dead = int((DU[-1][-1] + 1).sum()) - int(
                (U[-1][-1] + 1).sum())
        dead_wall = time.perf_counter() - t0
        # the dead phase is one SHORT dispatch, so per-dispatch
        # overhead (measured via the k=1 probe) would
        # dominate its wall and masquerade as fault impact —
        # subtract it so dip_pct reports the kill, not the
        # dispatch tax
        overhead_s = max(k1_ms - round_ms, 0.0) / 1e3
        dead_throughput = committed_dead / max(
            dead_wall - overhead_s, 1e-6)
        if RESIDENT:
            # one [G] read between phases — fault-leg diagnostics,
            # not the measured steady state
            lu, _ = shard_cursors(cfg, sc.leader, sc.ss)
            leader_frontier_at_revive = np.asarray(lu).copy()
        else:
            leader_frontier_at_revive = DU[-1][-1].copy()
        sc.revive(victim)
        recover_rounds = None
        RU, RC = [], []
        t0 = time.perf_counter()
        for d in range(rec_d):
            if RESIDENT:
                committed_cursor, _ = _run_res(k, p)
            else:
                u, c = sc.run_fused(k, p, substeps=SS_N)
                RU.append(u)
                RC.append(c)
            vup = np.asarray(sc.ss.states.committed_upto[:, victim])
            if recover_rounds is None and (
                    vup >= leader_frontier_at_revive).all():
                recover_rounds = (d + 1) * k  # upper bound
        rec_wall = time.perf_counter() - t0
        _progress(f"fault leg done {time.perf_counter() - t_boot:.1f}s "
                  f"(recover_rounds={recover_rounds})")
        kill_recover = {
            "victim": victim,
            "dead_rounds": k_dead,
            "throughput_during_dead_overhead_corrected":
                round(dead_throughput, 1),
            "dip_pct": round(
                100 * (1 - dead_throughput / throughput), 1)
            if throughput else None,
            "recover_rounds_upper_bound": recover_rounds,
            "recover_wall_s": round(rec_wall, 2),
        }
    else:
        DU, DC, RU, RC = [], [], [], []
        kill_recover = {"skipped": "MP_BENCH_FAULT=0"}

    # -- drain: no new proposals until fully committed (no censored
    # tail in the latency sample) --
    drain_rounds = 0
    if RESIDENT:
        in_flight = None
        for _ in range(8):
            committed_cursor, in_flight = _run_res(k, 0)
            drain_rounds += k
            if in_flight == 0:
                break
    else:
        for _ in range(8):
            u, c = sc.run_fused(k, 0, substeps=SS_N)
            RU.append(u)
            RC.append(c)
            drain_rounds += k
            if (np.asarray(sc.ss.states.committed_upto[:, sc.leader])
                    >= np.asarray(sc.ss.states.crt_inst[:, sc.leader]) - 1).all():
                break

    # -- latency over the WHOLE run (healthy + dead + recovery +
    # drain), in rounds at the healthy fused rate --
    hist_overflow = 0
    tel_rows = None
    if RESIDENT:
        # the ONE full readback, after the measured window: exact
        # per-slot latencies from the device-accumulated histogram
        # plus the paxray telemetry ring (read before end_resident
        # disarms it)
        if TELEMETRY:
            tel_rows = sc.resident_telemetry()
        p50, p99, n_lat, hist_overflow = _latency_from_hist(
            sc.end_resident(), round_ms)
        uncommitted = int(in_flight)
        committed_total = int(committed_cursor)
    else:
        uptos = np.concatenate(U + DU + RU, axis=0)
        crts = np.concatenate(C + DC + RC, axis=0)
        p50, p99, n_lat, uncommitted = _latency_rounds(
            uptos, crts, round_ms)
        committed_total = int((uptos[-1] + 1).sum())
    # paxwatch journal for this bench PROCESS: the loud paths land
    # as queryable events (stamped into the artifact and, under
    # --trace, the merged timeline) — the stdout lines themselves
    # stay byte-identical
    from minpaxos_tpu.obs.watch import EV_LATENCY_OVERFLOW, EventJournal

    watch_journal = EventJournal(capacity=64)
    warn = overflow_warning(hist_overflow)
    if warn:
        # LOUD, on stdout next to the record itself (the artifact
        # stamp alone was missable)
        print(warn, flush=True)
        _progress(warn)
        watch_journal.record(EV_LATENCY_OVERFLOW, subject=-1,
                             value=int(hist_overflow))
    result = {
        "metric": "committed_instances_per_sec",
        "value": round(throughput, 1),
        "unit": "instances/sec",
        "vs_baseline": round(throughput / NORTH_STAR_PER_CHIP, 4),
        "measured_this_run": True,
        "device_ms_per_round": round(round_ms, 3),
        "dispatch_overhead_ms": round(k1_ms - round_ms, 1),
        # per-dispatch walls: constant-shape dispatches must be
        # constant-time — growth here is a dispatch queue backing
        # up, visible without a rerun
        "dispatch_wall_ms": [round((b - a) * 1e3, 1)
                             for a, b in zip(walls, walls[1:])],
        "rounds_per_dispatch": k,
        "p50_quorum_decision_ms": round(p50, 3),
        "p99_quorum_decision_ms": round(p99, 3),
        "latency_samples": n_lat,
        "latency_uncommitted_after_drain": uncommitted,
        "latency_hist_overflow": hist_overflow,
        "drain_rounds": drain_rounds,
        "concurrent_instances": g * w,
        "substeps": SS_N,
        # PR 8 provenance: which measured loop produced this
        # record, under what donation discipline, from which
        # workload stream — and, in --ladder mode, the sweep that
        # picked the shape. Old consumers ignore unknown keys;
        # records from pre-resident trees parse as resident=False
        # via .get("resident", False).
        "resident": RESIDENT,
        "donation": DONATION,
        # paxray provenance: whether the device telemetry ring was
        # armed (BENCH_TELEMETRY) and how many rounds it captured —
        # the on/off dispatch wall is gated within 2% by
        # tools/obs_smoke.py --resident, so enabled=True never
        # marks a slower record
        "telemetry": {"enabled": TELEMETRY and RESIDENT,
                      "rounds_captured":
                          0 if tel_rows is None else int(len(tel_rows))},
        "workload": {"generator": "threefry2x32",
                     "seed": WORKLOAD_SEED},
        "shape": {"n_shards": g, "window": w, "proposals": p,
                  "rounds_per_dispatch": k, "catchup_rows": cu_rows,
                  "inbox": cfg.inbox,
                  "route_fabric": cfg.route_fabric,
                  "shard_devices": shard_devices,
                  "ladder_chosen": ladder is not None},
        "proposals_per_round": g * p,
        "committed_total": committed_total,
        "metrics": mx.snapshot(),
        # paxwatch: this process's journaled loud-path events
        # (latency-histogram overflow today; {} = clean run)
        "watch_events": watch_journal.counts_by_kind(),
        "kill_recover": kill_recover,
        "n_replicas": cfg.n_replicas,
        # resolved quorum sizes (PR 16): default = majority
        "q1": cfg.quorum1,
        "q2": cfg.quorum2,
        "n_shards": g,
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "baseline": ("north-star 12.5e6 inst/s/chip (1M concurrent, "
                     "<10ms p50, v5e-8/8); reference publishes none "
                     "(BASELINE.md)"),
    }
    if ladder is not None:
        result["ladder"] = ladder

    # -- unified Perfetto timeline (--trace PATH): host dispatch
    # slices (flight-recorder rows, pid 0) merged with device-round
    # slices + frontier/in-flight counter tracks rendered from the
    # post-window telemetry readback (reserved DEVICE_PID) — one
    # validated file a resident dispatch and the TCP runtime share.
    if trace_path and not disp_log:
        # the timeline instruments the RESIDENT dispatch loop; in
        # BENCH_RESIDENT=0 legacy mode nothing was captured — say
        # so instead of writing an empty file that looks like a
        # capture
        _progress("--trace: no dispatches captured (tracing "
                  "instruments the resident loop; BENCH_RESIDENT=0 "
                  "runs the legacy path) — no trace written")
    elif trace_path:
        from minpaxos_tpu.obs.recorder import (
            chrome_trace,
            device_round_events,
            validate_chrome_trace,
        )

        events = host_rec.to_events(pid=0)
        if tel_rows is not None and len(tel_rows):
            events += device_round_events(tel_rows, disp_log, g)
        if watch_journal.events_total():
            # schema v6: journaled incidents as instant events on
            # the reserved WATCH_PID, next to the dispatch slices
            from minpaxos_tpu.obs.watch import event_chrome_events

            events += event_chrome_events(watch_journal.snapshot())
        trace = chrome_trace(events)
        errs = validate_chrome_trace(trace)
        if errs:
            _progress(f"trace INVALID ({len(errs)} schema errors): "
                      f"{errs[:3]}")
        else:
            with open(trace_path, "w") as f:
                json.dump(trace, f)
            _progress(f"wrote {len(events)} trace events to "
                      f"{trace_path} (open in ui.perfetto.dev)")
            result["trace_file"] = trace_path

    # -- BASELINE side configs 2-4 (config 1, the TCP runtime, is
    # measured separately: bench_tcp.py writes BENCH_TCP.json) --
    result["configs"] = {}
    for name, (scfg, sg, sp, sk, proto) in side_shapes(on_tpu).items():
        t0 = time.perf_counter()
        result["configs"][name] = _side_config(scfg, sg, sp, sk, proto)
        _progress(f"config {name} {time.perf_counter() - t0:.0f}s")
    _emit(result)


def _run_ladder_mode() -> None:
    """``bench.py --ladder``: run the shape-ladder autotuner
    (tools/shape_ladder.py) as a subprocess, then measure the full
    record at the throughput-optimal point in a child with the same
    virtual-device environment. The sweep record rides the artifact
    (``ladder``), so the headline documents the alternatives its shape
    beat. Budget via MP_BENCH_LADDER_BUDGET_S (default 900 s)."""
    import tempfile

    ncpu = os.cpu_count() or 1
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        # the sweep's meshed points and the measured winner must see
        # the same device count, or the winner is irreproducible
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={ncpu}"
                            ).strip()
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "shape_ladder.py")
    fd, sweep_path = tempfile.mkstemp(suffix="_ladder.json")
    os.close(fd)
    budget = os.environ.get("MP_BENCH_LADDER_BUDGET_S", "900")
    _progress(f"ladder sweep (budget {budget}s, {ncpu} virtual devices)")
    try:
        proc = subprocess.run(
            [sys.executable, tool, "--json", sweep_path,
             "--budget-s", budget],
            env=env, stdout=subprocess.DEVNULL, timeout=3600.0)
        if proc.returncode != 0:
            _die("ladder-sweep", f"shape_ladder rc={proc.returncode}")
        with open(sweep_path) as f:
            sweep = json.load(f)
        win = sweep.get("winner")
        if not win:
            _die("ladder-sweep", "no legal (exactly-drained) point")
        _progress(f"ladder winner: g={win['g']} w={win['w']} p={win['p']} "
                  f"k={win['k']} sd={win['shard_devices']} "
                  f"({win['inst_per_sec']:.0f} inst/s in the sweep)")
        env2 = dict(env,
                    MP_BENCH_CHILD=",".join(str(win[x])
                                            for x in ("g", "w", "p", "k")),
                    MP_BENCH_LADDER_FILE=sweep_path,
                    MP_BENCH_SHARD_DEVICES=str(win["shard_devices"]),
                    # occupancy-adaptive capacity rides along: the
                    # measured record must run the winner's inbox,
                    # not re-derive the default sizing
                    MP_BENCH_INBOX=str(win.get("inbox") or 0),
                    # flexible quorums: a quorum-sweep winner carries
                    # its (q1, q2); the record re-runs the pair that
                    # won (resolved majority == explicit majority)
                    MP_BENCH_Q1=str(win.get("q1") or 0),
                    MP_BENCH_Q2=str(win.get("q2") or 0),
                    # throughput shapes use economy catch-up sizing;
                    # kill/recover stays with the default-shape run
                    MP_BENCH_FAULT="0")
        proc = subprocess.run([sys.executable, __file__], env=env2,
                              stdout=subprocess.PIPE, timeout=3600.0)
        lines = [ln for ln in proc.stdout.decode().splitlines()
                 if ln.strip().startswith("{")]
        if proc.returncode != 0 or not lines:
            _die("ladder-measure", f"child rc={proc.returncode}")
        print(lines[-1])
    except subprocess.TimeoutExpired:
        _die("ladder", "sweep or measure child hung > 3600s")
    finally:
        try:
            os.remove(sweep_path)
        except OSError:
            pass


def main() -> None:
    """``--ladder``: the CPU autotune (a parent that never touches JAX
    launching children). Otherwise ONE process: ``measure()`` on the
    backend JAX finds, at ``MP_BENCH_CHILD="g,w,p,k"`` when set."""
    # observability knobs, normalized to env so the --ladder measure
    # child inherits them: --xprof DIR wraps the measured phase in a
    # jax.profiler trace (alias for MP_BENCH_PROFILE); --trace PATH
    # writes the merged host+device Perfetto timeline (paxray).
    argv = sys.argv[1:]
    for flag, env_key in (("--xprof", "MP_BENCH_PROFILE"),
                          ("--trace", "MP_BENCH_TRACE")):
        if flag in argv:
            i = argv.index(flag)
            # a following flag must not be silently consumed as the
            # path (`--trace --ladder` would write a file named
            # "--ladder" and still enter ladder mode)
            if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
                _progress(f"{flag} needs a path argument")
                sys.exit(2)
            os.environ[env_key] = argv[i + 1]

    shape = os.environ.get("MP_BENCH_CHILD")
    if "--ladder" in argv and not shape:
        # autotuned mode: sweep tools/shape_ladder.py's grid first,
        # then measure the full record at the throughput-optimal point
        # (a child process, so the winner runs with the shard axis
        # meshed over every virtual CPU device the sweep used).
        _run_ladder_mode()
        return
    ladder_rec = None
    if os.environ.get("MP_BENCH_LADDER_FILE"):
        with open(os.environ["MP_BENCH_LADDER_FILE"]) as f:
            ladder_rec = json.load(f)
    from minpaxos_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    measure(tuple(int(x) for x in shape.split(",")) if shape else None,
            ladder=ladder_rec)


if __name__ == "__main__":
    main()
