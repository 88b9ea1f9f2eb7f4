"""Shape-ladder autotuner: find the throughput-optimal (shards x
window x proposals x k) point for the device-resident consensus loop.

The bench's shapes were hand-picked for SURVIVAL (the biggest shape a
fragile remote worker boots), not throughput. This tool replaces that
guess with a measurement: it runs the resident fused loop
(parallel/sharded.py ``sharded_run_resident``) over a small grid of
(g, w, p, k) points per protocol, times a few back-to-back dispatches
at each, verifies every point drains exactly (assigned == committed,
the latency-accounting contract), and reports the winner. ``bench.py
--ladder`` consumes the JSON and measures its full record at the
winning point; the whole sweep lands in the bench artifact so a record
documents the alternatives its shape beat.

Grid design (PR 8 ablation, PERF.md): commits/round are capped by p
(proposal rows per shard per round) but only while the window stays >=
~4x p deep (the commit pipeline is 3 deliveries); inbox capacity costs
~50 us/row/round on the measured CPU host, so catchup_rows uses
economy sizing p/4 instead of a fixed 128 (ladder points skip the
bench's fault leg; sizing policy is imported from bench.py so the
winner re-measures under exactly the sweep's config — key space and
KV capacity scale with p, keeping the stride-walk keys
duplicate-free at every point); and shard counts beyond the device
count only dilute one core's time, so g sweeps {1, device_count}
with the shard axis meshed over real devices when there is more than
one.

Budget: points are measured best-first under ``--budget-s``; points
dropped for budget are LISTED in the output (never silently) and the
already-measured prefix still yields a winner.

    JAX_PLATFORMS=cpu python tools/shape_ladder.py [--json out.json]
    python tools/shape_ladder.py --smoke   # 2 tiny points, CI gate
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the sweep is CPU-friendly by default; let an operator pin the
# backend exactly as for the other tools
import jax  # noqa: E402
import numpy as np  # noqa: E402

# sizing policy is SHARED with bench.py (single definition): the
# measured winner must re-run under exactly the config that won the
# sweep — catch-up/inbox rows, key space, AND KV capacity. Ladder
# points use the economy (fault=False) catch-up sizing; the bench's
# kill/recover leg runs at its default shape with fault-viable sizing.
from bench import cpu_catchup_rows, cpu_key_space, cpu_kv_pow2  # noqa: E402
from minpaxos_tpu.models.minpaxos import MinPaxosConfig  # noqa: E402
from minpaxos_tpu.models.paxos import classic_config  # noqa: E402
from minpaxos_tpu.parallel import make_mesh  # noqa: E402
from minpaxos_tpu.parallel.sharded import ShardedCluster  # noqa: E402


def point_config(protocol: str, w: int, p: int, inbox: int | None = None,
                 q1: int = 0, q2: int = 0) -> MinPaxosConfig:
    cu = cpu_catchup_rows(p, fault=False)
    kw = dict(n_replicas=5, window=w, inbox=p + 2 * cu + 64 + 64,
              exec_batch=p, kv_pow2=cpu_kv_pow2(p), catchup_rows=cu,
              recovery_rows=64, q1=q1, q2=q2)
    if protocol == "classic":
        if inbox is not None:
            kw["inbox"] = inbox
        return classic_config(**kw)
    if protocol == "mencius":
        # per-step commit-broadcast chunk must beat the per-owner
        # proposal rate (bench.py mencius side config rationale)
        kw["catchup_rows"] = max(kw["catchup_rows"], 2 * p)
        kw["inbox"] = max(kw["inbox"], 4 * p)
        kw["noop_delay"] = 8
    if inbox is not None:
        kw["inbox"] = inbox
    return MinPaxosConfig(**kw)


def adaptive_capacity(hwm: int) -> int:
    """Occupancy-derived inbox capacity: the measured delivered-rows
    high-water mark (paxray TEL_INBOX_HWM) plus 25% headroom, rounded
    up to 32 rows. The routing capacity (cfg.inbox) takes this number
    — below it a point LOSES proposals, which the lossless check
    rejects. (The kernel's rows are chosen on the device each round:
    parallel/sharded.py ``sharded_round``.)"""
    return max(64, ((hwm + hwm // 4 + 8 + 31) // 32) * 32)


def measure_point(protocol: str, g: int, w: int, p: int, k: int,
                  dispatches: int = 3, key_space: int | None = None,
                  shard_devices: int = 1, seed: int = 0,
                  inbox: int | None = None,
                  q1: int = 0, q2: int = 0) -> dict:
    """Time the resident loop at one (g, w, p, k) point: warm one
    dispatch, run ``dispatches`` back-to-back (two-scalar readbacks
    only), then drain and REQUIRE exactness (in-flight == 0) — a point
    that cannot drain is not a legal operating point, however fast.

    The paxray telemetry ring rides every point; the post-window
    readback (the sanctioned once-after-the-measured-window path)
    yields the point's delivered-occupancy high-water mark
    (``occupancy_hwm``), which seeds the adaptive-capacity axis —
    ``inbox`` overrides the default capacity with an
    occupancy-derived one. ``lossless`` pins that no proposal was
    dropped (total commits == total injected; minpaxos/classic only —
    Mencius frontiers count SKIP no-op slots, so drained_exact is its
    contract)."""
    cfg = point_config(protocol, w, p, inbox=inbox, q1=q1, q2=q2)
    if key_space is None:
        key_space = cpu_key_space(p)
    mesh = None
    if shard_devices > 1:
        mesh = make_mesh(n_shard_devices=shard_devices,
                         n_replica_devices=1)
    t_build = time.perf_counter()
    sc = ShardedCluster(cfg, g, ext_rows=p, mesh=mesh, protocol=protocol,
                        key_space=key_space, seed=seed)
    if protocol != "mencius":
        sc.elect(0)
    # ring sized for every round the point can run (warm + baseline +
    # measured + drain) so the readback never wraps
    sc.begin_resident(telemetry_rounds=(2 + dispatches + 8) * k)
    sc.run_resident(k, p)  # warm/compile
    compile_s = time.perf_counter() - t_build
    c0, _ = sc.run_resident(k, p)
    t0 = time.perf_counter()
    committed = c0
    for _ in range(dispatches):
        committed, _ = sc.run_resident(k, p)
    wall = time.perf_counter() - t0
    measured = committed - c0  # commits inside the timed window only
    in_flight = None
    total = committed
    drain_dispatches = 0
    for _ in range(8):
        total, in_flight = sc.run_resident(k, 0)
        drain_dispatches += 1
        if in_flight == 0:
            break
    from minpaxos_tpu.obs.recorder import TEL_INBOX_HWM

    tel = sc.resident_telemetry()
    hwm = int(tel[:, TEL_INBOX_HWM].max()) if len(tel) else 0
    hist = sc.end_resident()
    injected = (2 + dispatches) * k * p * g * (
        cfg.n_replicas if protocol == "mencius" else 1)
    return {
        "protocol": protocol,
        "g": g, "w": w, "p": p, "k": k,
        "shard_devices": shard_devices,
        # resolved flexible-quorum sizes (PR 16): default = majority
        "q1": cfg.quorum1,
        "q2": cfg.quorum2,
        "catchup_rows": cfg.catchup_rows,
        "inbox": cfg.inbox,
        "adaptive": inbox is not None,
        "inst_per_sec": round(measured / wall, 1),
        "ms_per_round": round(wall / (dispatches * k) * 1e3, 3),
        "committed": int(measured),
        "committed_total": int(total),
        "drained_exact": in_flight == 0,
        "occupancy_hwm": hwm,
        # every injected proposal committed. Points can fail this for a
        # NON-capacity reason: deep-pipeline shapes (w = 4p) bounce a
        # slice of proposals off the full window at ANY capacity — the
        # PR-8/9 grid always had that; only capacity-ATTRIBUTABLE loss
        # (adaptive total < the same point's base total) disqualifies,
        # see _legal
        "lossless": (None if protocol == "mencius"
                     else int(total) == injected),
        "latency_samples": int(hist.sum()),
        "compile_s": round(compile_s, 1),
    }


def default_grid(protocol: str, device_count: int) -> list[tuple]:
    """(g, w, p, k, shard_devices) points, best-guess-first so a tight
    budget still measures the likely winners."""
    d = max(1, device_count)
    pts: list[tuple] = []
    for p in (1024, 512, 256):
        for g, sd in ([(d, d)] if d > 1 else []) + [(1, 1)]:
            pts.append((g, 4 * p, p, 8, sd))
    # k sensitivity at the expected winner
    pts.append((d if d > 1 else 1, 4096, 1024, 16, d))
    # the PR-7 hand-picked survival shape, as the sweep's own baseline
    pts.append((8, 512, 64, 8, 1))
    return pts


SMOKE_POINT = (1, 128, 16, 2, 1)  # base; the 2nd smoke point derives
# its capacity from this one's measured occupancy (same 2-compile
# budget as the original fixed pair — no new compiled gate variant)


def _legal(r: dict) -> bool:
    """A crownable point: drains exactly, no error — and an ADAPTIVE
    point must not have lost proposals to its capacity choice: either
    absolutely lossless, or (deep-pipeline shapes that bounce
    proposals off the full window at any capacity) committing exactly
    what its own base-capacity run committed (``lossless_vs_base``,
    stamped by the sweep). Base points keep the PR-8/9 bar."""
    if not (bool(r.get("drained_exact")) and not r.get("error")):
        return False
    if not r.get("adaptive"):
        return True
    return bool(r.get("lossless")) or bool(r.get("lossless_vs_base"))


def sweep(protocol: str = "minpaxos", budget_s: float = 900.0,
          points: list[tuple] | None = None, dispatches: int = 3,
          seed: int = 0, adaptive: bool = True) -> dict:
    """Measure the grid, then — ``adaptive`` — re-measure the best
    base point with its inbox capacity derived from the MEASURED
    occupancy high-water mark (telemetry TEL_INBOX_HWM ->
    ``adaptive_capacity``). The swept axis the PR-11 tentpole adds:
    branch-free kernels cost ∝ capacity, so occupancy-fit capacity is
    a direct throughput lever; a lossy point (dropped proposals) is
    rejected by ``_legal``."""
    t_start = time.perf_counter()
    grid = points if points is not None else default_grid(
        protocol, jax.device_count())
    results, dropped = [], []

    def run_point(g, w, p, k, sd, inbox=None, derived=None, q1=0, q2=0):
        try:
            rec = measure_point(protocol, g, w, p, k,
                                dispatches=dispatches, shard_devices=sd,
                                seed=seed, inbox=inbox, q1=q1, q2=q2)
        except Exception as e:  # noqa: BLE001 — a too-big point must
            # not kill the sweep; the failure is recorded, not hidden
            rec = {"protocol": protocol, "g": g, "w": w, "p": p, "k": k,
                   "shard_devices": sd, "q1": q1, "q2": q2,
                   "error": repr(e)[:200]}
        if derived is not None:
            rec["derived_from_hwm"] = derived
        results.append(rec)
        print(f"[ladder] {rec}", file=sys.stderr, flush=True)
        return rec

    for pt in grid:
        g, w, p, k, sd = pt
        if time.perf_counter() - t_start > budget_s and results:
            dropped.append(list(pt))
            continue
        run_point(g, w, p, k, sd)
    if adaptive:
        base_legal = [r for r in results if _legal(r)
                      and r.get("occupancy_hwm", 0) > 0]
        if base_legal and time.perf_counter() - t_start <= budget_s:
            best = max(base_legal, key=lambda r: r["inst_per_sec"])
            cap = adaptive_capacity(best["occupancy_hwm"])
            if cap < best["inbox"] + best["p"]:  # else nothing to gain
                rec = run_point(best["g"], best["w"], best["p"],
                                best["k"], best["shard_devices"],
                                inbox=cap, derived=best["occupancy_hwm"])
                # capacity-attributable loss check: same workload
                # schedule as the base run, so equal committed totals
                # mean the tighter capacity dropped nothing even on
                # shapes that bounce proposals off the window
                if rec.get("committed_total") == best.get(
                        "committed_total"):
                    rec["lossless_vs_base"] = True
        elif base_legal:
            dropped.append(["adaptive", "budget"])

    # flexible-quorum sweep (PR 16): re-measure the crowned SHAPE at
    # every other certified (q1, q2) pair for n=5 (the ledger rows in
    # analysis/quorum_golden.GOLDEN_THRESHOLDS — each satisfies
    # q1 + q2 > n, verify/quorum.py). Smaller q2 means fewer ACCEPT
    # votes per commit scan; q1 grows to compensate. Every pair bakes
    # new kernel thresholds (a fresh compile), so the stage is
    # budget-guarded and only runs on the already-measured winner.
    legal = [r for r in results if _legal(r)]
    shape_winner = (max(legal, key=lambda r: r["inst_per_sec"])
                    if legal else None)
    quorum_results: list[dict] = []
    if shape_winner is not None:
        from minpaxos_tpu.analysis.quorum_golden import GOLDEN_THRESHOLDS

        n = 5  # point_config pins n_replicas=5
        default_pair = (n // 2 + 1, n // 2 + 1)
        sw = shape_winner
        for pair in GOLDEN_THRESHOLDS[n]:
            if pair == default_pair:
                continue  # the base grid already measured majority
            if time.perf_counter() - t_start > budget_s:
                dropped.append(["quorum", list(pair)])
                continue
            rec = run_point(
                sw["g"], sw["w"], sw["p"], sw["k"], sw["shard_devices"],
                inbox=sw["inbox"] if sw.get("adaptive") else None,
                q1=pair[0], q2=pair[1])
            # same workload schedule as the winner's run: equal
            # committed totals mean the pair dropped nothing
            if rec.get("committed_total") == sw.get("committed_total"):
                rec["lossless_vs_base"] = True
            quorum_results.append(rec)
    legal = [r for r in results if _legal(r)]
    winner = max(legal, key=lambda r: r["inst_per_sec"]) if legal else None
    # best point across the default-quorum shape winner and every
    # legal flexible pair — the artifact's quorum-sweep verdict
    q_pool = ([shape_winner] if shape_winner is not None else []) + [
        r for r in quorum_results if _legal(r)]
    quorum_winner = (max(q_pool, key=lambda r: r["inst_per_sec"])
                     if q_pool else None)
    return {
        "protocol": protocol,
        "backend": jax.devices()[0].platform,
        "device_count": jax.device_count(),
        "budget_s": budget_s,
        "points": results,
        "dropped_for_budget": dropped,
        "winner": winner,
        "quorum_sweep": quorum_results,
        "quorum_winner": quorum_winner,
    }


def smoke() -> int:
    """CI gate (tools/run_tier1.sh): two tiny points through the full
    resident path — a fixed base point, then a g=2 point whose inbox
    capacity is DERIVED from the base point's measured occupancy
    high-water mark (the PR-11 adaptive-capacity path). Contract: commits flow, every point
    drains exactly, the adaptive point is LOSSLESS (occupancy-fit
    capacity dropped nothing), and the latency sample is complete.
    Still exactly two compiled dispatch variants; budget <=60s after
    compile."""
    t0 = time.perf_counter()
    g, w, p, k, sd = SMOKE_POINT

    def _point(*a, **kw):
        # same containment contract as sweep()'s run_point: a point
        # that throws becomes a FAIL-able error record, not a raw
        # traceback that skips the gate's diagnostics
        try:
            return measure_point(*a, **kw)
        except Exception as e:  # noqa: BLE001
            return {"error": repr(e)[:200]}

    points = [_point("minpaxos", g, w, p, k, dispatches=2,
                     shard_devices=sd)]
    base = points[0]
    ok = True
    if not base.get("error") and base.get("occupancy_hwm", 0) > 0:
        cap = adaptive_capacity(base["occupancy_hwm"])
        points.append(_point("minpaxos", 2, w, p, k, dispatches=2,
                             shard_devices=sd, inbox=cap))
    else:
        print(f"FAIL: base point unusable (no occupancy readback): {base}")
        ok = False
    wall = time.perf_counter() - t0
    for r in points:
        if r.get("error") or not r.get("drained_exact"):
            print(f"FAIL: ladder point did not drain exactly: {r}")
            ok = False
            continue
        if r["committed"] <= 0 or r["latency_samples"] <= 0:
            print(f"FAIL: ladder point made no progress: {r}")
            ok = False
        if r.get("lossless") is False:
            print(f"FAIL: point dropped proposals (capacity below "
                  f"occupancy): {r}")
            ok = False
    winner = max([r for r in points if _legal(r)],
                 key=lambda r: r["inst_per_sec"], default=None)
    if winner is None:
        print("FAIL: no legal winner among smoke points")
        ok = False
    post_compile = wall - sum(r.get("compile_s", 0) for r in points)
    if ok:
        adapt = points[1]
        print(f"shape-ladder smoke: {len(points)} points, winner "
              f"g={winner['g']} w={winner['w']} p={winner['p']} "
              f"k={winner['k']} ({winner['inst_per_sec']:.0f} inst/s); "
              f"adaptive point: hwm={base['occupancy_hwm']} -> "
              f"inbox={adapt['inbox']} (was {base['inbox']}), "
              f"lossless+drain-exact; "
              f"{wall:.1f}s wall ({post_compile:.1f}s post-compile)")
    else:
        print("shape-ladder smoke: FAILED")
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--protocol", default="minpaxos",
                    choices=("minpaxos", "classic", "mencius"))
    ap.add_argument("--budget-s", type=float, default=900.0)
    ap.add_argument("--dispatches", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None,
                    help="write the sweep record to this path")
    ap.add_argument("--smoke", action="store_true",
                    help="2-point tiny-shape CI gate (run_tier1.sh)")
    args = ap.parse_args()
    if args.smoke:
        return smoke()
    rec = sweep(args.protocol, args.budget_s, dispatches=args.dispatches,
                seed=args.seed)
    out = json.dumps(rec, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(out + "\n")
    print(out)
    if rec["winner"] is None:
        print("no legal (exactly-drained) point measured", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
