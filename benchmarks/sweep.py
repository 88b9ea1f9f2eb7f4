"""Find the highest rate a served configuration sustains: one cluster,
one window per rate, in this process.

    python3 benchmarks/sweep.py --workload served3_open_knee80 \
        --rates 1000,2000,4000,8000 --seconds 12 --seed 1

Not part of a check: a ``benchmark`` PR runs it once on the chip, writes
the knee's four fifths and tenth into the cells' files and the table
into PERF.md. A rate is *sustained* when every request was answered,
none was shed, and the second half of the window waited no longer than
the first (no growing backlog). Prints one JSON line per rate and the
table again at the end; ``--out`` also writes it to a file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    import numpy as np

    from benchmarks import run as harness
    from benchmarks.lib import manifest as mf
    from benchmarks.lib.compile_meter import CompileMeter

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    _, config, workload = harness.load_cell(mf.load(), args.workload,
                                            args.rehearse_cpu)
    device = harness.device_info()
    if device["platform"] != ("cpu" if args.rehearse_cpu else "tpu"):
        print(f"sweep: needs the chip; JAX found {device}", file=sys.stderr)
        return 2

    harness.prepare_backend()
    scratch = ROOT / ".bench_scratch" / "sweep"
    shutil.rmtree(scratch, ignore_errors=True)
    scratch.mkdir(parents=True)
    ctx = harness.Context(config, workload, args.seed, args.seconds, scratch,
                          CompileMeter(), None, args.rehearse_cpu)
    runner = harness.load_module(
        ROOT / "benchmarks" / "runners" / f"{config['runner']}.py",
        "runner_sweep").Runner(ctx)
    table = []
    try:
        runner.setup()
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            runner.traffic = dataclasses.replace(runner.traffic, rate_hz=rate)
            ctx.seed = args.seed + i
            t0 = runner.window()
            r, c = runner.result, runner.counters()
            answered = ~np.isnan(r["t_reply"])
            lat = (r["t_reply"] - r["t_due"]) * 1e3
            half = r["t_due"] < t0 + args.seconds / 2
            row = {"rate_hz": rate, "requests": len(lat),
                   "unanswered": int((~answered).sum()),
                   "rejects": c["rejects"], "retransmits": c["retransmits"],
                   "acked_per_s": c["acked_in_window"] / args.seconds,
                   "p50_ms": float(np.percentile(lat[answered], 50)),
                   "p95_ms": float(np.percentile(lat[answered], 95)),
                   "p50_first_half_ms": float(np.median(lat[answered & half])),
                   "p50_second_half_ms": float(
                       np.median(lat[answered & ~half])),
                   "gen_late_max_ms": c["gen_behind_max_s"] * 1e3,
                   "rows_per_dispatch": c["leader_proposals"]
                   / max(c["leader_dispatches"], 1),
                   "leader_dispatches_per_s": c["leader_dispatches"]
                   / c["leader_window_s"],
                   "compiles": ctx.meter.take()["compilations"]}
            row["sustained"] = bool(
                row["unanswered"] == 0 and row["rejects"] == 0
                and row["p50_second_half_ms"]
                <= 1.5 * row["p50_first_half_ms"] + 5.0)
            table.append(row)
            print(json.dumps(row), flush=True)
        try:
            numbers, _, _, _ = runner.check()
            print(json.dumps({"checks": numbers}), flush=True)
        except ValueError as e:  # a long sweep outgrows the snapshot trigger
            print(json.dumps({"checks": None, "why": str(e)}), flush=True)
    finally:
        runner.close()
        shutil.rmtree(scratch, ignore_errors=True)
    if args.out:
        out = ROOT / args.out
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
