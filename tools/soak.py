#!/usr/bin/env python
"""paxsoak: scenario-driven soak runs with one joined scorecard.

    tools/soak.py --smoke             # CI gate: 2 short phases incl.
                                      # a micro overload burst, 45 s
                                      # budget after boot, JSON verdict
    tools/soak.py --full              # the committed SOAK.json run:
                                      # warmup -> Zipf skew -> overload
                                      # burst -> partition-under-load
                                      # -> heal -> drain
    tools/soak.py --manifest m.json   # run your own phase manifest
    tools/soak.py --json SOAK.json    # where the scorecard lands

The scorecard joins, per phase: client-side acked/shed/retransmit
counts and p50/p99/p999, the paxwatch detector raise->clear timeline
classified against the ground-truth fault/phase timeline, per-phase
traced stage tables (tools/tail.py math), and the admission gate's
counters.

Smoke pass criteria (the tier-1 wiring): every phase ran, EV_PHASE
landed on every replica's journal, exactly-once held across shards
(0 lost), and the scorecard is well-formed — the gate firing
ORGANICALLY is asserted for the committed full run (where the
overload phase is sized to provoke it), not for the CI micro burst,
whose sizing must stay friendly to slow shared hosts.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

SMOKE_BUDGET_S = 45.0  # measured from the end of cluster boot


def smoke_verdict(card: dict, n_replicas: int) -> dict:
    """The tier-1 gate's pass line (see module docstring)."""
    eo = card["exactly_once"]
    phases_ran = (len(card["phases"]) == len(card["manifest"]["phases"])
                  and all(p["client"]["sent"] > 0
                          and p["client"]["acked"] > 0
                          for p in card["phases"]))
    # EV_PHASE fan-out proof: every (ordinal incl. drain) x replica
    want_edges = (len(card["phases"]) + 1) * n_replicas
    checks = {
        "phases_ran": phases_ran,
        "ev_phase_on_every_replica":
            len(card["phase_events"]) == want_edges,
        "exactly_once": eo["lost"] == 0 and eo["acked_unique"] > 0,
        "no_dead_sessions": eo["dead_sessions"] == 0,
        "scorecard_joined": bool(card["stage_tables"]["overall"]
                                 or card["watch"]["samples"] > 0),
    }
    checks["ok"] = all(checks.values())
    return checks


def main(argv=None) -> int:
    p = argparse.ArgumentParser("paxsoak")
    p.add_argument("--smoke", action="store_true",
                   help="CI gate: the 2-phase smoke manifest under a "
                        f"{SMOKE_BUDGET_S:.0f} s post-boot budget")
    p.add_argument("--full", action="store_true",
                   help="the committed multi-phase chaos-under-load "
                        "run (writes SOAK.json's content)")
    p.add_argument("--manifest", default="",
                   help="path to a custom manifest JSON, or a named "
                        "manifest (smoke/full)")
    p.add_argument("--sessions", type=int, default=0,
                   help="override the manifest's swarm sessions")
    p.add_argument("--shards", type=int, default=0,
                   help="override the manifest's swarm shards")
    p.add_argument("--json", default="",
                   help="write the scorecard to this file")
    args = p.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    from minpaxos_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()

    from minpaxos_tpu.soak.scenario import (
        MANIFESTS,
        run_scenario,
        save_scorecard,
    )

    if args.smoke and args.full:
        p.error("--smoke and --full are exclusive")
    if args.manifest:
        if args.manifest in MANIFESTS:
            manifest = dict(MANIFESTS[args.manifest])
        else:
            manifest = json.loads(Path(args.manifest).read_text())
    elif args.full:
        manifest = dict(MANIFESTS["full"])
    else:
        manifest = dict(MANIFESTS["smoke"])
    if args.sessions:
        manifest["sessions"] = args.sessions
    if args.shards:
        manifest["shards"] = args.shards

    t0 = time.monotonic()
    card = run_scenario(manifest, log=lambda m: print(m, flush=True))
    card["wall_s"] = round(time.monotonic() - t0, 2)

    if args.json:
        save_scorecard(card, args.json)
        print(f"[soak] scorecard written to {args.json}", flush=True)

    if args.smoke or (args.manifest == "smoke"):
        checks = smoke_verdict(card, int(manifest.get("n_replicas", 3)))
        # the budget is advisory-but-loud: boot (jit) time is excluded
        # like the chaos smoke's, and phase walls are fixed by the
        # manifest, so an overrun means the drain dragged
        phase_wall = sum(p["t1_wall"] - p["t0_wall"]
                         for p in card["phases"])
        drain_wall = card["drain"]["t1_wall"] - card["drain"]["t0_wall"]
        checks["in_budget"] = phase_wall + drain_wall <= SMOKE_BUDGET_S
        checks["ok"] = checks["ok"] and checks["in_budget"]
        line = {**checks,
                "acked": card["exactly_once"]["acked_unique"],
                "shed": sum(p["cluster"]["coalesce_admission_rejects"]
                            for p in card["phases"]),
                "wall_s": card["wall_s"]}
        print(f"[soak] smoke verdict: {json.dumps(line)}", flush=True)
        return 0 if checks["ok"] else 1

    line = {"ok": card["ok"], **card["criteria"],
            "acked": card["exactly_once"]["acked_unique"],
            "lost": card["exactly_once"]["lost"],
            "alarms": card["watch"]["alarm_counts"],
            "wall_s": card["wall_s"]}
    print(f"[soak] verdict: {json.dumps(line)}", flush=True)
    return 0 if card["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
