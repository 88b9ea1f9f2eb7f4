#!/usr/bin/env python
"""trend — the cross-PR throughput/latency trajectory, as markdown.

The bench artifacts were stamped for exactly this (`measured_this_run`,
`resident`, `shape`, mtimes), but nothing ever read them side by side:
every session that wanted the regression view re-opened BENCH_*.json by
hand. This tool prints it once: per committed accelerator artifact
(`BENCH_r*.json` driver captures, `BENCH_LADDER_CPU.json`,
`BENCH_TCP.json`) the headline throughput, quorum p50/p99, platform and
shape — plus verification coverage from the model-checker artifacts
(`MC.json`/`MC_FLEX.json`: refined edges, fair lassos, mutant
self-tests), the paxsoak scenario scorecard (`SOAK.json`: per-phase
throughput / latency / admission shed / alarm classification from the
committed chaos-under-load run) and the repo-growth trajectory from
`PROGRESS.jsonl` (per driver round: commits, LoC). Report-only: reads the committed
artifacts, writes nothing, imports no JAX — safe to run anywhere,
cheap enough to paste into a PR description.

    python tools/trend.py              # markdown tables on stdout
    python tools/trend.py --json      # machine form

Driver captures (`BENCH_r*.json`) are best-effort parses — there may
be none: a capture with no parseable record reports its error instead
of a number, and nothing is ever silently skipped.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _extract_record(cap: dict) -> tuple[dict | None, str]:
    """(bench record, provenance) from one BENCH_r*.json driver
    capture: the `parsed` record when the driver got one, else the
    last parseable JSON line of the captured tail."""
    rec = cap.get("parsed")
    if isinstance(rec, dict) and "value" in rec:
        return rec, "live"
    tail = cap.get("tail") or ""
    for ln in reversed([l for l in tail.splitlines()
                        if l.strip().startswith("{")]):
        try:
            rec = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if "value" in rec:
            return rec, "live"
    return None, "unparseable"


def _fmt(v, nd=1):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:,.{nd}f}"
    return f"{v:,}" if isinstance(v, int) else str(v)


def _row_from_record(name: str, rec: dict, provenance: str,
                     mtime: float) -> dict:
    shape = rec.get("shape") or {}
    return {
        "artifact": name,
        "provenance": provenance,
        "platform": rec.get("platform"),
        "resident": rec.get("resident", False),
        # flexible quorums (PR 16): absent on pre-PR-16 artifacts
        "q1": rec.get("q1"),
        "q2": rec.get("q2"),
        "inst_per_sec": rec.get("value"),
        "p50_ms": rec.get("p50_quorum_decision_ms",
                          rec.get("p50_quorum_decision_ms_censored")),
        "p99_ms": rec.get("p99_quorum_decision_ms"),
        "concurrent": rec.get("concurrent_instances"),
        "shape": (f"g={shape.get('n_shards')} w={shape.get('window')} "
                  f"p={shape.get('proposals')} "
                  f"k={shape.get('rounds_per_dispatch')}"
                  if shape else "-"),
        "error": (rec.get("error") or "")[:60] or None,
        "mtime_utc": time.strftime("%Y-%m-%d", time.gmtime(mtime)),
    }


def collect_bench_rows(repo: Path = REPO) -> list[dict]:
    rows = []
    for path in sorted(glob.glob(str(repo / "BENCH_r*.json"))):
        name = os.path.basename(path)
        try:
            cap = json.load(open(path))
        except (OSError, json.JSONDecodeError) as e:
            rows.append({"artifact": name, "provenance": "unreadable",
                         "error": repr(e)[:60]})
            continue
        rec, prov = _extract_record(cap)
        if rec is None:
            rows.append({"artifact": name, "provenance": prov,
                         "error": f"rc={cap.get('rc')}, no record in tail"})
            continue
        rows.append(_row_from_record(name, rec, prov,
                                     os.path.getmtime(path)))
    lad = repo / "BENCH_LADDER_CPU.json"
    if lad.exists():
        try:
            rec = json.load(open(lad))
            rows.append(_row_from_record(lad.name, rec, "live",
                                         os.path.getmtime(lad)))
        except (OSError, json.JSONDecodeError) as e:
            rows.append({"artifact": lad.name, "provenance": "unreadable",
                         "error": repr(e)[:60]})
    return rows


def collect_tcp_row(repo: Path = REPO) -> dict | None:
    path = repo / "BENCH_TCP.json"
    if not path.exists():
        return None
    try:
        rec = json.load(open(path))
    except (OSError, json.JSONDecodeError):
        return None
    return {
        "artifact": path.name,
        "ops_per_sec": rec.get("ops_per_sec"),
        "serial_p50_ms": rec.get("serial_p50_ms"),
        "serial_p99_ms": rec.get("serial_p99_ms"),
        "stage_tail": _stage_tail(rec.get("serial_traced")),
        "stage_tail_baseline": _stage_tail(
            (rec.get("serial_cadence_baseline") or {}).get("serial_traced")),
        # flexible-quorum paired A/B (PR 16): commit-stage p99 at N=5,
        # majority (q2=3) vs flexible (q1=4, q2=2)
        "flex_commit_p99_ms": (
            rec.get("flex_quorum_ab") or {}).get("commit_p99_ms"),
        "mtime_utc": time.strftime(
            "%Y-%m-%d", time.gmtime(os.path.getmtime(path))),
    }


def _stage_tail(traced: dict | None) -> dict | None:
    """The tail-trajectory row (ISSUE 15): commit / exec_wait p99 and
    their share of the traced end-to-end p99, from a serial leg's
    embedded paxtrace stage table — so the tail's WHERE is tracked
    across PRs like throughput, not just its size."""
    if not isinstance(traced, dict):
        return None
    stages = traced.get("stages") or {}
    total = (traced.get("total_ms") or {}).get("p99")
    commit = (stages.get("commit") or {}).get("p99")
    exec_wait = (stages.get("exec_wait") or {}).get("p99")
    if total is None or commit is None or exec_wait is None:
        return None
    # share of the tail owned by commit+exec_wait, from the
    # tail-command stage MEANS (per-stage p99s are order statistics
    # of different commands — their sum can exceed the total p99);
    # fall back to the p99 ratio for pre-PR-12 artifacts without the
    # tail stanza
    means = (traced.get("tail") or {}).get("stage_means_ms") or {}
    mean_total = sum(means.values())
    if mean_total:
        share = (means.get("commit", 0.0)
                 + means.get("exec_wait", 0.0)) / mean_total
    else:
        share = (commit + exec_wait) / total if total else None
    return {
        "commit_p99_ms": round(commit, 3),
        "exec_wait_p99_ms": round(exec_wait, 3),
        "total_p99_ms": round(total, 3),
        "commit_exec_share": round(share, 3) if share is not None else None,
        "worst_stage": (traced.get("tail") or {}).get("worst_stage"),
    }


def collect_health_rows(repo: Path = REPO) -> list[dict]:
    """paxwatch health evidence from committed artifacts: per
    CHAOS.json campaign run the live-detector alarm counts, the
    cluster event-journal kinds, and the stall-schedule live verdict
    (fired-in-window / attributed / cleared); plus any PAXWATCH*.jsonl
    retention series (raw/coarse coverage). Parsed directly — no
    minpaxos import, same zero-dependency contract as the rest of
    this tool."""
    rows: list[dict] = []
    chaos = repo / "CHAOS.json"
    if chaos.exists():
        try:
            doc = json.load(open(chaos))
        except (OSError, json.JSONDecodeError) as e:
            rows.append({"artifact": chaos.name, "error": repr(e)[:60]})
            doc = {"runs": []}
        for r in doc.get("runs", []):
            w = r.get("watch") or {}
            stall = w.get("stall") or {}
            rows.append({
                "artifact": chaos.name,
                "run": f"{r.get('schedule')}@{r.get('seed')}",
                "alarms": w.get("alarm_counts") or {},
                "events": r.get("cluster_events") or {},
                "stall_live": (
                    None if not stall else
                    f"fired={stall.get('fired_in_window')} "
                    f"attributed={stall.get('attributed')} "
                    f"cleared={stall.get('cleared')}"),
                "faults": r.get("faults_injected"),
                "ok": r.get("ok"),
            })
    for path in sorted(glob.glob(str(repo / "PAXWATCH*.jsonl"))):
        raw = coarse = bad = 0
        try:
            for ln in open(path, encoding="utf-8"):
                try:
                    d = json.loads(ln)
                except json.JSONDecodeError:
                    bad += 1
                    continue
                raw += "raw" in d
                coarse += "coarse" in d
        except OSError as e:
            rows.append({"artifact": os.path.basename(path),
                         "error": repr(e)[:60]})
            continue
        rows.append({"artifact": os.path.basename(path),
                     "run": "series", "raw_samples": raw,
                     "coarse_buckets": coarse, "torn_lines": bad,
                     "bytes": os.path.getsize(path)})
    return rows


def collect_verify_rows(repo: Path = REPO) -> list[dict]:
    """Verification evidence from the committed model-checker
    artifacts: per MC.json / MC_FLEX.json run the state/transition
    totals, paxref refinement coverage (edges held to the abstract
    spec), liveness verdicts (fair lassos found — 0 on healthy legs),
    and which seeded mutants the self-tests re-found. Trended so a
    PR that quietly shrinks coverage (fewer refined edges, a skipped
    mutant) shows up next to the throughput row it bought."""
    rows: list[dict] = []
    for name in ("MC.json", "MC_FLEX.json"):
        path = repo / name
        if not path.exists():
            continue
        try:
            doc = json.load(open(path))
        except (OSError, json.JSONDecodeError) as e:
            rows.append({"artifact": name, "error": repr(e)[:60]})
            continue
        runs = doc.get("runs") or []
        refine = doc.get("refine") or {}
        liveness = doc.get("liveness") or {}
        live_legs = liveness.get("legs") or []
        mutants = {
            "quorum": (doc.get("mutant_self_test") or {}).get("found"),
            "flex": (doc.get("flex_mutant_self_test") or {}).get("found"),
            "refine": (doc.get("refine_mutant_self_test")
                       or {}).get("found"),
            "lasso": (doc.get("lasso_mutant_self_test") or {}).get("found"),
        }
        rows.append({
            "artifact": name,
            "ok": doc.get("ok"),
            "runs": len(runs),
            "states": sum(r.get("states") or 0 for r in runs),
            "transitions": sum(r.get("transitions") or 0 for r in runs),
            # MC_FLEX stamps refined_edges at top level (every sweep
            # run is refinement-checked); MC.json under "refine"
            "refined_edges": (doc.get("refined_edges")
                              if doc.get("refined_edges") is not None
                              else refine.get("edges_checked")),
            "liveness_legs": len(live_legs),
            "fair_lassos": sum(l.get("fair_lassos") or 0
                               for l in live_legs),
            "mutants_found": " ".join(
                f"{k}:{'y' if v else 'n'}" for k, v in mutants.items()
                if v is not None) or None,
            "wall_s": doc.get("wall_s"),
            "mtime_utc": time.strftime(
                "%Y-%m-%d", time.gmtime(os.path.getmtime(path))),
        })
    return rows


def collect_soak_rows(repo: Path = REPO) -> dict | None:
    """paxsoak scorecard (SOAK.json, tools/soak.py --full): the
    per-phase join — offered vs acked throughput, client latency
    percentiles, admission-gate shed, retransmits, and the detector
    alarms classified against the ground-truth fault windows — plus
    the exactly-once totals and the acceptance criteria stanza. One
    committed artifact, rendered as one table."""
    path = repo / "SOAK.json"
    if not path.exists():
        return None
    try:
        card = json.load(open(path))
    except (OSError, json.JSONDecodeError) as e:
        return {"artifact": path.name, "error": repr(e)[:60]}
    alarms = card.get("alarms") or []
    rows = []
    for p in card.get("phases") or []:
        cl = p.get("client") or {}
        cu = p.get("cluster") or {}
        lat = cl.get("lat_ms") or {}
        dur = p.get("t1_wall", 0) - p.get("t0_wall", 0)
        ph_alarms = [a for a in alarms if a.get("phase") == p.get("name")]
        rows.append({
            "phase": p.get("name"), "kind": p.get("kind"),
            "dur_s": round(dur, 1),
            "sent": cl.get("sent"), "acked": cl.get("acked"),
            "acked_per_s": (round(cl.get("acked", 0) / dur, 1)
                            if dur > 0 else None),
            "retransmits": cl.get("retransmits"),
            "shed": cu.get("coalesce_admission_rejects"),
            "committed": cu.get("committed_slots"),
            "p50_ms": lat.get("p50"), "p99_ms": lat.get("p99"),
            "p999_ms": lat.get("p999"),
            "alarms_in_window": sum(
                1 for a in ph_alarms if a.get("in_fault_window")),
            "alarms_outside": sum(
                1 for a in ph_alarms if not a.get("in_fault_window")),
        })
    return {
        "artifact": path.name,
        "name": card.get("name"),
        "rows": rows,
        "exactly_once": card.get("exactly_once") or {},
        "criteria": card.get("criteria") or {},
        "alarm_counts": (card.get("watch") or {}).get("alarm_counts"),
        "wall_s": card.get("wall_s"),
        "mtime_utc": time.strftime(
            "%Y-%m-%d", time.gmtime(os.path.getmtime(path))),
    }


def collect_durability_rows(repo: Path = REPO) -> list[dict]:
    """paxdur durability evidence from the committed artifacts: per
    durable CHAOS.json run the snapshot/truncation counts, redo-log
    bytes freed vs the final on-disk size (is truncation actually
    bounding disk), and the worst recovery wall from EV_RECOVERY;
    plus the SOAK.json crash_restart verdict (snapshot/recovery event
    totals and the crash-attribution criterion). Trended per PR so a
    change that quietly stops snapshots from engaging — or makes
    recovery walltime blow up — shows in the same table as the
    throughput it bought."""
    rows: list[dict] = []
    chaos = repo / "CHAOS.json"
    if chaos.exists():
        try:
            doc = json.load(open(chaos))
        except (OSError, json.JSONDecodeError) as e:
            rows.append({"artifact": chaos.name, "error": repr(e)[:60]})
            doc = {"runs": []}
        for r in doc.get("runs", []):
            d = r.get("durability")
            if not d:
                continue
            lb = d.get("log_bytes") or {}
            rows.append({
                "artifact": chaos.name,
                "run": f"{r.get('schedule')}@{r.get('seed')}",
                "snapshots": d.get("snapshots"),
                "truncations": d.get("truncations"),
                "bytes_freed": d.get("bytes_freed"),
                "log_bytes_final_max": (max(lb.values())
                                        if lb else None),
                "recovery_ms": d.get("recovery_ms_max"),
                "ok": r.get("ok"),
            })
    soak_p = repo / "SOAK.json"
    if soak_p.exists():
        try:
            card = json.load(open(soak_p))
        except (OSError, json.JSONDecodeError):
            card = None
        ec = (card or {}).get("event_counts") or {}
        if ec.get("snapshot") or ec.get("recovery"):
            rows.append({
                "artifact": soak_p.name,
                "run": card.get("name"),
                "snapshots": ec.get("snapshot", 0),
                "truncations": ec.get("truncate", 0),
                "bytes_freed": None,
                "log_bytes_final_max": None,
                "recovery_ms": None,
                "ok": (card.get("criteria")
                       or {}).get("crash_detected_and_attributed"),
            })
    return rows


def collect_progress(repo: Path = REPO) -> list[dict]:
    """Last PROGRESS.jsonl sample per driver round: commits and LoC at
    round end — the repo-growth axis the bench trajectory rides on."""
    path = repo / "PROGRESS.jsonl"
    if not path.exists():
        return []
    last: dict[int, dict] = {}
    for ln in path.read_text().splitlines():
        try:
            d = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if "round" in d:
            last[int(d["round"])] = d
    return [
        {"round": r, "commits": d.get("commits"), "loc": d.get("loc"),
         "wall_h": round((d.get("wall_s") or 0) / 3600.0, 1)}
        for r, d in sorted(last.items())
    ]


def _fmt_counts(d: dict | None) -> str:
    if not d:
        return "-"
    return " ".join(f"{k}:{v}" for k, v in sorted(d.items()))


def render_markdown(bench, tcp, progress, health=None, verify=None,
                    soak=None, durability=None) -> str:
    out = ["## Cross-PR bench trajectory (device loop)", ""]
    hdr = ("| artifact | when | platform | resident | inst/s | p50 ms "
           "| p99 ms | concurrent | shape | note |")
    out += [hdr, "|" + "---|" * 10]
    for r in bench:
        note = r.get("error") or ""
        shape = r.get("shape", "-")
        if r.get("q1") and r.get("q2"):
            shape = f"{shape} q={r['q1']}/{r['q2']}"
        out.append(
            f"| {r['artifact']} | {r.get('mtime_utc', '-')} "
            f"| {r.get('platform', '-')} "
            f"| {'y' if r.get('resident') else 'n'} "
            f"| {_fmt(r.get('inst_per_sec'))} | {_fmt(r.get('p50_ms'), 2)} "
            f"| {_fmt(r.get('p99_ms'), 2)} | {_fmt(r.get('concurrent'))} "
            f"| {shape} | {note} |")
    if tcp:
        out += ["", "## TCP runtime (BENCH_TCP.json)", "",
                "| artifact | when | ops/s | serial p50 ms | serial p99 ms |",
                "|" + "---|" * 5,
                f"| {tcp['artifact']} | {tcp['mtime_utc']} "
                f"| {_fmt(tcp['ops_per_sec'])} "
                f"| {_fmt(tcp['serial_p50_ms'], 2)} "
                f"| {_fmt(tcp['serial_p99_ms'], 2)} |"]
        rows = [("event-driven", tcp.get("stage_tail")),
                ("cadence baseline", tcp.get("stage_tail_baseline"))]
        if any(st for _, st in rows):
            out += ["", "### Serial tail attribution (paxtrace stage "
                    "table, p99 ms)", "",
                    "| leg | commit | exec_wait | total | commit+exec "
                    "share | worst stage |", "|" + "---|" * 6]
            for label, st in rows:
                if not st:
                    continue
                share = st.get("commit_exec_share")
                out.append(
                    f"| {label} | {_fmt(st['commit_p99_ms'], 2)} "
                    f"| {_fmt(st['exec_wait_p99_ms'], 2)} "
                    f"| {_fmt(st['total_p99_ms'], 2)} "
                    f"| {f'{share:.0%}' if share is not None else '-'} "
                    f"| {st.get('worst_stage') or '-'} |")
        flex = tcp.get("flex_commit_p99_ms")
        if flex:
            out += ["", "### Flexible-quorum A/B (serial N=5, commit "
                    "stage p99 ms)", "",
                    "| majority (q2=3) | flexible (q1=4, q2=2) |",
                    "|" + "---|" * 2,
                    f"| {_fmt(flex.get('majority_q2_3'), 2)} "
                    f"| {_fmt(flex.get('flex_q1_4_q2_2'), 2)} |"]
    if health:
        out += ["", "## Cluster health (paxwatch artifacts)", "",
                "| artifact | run | ok | alarms | stall live | faults "
                "| events |", "|" + "---|" * 7]
        for h in health:
            if h.get("error"):
                out.append(f"| {h['artifact']} | - | - | - | - | - "
                           f"| {h['error']} |")
            elif h.get("run") == "series":
                out.append(
                    f"| {h['artifact']} | series "
                    f"| - | raw={h['raw_samples']} "
                    f"coarse={h['coarse_buckets']} | - | - "
                    f"| {_fmt(h['bytes'])} B |")
            else:
                out.append(
                    f"| {h['artifact']} | {h['run']} "
                    f"| {'y' if h.get('ok') else 'n'} "
                    f"| {_fmt_counts(h.get('alarms'))} "
                    f"| {h.get('stall_live') or '-'} "
                    f"| {_fmt(h.get('faults'))} "
                    f"| {_fmt_counts(h.get('events'))} |")
    if verify:
        out += ["", "## Verification coverage (paxmc/paxref artifacts)", "",
                "| artifact | when | ok | runs | states | transitions "
                "| refined edges | liveness legs | fair lassos "
                "| mutants re-found | wall s |", "|" + "---|" * 11]
        for v in verify:
            if v.get("error"):
                out.append(f"| {v['artifact']} | - | - | - | - | - | - "
                           f"| - | - | - | {v['error']} |")
                continue
            out.append(
                f"| {v['artifact']} | {v.get('mtime_utc', '-')} "
                f"| {'y' if v.get('ok') else 'n'} | {_fmt(v.get('runs'))} "
                f"| {_fmt(v.get('states'))} | {_fmt(v.get('transitions'))} "
                f"| {_fmt(v.get('refined_edges'))} "
                f"| {_fmt(v.get('liveness_legs'))} "
                f"| {_fmt(v.get('fair_lassos'))} "
                f"| {v.get('mutants_found') or '-'} "
                f"| {_fmt(v.get('wall_s'))} |")
    if soak:
        out += ["", "## Soak scenario (paxsoak SOAK.json)", ""]
        if soak.get("error"):
            out += [f"{soak['artifact']}: {soak['error']}"]
        else:
            eo = soak.get("exactly_once") or {}
            crit = soak.get("criteria") or {}
            out += [
                f"`{soak['artifact']}` run `{soak.get('name')}` "
                f"({soak.get('mtime_utc', '-')}): "
                f"acked {_fmt(eo.get('acked_unique'))}"
                f"/{_fmt(eo.get('sent_unique'))} unique, "
                f"lost {_fmt(eo.get('lost'))}, "
                f"dup {_fmt(eo.get('duplicates'))}, "
                f"criteria " + " ".join(
                    f"{k}:{'y' if v else 'n'}"
                    for k, v in sorted(crit.items())), "",
                "| phase | kind | dur s | sent | acked | acked/s "
                "| retx | shed | p50 ms | p99 ms | p999 ms "
                "| alarms in/out window |",
                "|" + "---|" * 12]
            for r in soak.get("rows") or []:
                out.append(
                    f"| {r['phase']} | {r['kind']} | {r['dur_s']} "
                    f"| {_fmt(r['sent'])} | {_fmt(r['acked'])} "
                    f"| {_fmt(r['acked_per_s'])} "
                    f"| {_fmt(r['retransmits'])} | {_fmt(r['shed'])} "
                    f"| {_fmt(r['p50_ms'], 1)} | {_fmt(r['p99_ms'], 1)} "
                    f"| {_fmt(r['p999_ms'], 1)} "
                    f"| {r['alarms_in_window']}/{r['alarms_outside']} |")
    if durability:
        out += ["", "## Durability (paxdur: CHAOS.json durable runs + "
                "SOAK.json)", "",
                "| artifact | run | ok | snapshots | truncations "
                "| bytes freed | final log max | recovery ms |",
                "|" + "---|" * 8]
        for d in durability:
            if d.get("error"):
                out.append(f"| {d['artifact']} | - | - | - | - | - | - "
                           f"| {d['error']} |")
                continue
            out.append(
                f"| {d['artifact']} | {d.get('run', '-')} "
                f"| {'y' if d.get('ok') else 'n'} "
                f"| {_fmt(d.get('snapshots'))} "
                f"| {_fmt(d.get('truncations'))} "
                f"| {_fmt(d.get('bytes_freed'))} "
                f"| {_fmt(d.get('log_bytes_final_max'))} "
                f"| {_fmt(d.get('recovery_ms'))} |")
    if progress:
        out += ["", "## Repo growth (PROGRESS.jsonl, per driver round)", "",
                "| round | commits | LoC | wall h |", "|" + "---|" * 4]
        out += [f"| {p['round']} | {_fmt(p['commits'])} | {_fmt(p['loc'])} "
                f"| {p['wall_h']} |" for p in progress]
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        "trend", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--json", action="store_true",
                    help="emit the collected rows as JSON instead of "
                         "markdown")
    ap.add_argument("--repo", default=str(REPO),
                    help="repo root holding the artifacts")
    args = ap.parse_args(argv)
    repo = Path(args.repo)
    bench = collect_bench_rows(repo)
    tcp = collect_tcp_row(repo)
    progress = collect_progress(repo)
    health = collect_health_rows(repo)
    verify = collect_verify_rows(repo)
    soak = collect_soak_rows(repo)
    durability = collect_durability_rows(repo)
    if args.json:
        print(json.dumps({"bench": bench, "tcp": tcp,
                          "progress": progress, "health": health,
                          "verify": verify, "soak": soak,
                          "durability": durability},
                         indent=1))
    else:
        print(render_markdown(bench, tcp, progress, health, verify,
                              soak, durability))
    return 0


if __name__ == "__main__":
    sys.exit(main())
