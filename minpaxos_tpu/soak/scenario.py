"""paxsoak scenario driver: phase manifests, execution, scorecard.

A **manifest** is a plain JSON-able dict describing one soak run:
cluster shape (replicas, quorums), swarm shape (sessions, shards),
and an ordered list of **phases**. Each phase names a workload
profile and an open-loop arrival envelope (soak/profiles.py), and may
attach a chaos fault (installed/cleared at fractions of the phase
window — partition-under-load). The driver:

* boots a ChaosCluster (the chaos-campaign harness shape) and an
  OpenLoopSwarm, and attaches a HealthWatcher at 4 Hz;
* journals every phase boundary as an ``EV_PHASE`` event on EVERY
  replica (``cluster_phase`` fan-out, all-n semantics) so phase edges
  live in the same monotonic event domain as detector raises/clears
  and chaos installs;
* snapshots cluster stats at each boundary, so per-phase deltas of
  the admission gate's counters (``coalesce_admission_rejects``) and
  commit progress are exact;
* after the final drain, joins everything into ONE scorecard —
  ``SOAK.json``: per-phase client latencies + shed/retransmit
  accounting, the detector raise->clear timeline classified against
  the ground-truth fault/phase timeline, per-phase traced stage
  tables (the tools/tail.py math over client + cluster span
  collections), exactly-once totals, and a criteria stanza the
  acceptance gate reads directly.

The JAX-heavy imports (ChaosCluster -> replica) happen inside
``run_scenario``; the manifest/scorecard helpers stay importable by
report-only tools.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np

from minpaxos_tpu.obs.trace import (
    ST_ORIGIN,
    ST_SEND,
    align_collections,
    span_chains,
    stage_decomposition,
    stage_table,
)
from minpaxos_tpu.obs.watch import (
    DET_BACKLOG,
    DET_BURN,
    EV_ALARM,
    EV_AUX,
    EV_KIND,
    EV_PHASE,
    EV_SUBJECT,
    EV_VALUE,
    EV_WALL,
    N_EVENT_FIELDS,
    PHASE_CUSTOM,
    PHASE_KIND_IDS,
    PHASE_KIND_NAMES,
    SLO,
    HealthWatcher,
    counts_by_kind,
)
from minpaxos_tpu.soak.profiles import ArrivalSpec, resolve_profile
from minpaxos_tpu.soak.swarm import OpenLoopSwarm

SCHEMA_VERSION = 1

# --------------------------------------------------------- manifests

#: tier-1 smoke: 2 phases incl. a micro overload burst, tiny swarm,
#: same compiled cluster shape as the chaos smoke (no new variants).
SMOKE_MANIFEST: dict = {
    "name": "smoke",
    "n_replicas": 3, "q1": 0, "q2": 0,
    "sessions": 64, "shards": 2,
    "retransmit_s": 0.75,
    "trace_pow2": 5,
    "seed": 7,
    "drain_timeout_s": 20.0,
    "phases": [
        {"name": "warmup", "kind": "warmup", "profile": "uniform",
         "rate_hz": 200.0, "duration_s": 4.0},
        {"name": "micro_burst", "kind": "overload",
         "profile": "write_storm", "rate_hz": 600.0, "duration_s": 6.0,
         "burst_x": 10.0, "burst_t0_frac": 0.25, "burst_t1_frac": 0.75},
    ],
}

#: the committed SOAK.json run: warmup -> Zipf skew -> open-loop
#: overload burst -> partition-under-load -> heal, then drain.
FULL_MANIFEST: dict = {
    "name": "full",
    "n_replicas": 3, "q1": 0, "q2": 0,
    "sessions": 4096, "shards": 8,
    "retransmit_s": 1.0,
    "trace_pow2": 6,
    "seed": 23,
    "drain_timeout_s": 45.0,
    # durable stores so the crash_restart phase has something to
    # recover from; the snapshot threshold is sized so the multi-
    # minute run still checkpoints + truncates several times (paxdur)
    # WITHOUT the checkpoint pause dominating behavior: take_snapshot
    # syncs the device KV and swaps the segment on the protocol
    # thread, and at 64 KiB (~3 s cadence under this load) those
    # pauses starved the cluster enough to flip the overload
    # backpressure from the coalescer door to the device window —
    # 256 KiB keeps the bounded-disk story while staying off the
    # hot path's back
    "durable": True,
    # size the ingress coalescer's row cap to this host's commit rate
    # (~600 slots/s on the 1-core CI box): the stock cap of inbox/2 =
    # 512 pending rows is ~1 s of queue — sized for a host an order of
    # magnitude faster — so the admission gate's queue-depth arm could
    # never engage before the retransmit horizon. A shed needs BOTH
    # the gate hot AND pending past the cap at put() time, and pending
    # is bounded by arrival_rate x tick_wall (~2.7 rows/ms x 10-20 ms
    # loaded ticks during the burst ≈ 30-55 rows): 32 rows ≈ a device
    # batch puts the cap under the burst's per-tick build-up — so the
    # door sheds DURING the burst, holding the excess at the clients
    # under backoff instead of melting the server queue — while the
    # 250 Hz steady phases build only ~5 rows/tick; the gate still
    # sheds ONLY while the window/burn/backlog arms report overload,
    # so this is deployment sizing, not a synthetic trip.
    "runtime_flags": {"coalesce_rows": 32, "snap_every_bytes": 262144},
    "phases": [
        {"name": "warmup", "kind": "warmup", "profile": "uniform",
         "rate_hz": 300.0, "duration_s": 8.0},
        {"name": "hot_skew", "kind": "skew", "profile": "hot_zipf",
         "rate_hz": 500.0, "duration_s": 10.0,
         "diurnal_amp": 0.3, "diurnal_period_s": 10.0},
        # x9 on the ~600 slots/s host queues ~8k excess commands —
        # decisively past capacity (the gate + burn alarm must trip)
        # yet small enough that the cooldown drains it before the
        # partition phase even on a slow shared-host run; the durable
        # cluster can't absorb the x14 the pre-paxdur record used
        # without the drain racing host variance into the next phase
        {"name": "overload_burst", "kind": "overload",
         "profile": "write_storm", "rate_hz": 300.0, "duration_s": 12.0,
         "burst_x": 9.0, "burst_t0_frac": 0.2, "burst_t1_frac": 0.45},
        # still the overload segment: the burst's shed commands keep
        # retransmitting (with backoff) until admitted, so the gate's
        # tail activity and any residual shedding must be accounted
        # HERE, not bled into the partition phase's books — sized so
        # the burst's ~15k queued excess fully drains before the
        # partition phase starts (the durable cluster's net drain is
        # ~600 slots/s; 25 s at a 60 Hz trickle clears it with margin)
        {"name": "burst_cooldown", "kind": "overload",
         "profile": "uniform", "rate_hz": 60.0, "duration_s": 25.0},
        {"name": "partition_under_load", "kind": "partition",
         "profile": "mixed", "rate_hz": 250.0, "duration_s": 14.0,
         "chaos": {"op": "isolate", "target": 2,
                   "t0_frac": 0.15, "t1_frac": 0.70}},
        {"name": "heal", "kind": "heal", "profile": "uniform",
         "rate_hz": 250.0, "duration_s": 8.0},
        # paxdur: kill a durable follower mid-load, restart it on the
        # same store dir at t1_frac — it must recover from snapshot +
        # redo suffix, catch up live, and the dead-replica stall alarm
        # must raise inside the window, name it, and clear
        {"name": "crash_restart", "kind": "crash_restart",
         "profile": "uniform", "rate_hz": 250.0, "duration_s": 14.0,
         "crash": {"target": 2, "t0_frac": 0.15, "t1_frac": 0.55}},
    ],
}

MANIFESTS = {"smoke": SMOKE_MANIFEST, "full": FULL_MANIFEST}


def phase_arrival(ph: dict) -> ArrivalSpec:
    """The phase dict's arrival-envelope fields as an ArrivalSpec."""
    return ArrivalSpec(
        rate_hz=float(ph["rate_hz"]),
        duration_s=float(ph["duration_s"]),
        burst_x=float(ph.get("burst_x", 1.0)),
        burst_t0_frac=float(ph.get("burst_t0_frac", 0.0)),
        burst_t1_frac=float(ph.get("burst_t1_frac", 0.0)),
        diurnal_amp=float(ph.get("diurnal_amp", 0.0)),
        diurnal_period_s=float(ph.get("diurnal_period_s", 60.0)))


def _chaos_plan(spec: dict, n: int):
    """Build the phase's FaultPlan from its manifest stanza."""
    from minpaxos_tpu.chaos.plan import FaultPlan

    plan = FaultPlan(n, seed=int(spec.get("seed", 1)))
    op = spec.get("op", "isolate")
    if op == "isolate":
        plan.isolate(int(spec["target"]))
    elif op == "partition":
        plan.partition(list(spec["group_a"]), list(spec["group_b"]))
    else:
        raise ValueError(f"unknown soak chaos op {op!r}")
    return plan


def lat_pcts(sorted_ms: list[float]) -> dict:
    """p50/p90/p99/p999/mean/max over an ALREADY sorted latency
    list (the swarm merge's output)."""
    if not sorted_ms:
        return {"n": 0, "p50": 0.0, "p90": 0.0, "p99": 0.0,
                "p999": 0.0, "mean": 0.0, "max": 0.0}
    v = sorted_ms
    pick = lambda q: float(v[min(int(q * len(v)), len(v) - 1)])  # noqa: E731
    return {"n": len(v), "p50": round(pick(0.50), 3),
            "p90": round(pick(0.90), 3), "p99": round(pick(0.99), 3),
            "p999": round(pick(0.999), 3),
            "mean": round(float(np.mean(v)), 3),
            "max": round(float(v[-1]), 3)}


# ------------------------------------------------- scorecard joins


def _stats_totals(resp: dict) -> dict:
    """Cluster-wide counter totals (+ leader frontier) from one stats
    fan-out — the per-phase delta's operands."""
    keys = ("coalesce_admission_rejects", "coalesce_wakeups",
            "coalesce_deadline_hits", "proposals",
            "proposals_rejected", "chaos_injected")
    tot = {k: 0 for k in keys}
    frontier = -1
    for r in resp.get("replicas", []):
        cnt = (r.get("metrics") or {}).get("counters") or {}
        for k in keys:
            tot[k] += int(cnt.get(k, 0))
        frontier = max(frontier, int(r.get("frontier", -1)))
    tot["frontier"] = frontier
    return tot


def _stats_delta(a: dict, b: dict) -> dict:
    out = {k: b[k] - a[k] for k in a if k != "frontier"}
    out["committed_slots"] = b["frontier"] - a["frontier"]
    return out


def classify_alarms(alarms: list[dict], phases: list[dict],
                    fault_windows: list[dict]) -> list[dict]:
    """Annotate each HealthWatcher alarm with the phase its raise
    landed in and whether it fell inside a ground-truth fault window
    (install..clear + a grace for detector window lag)."""
    out = []
    for a in alarms:
        rec = {"detector": a["detector"], "subject": a["subject"],
               "t_raised": a["t_raised"], "t_cleared": a["t_cleared"]}
        rec["phase"] = next(
            (p["name"] for p in phases
             if p["t0_wall"] <= a["t_raised"] < p["t1_wall"]), None)
        fw = next((w for w in fault_windows
                   if w["t_install"] <= a["t_raised"]
                   <= w["t_clear"] + w.get("grace_s", 3.0)), None)
        rec["in_fault_window"] = fw is not None
        rec["cleared_after_heal"] = (
            a["t_cleared"] is not None
            and (fw is None or a["t_cleared"] >= fw["t_clear"]))
        out.append(rec)
    return out


def phase_stage_tables(collections: list[dict],
                       phases: list[dict]) -> dict:
    """The tools/tail.py math (align -> chains -> decomposition ->
    stage table), bucketed per phase: a chain belongs to the phase its
    SEND boundary's wall time lands in. Returns ``{"overall": table,
    "per_phase": {name: table}}``."""
    ref = next((c["anchor"] for c in collections if c.get("anchor")),
               None)
    chains = span_chains(align_collections(collections,
                                           ref_anchor=ref))
    decomp = stage_decomposition(chains)
    ref_off = (ref["wall_ns"] - ref["mono_ns"]) if ref else 0
    per: dict[str, list] = {p["name"]: [] for p in phases}
    for d in decomp:
        st = chains.get(d["trace_id"], {})
        start = st.get(ST_SEND) or st.get(ST_ORIGIN)
        if start is None:
            continue
        wall_s = (start[0] + ref_off) / 1e9
        for p in phases:
            if p["t0_wall"] <= wall_s < p["t1_wall"]:
                per[p["name"]].append(d)
                break
    return {"overall": stage_table(decomp),
            "per_phase": {name: stage_table(ds)
                          for name, ds in per.items()}}


def _journal_events(events_resp: dict) -> np.ndarray:
    """All replicas' journal rows from one ``cluster_events`` fan-out
    (wall column is absolute; no alignment needed for wall joins)."""
    rows = []
    for r in events_resp.get("replicas", []):
        j = r.get("journal") or {}
        ev = np.asarray(j.get("events") or [], np.int64)
        if ev.size:
            rows.append(ev.reshape(-1, N_EVENT_FIELDS))
    return (np.concatenate(rows) if rows
            else np.zeros((0, N_EVENT_FIELDS), np.int64))


def evaluate_criteria(scorecard: dict) -> dict:
    """The acceptance stanza, computed from the joined record:

    * ``admission_organic`` — the gate shed rows during every
      overload-kind phase and NOWHERE else;
    * ``overload_alarm_journaled`` — a burn/backlog EV_ALARM edge
      (replica- or watcher-journaled) inside an overload window;
    * ``partition_detected_in_window`` — every watcher raise during a
      partition-kind phase fell inside the ground-truth fault window
      AND cleared after heal (vacuously false if no alarm raised at
      all during a partition phase);
    * ``crash_detected_and_attributed`` — some frontier-stall alarm
      raised during a crash_restart-kind phase fell inside the
      ground-truth kill..restart window and NAMED the killed replica,
      and every crash-phase stall alarm eventually cleared. Mirrors
      the chaos campaign's ``_stall_verdict`` quantifiers exactly:
      the edge-detected alarm legitimately flaps under load, and the
      clear is NOT required to land after the restart mark — the
      detector clears the moment the recovered replica's frontier
      resumes advancing during catch-up, which is seconds BEFORE the
      restart call (which waits out post-boot settling) stamps the
      window closed (vacuously true with no crash phases — the smoke
      manifest);
    * ``exactly_once`` — 0 lost across all shards, duplicates
      absorbed client-side.
    """
    phases = scorecard["phases"]
    overload = [p for p in phases if p["kind"] == "overload"]
    other = [p for p in phases if p["kind"] != "overload"]
    admission_organic = (
        bool(overload)
        and any(p["cluster"]["coalesce_admission_rejects"] > 0
                for p in overload)
        and all(p["cluster"]["coalesce_admission_rejects"] == 0
                for p in other))
    alarm_edges = scorecard["alarm_edges"]
    overload_alarm = any(
        e["detector"] in ("p99_burn_rate", "backlog_growth")
        and any(p["t0_wall"] <= e["wall_s"] < p["t1_wall"]
                for p in overload)
        for e in alarm_edges)
    part_names = {p["name"] for p in phases if p["kind"] == "partition"}
    part_alarms = [a for a in scorecard["alarms"]
                   if a["phase"] in part_names]
    partition_ok = (bool(part_alarms)
                    and all(a["in_fault_window"]
                            and a["cleared_after_heal"]
                            for a in part_alarms)
                    ) if part_names else True
    # crash_restart phases: the kill target is ground truth from the
    # manifest; the dead-replica stall alarm must land in the window,
    # name the corpse, and clear once the restart catches up
    crash_targets = {
        p["name"]: int(p.get("crash", {}).get("target", -1))
        for p in scorecard.get("manifest", {}).get("phases", [])
        if p.get("kind") == "crash_restart" and p.get("crash")}
    crash_alarms = [a for a in scorecard["alarms"]
                    if a["phase"] in crash_targets
                    and a["detector"] == "frontier_stall"]
    crash_ok = (bool(crash_alarms)
                and any(a["in_fault_window"]
                        and a["subject"] == crash_targets[a["phase"]]
                        for a in crash_alarms)
                and all(a["t_cleared"] is not None
                        for a in crash_alarms)
                ) if crash_targets else True
    eo = scorecard["exactly_once"]
    exactly_once = eo["lost"] == 0 and eo["acked_unique"] > 0
    crit = {"admission_organic": admission_organic,
            "overload_alarm_journaled": overload_alarm,
            "partition_detected_in_window": partition_ok,
            "crash_detected_and_attributed": crash_ok,
            "exactly_once": exactly_once}
    crit["ok"] = all(crit.values())
    return crit


# ----------------------------------------------------------- driver


def run_scenario(manifest: dict, log=print) -> dict:
    """Execute one manifest end to end and return the scorecard
    (SOAK.json's content). Boots its own cluster; everything is torn
    down on the way out, success or not."""
    from minpaxos_tpu.chaos.campaign import (STALL_SLACK_SLOTS,
                                             ChaosCluster)
    from minpaxos_tpu.runtime.master import (cluster_chaos,
                                             cluster_events,
                                             cluster_phase,
                                             cluster_stats,
                                             cluster_tracespans)

    n = int(manifest.get("n_replicas", 3))
    t_start = time.time()
    log(f"paxsoak[{manifest['name']}]: booting {n}-replica cluster")
    cluster = ChaosCluster(n=n, q1=int(manifest.get("q1", 0)),
                           q2=int(manifest.get("q2", 0)),
                           durable=bool(manifest.get("durable", False)),
                           flags=manifest.get("runtime_flags"))
    swarm = None
    watcher = None
    fault_windows: list[dict] = []
    try:
        swarm = OpenLoopSwarm(
            cluster.maddr, sessions=int(manifest["sessions"]),
            shards=int(manifest["shards"]),
            retransmit_s=float(manifest.get("retransmit_s", 1.0)),
            trace_pow2=manifest.get("trace_pow2"))
        log(f"paxsoak: starting swarm "
            f"({manifest['sessions']} sessions / "
            f"{manifest['shards']} shards)")
        swarm.start()
        watcher = HealthWatcher(
            poll_fn=lambda: cluster_stats(cluster.maddr, timeout_s=5.0),
            slo=SLO(stall_s=0.6, stall_slack_slots=STALL_SLACK_SLOTS,
                    churn_window_s=5.0, churn_budget=4),
            interval_s=0.25)
        watcher.start()
        phases_out: list[dict] = []
        seed = int(manifest.get("seed", 0))
        for i, ph in enumerate(manifest["phases"]):
            kind = ph.get("kind", "custom")
            kind_id = PHASE_KIND_IDS.get(kind, PHASE_CUSTOM)
            arrival = phase_arrival(ph)
            resp = cluster_phase(cluster.maddr, i, kind_id,
                                 int(arrival.duration_s * 1e3))
            if not resp.get("ok"):
                raise RuntimeError(
                    f"EV_PHASE fan-out incomplete for phase {i}: {resp}")
            stats0 = _stats_totals(cluster_stats(cluster.maddr))
            t0_wall = time.time()
            timers: list[threading.Timer] = []
            if ph.get("chaos"):
                spec = ph["chaos"]
                plan = _chaos_plan(spec, n)
                window = {"phase": ph["name"], "plan": plan.to_dict(),
                          "t_install": None, "t_clear": None,
                          "grace_s": 3.0}
                fault_windows.append(window)

                def install(w=window, p=plan):
                    w["t_install"] = time.time()
                    r = cluster_chaos(cluster.maddr, op="install",
                                      plan=p.to_dict())
                    if not r.get("ok"):
                        log(f"paxsoak: WARN chaos install partial: {r}")

                def clear(w=window):
                    r = cluster_chaos(cluster.maddr, op="clear")
                    w["t_clear"] = time.time()
                    if not r.get("ok"):
                        log(f"paxsoak: WARN chaos clear partial: {r}")

                d = arrival.duration_s
                t_in = float(spec.get("t0_frac", 0.1)) * d
                t_out = float(spec.get("t1_frac", 0.7)) * d
                if not 0 <= t_in < t_out <= d:
                    raise ValueError(
                        f"chaos window [{t_in}, {t_out}] outside "
                        f"phase of {d}s")
                timers += [threading.Timer(t_in, install),
                           threading.Timer(t_out, clear)]
            if ph.get("crash"):
                # paxdur process fault: kill the target replica at
                # t0_frac, restart it (same ports, same store dir) at
                # t1_frac — a ground-truth fault window the alarm
                # classification joins against, like a chaos window
                spec = ph["crash"]
                rid = int(spec["target"])
                window = {"phase": ph["name"], "crash": {"rid": rid},
                          "t_install": None, "t_clear": None,
                          "grace_s": 3.0}
                fault_windows.append(window)

                def kill(w=window, r=rid):
                    w["t_install"] = time.time()
                    cluster.kill(r)

                def restart(w=window, r=rid):
                    cluster.restart(r)
                    w["t_clear"] = time.time()

                d = arrival.duration_s
                t_in = float(spec.get("t0_frac", 0.15)) * d
                t_out = float(spec.get("t1_frac", 0.55)) * d
                if not 0 <= t_in < t_out <= d:
                    raise ValueError(
                        f"crash window [{t_in}, {t_out}] outside "
                        f"phase of {d}s")
                timers += [threading.Timer(t_in, kill),
                           threading.Timer(t_out, restart)]
            for t in timers:
                t.start()
            log(f"paxsoak: phase {i} '{ph['name']}' ({kind}) — "
                f"{ph['rate_hz']:.0f} Hz x {arrival.duration_s:.0f}s"
                + (f" x{ph['burst_x']} burst" if ph.get("burst_x") else "")
                + (" + chaos" if ph.get("chaos") else "")
                + (" + crash" if ph.get("crash") else ""))
            res = swarm.run_phase(ph.get("profile", "uniform"),
                                  arrival, seed + i)
            for t in timers:
                t.join(timeout=10.0)
            t1_wall = time.time()
            stats1 = _stats_totals(cluster_stats(cluster.maddr))
            lat = lat_pcts(res.pop("lat_ms_sorted"))
            res.pop("shards", None)
            rec = {"ordinal": i, "name": ph["name"], "kind": kind,
                   "kind_id": kind_id, "t0_wall": t0_wall,
                   "t1_wall": t1_wall,
                   "planned": {"profile": ph.get("profile", "uniform"),
                               **arrival.to_dict()},
                   "client": {**res, "lat_ms": lat},
                   "cluster": _stats_delta(stats0, stats1)}
            phases_out.append(rec)
            log(f"paxsoak:   sent={res['sent']} acked={res['acked']} "
                f"retx={res['retransmits']} "
                f"outstanding={res['outstanding']} "
                f"p99={lat['p99']:.1f}ms "
                f"shed={rec['cluster']['coalesce_admission_rejects']}")
        # ---- drain: settle every outstanding command (exactly-once) --
        di = len(manifest["phases"])
        cluster_phase(cluster.maddr, di, PHASE_KIND_IDS["drain"], 0)
        t_d0 = time.time()
        drain = swarm.drain(float(manifest.get("drain_timeout_s", 30.0)))
        lat_d = lat_pcts(drain.pop("lat_ms_sorted"))
        drain.pop("shards", None)
        t_d1 = time.time()
        log(f"paxsoak: drain acked={drain['acked']} "
            f"outstanding={drain['outstanding']}")
        # settle detectors: let anything raised by the tail of the run
        # clear while the cluster idles, so clear edges are recorded
        time.sleep(3.0)
        watcher.stop()
        final = swarm.stop()
        events_rows = _journal_events(cluster_events(cluster.maddr))
        spans = cluster_tracespans(cluster.maddr)
        trace_cols = list(final.pop("traces"))
        for r in spans.get("replicas", []):
            if r.get("trace"):
                trace_cols.append(r["trace"])
    except BaseException:
        if swarm is not None:
            swarm.kill()
        if watcher is not None:
            watcher.stop()
        raise
    finally:
        cluster.stop()

    phases_for_join = phases_out + [{
        "name": "drain", "kind": "drain", "t0_wall": t_d0,
        "t1_wall": t_d1}]
    # raw EV_ALARM edges from the replica+watcher journals: the
    # replica-side burn detector journals its own edges, which the
    # watcher never sees — both count as "edge-journaled"
    all_journals = np.concatenate([
        events_rows,
        np.asarray(watcher.journal.snapshot(), np.int64).reshape(
            -1, N_EVENT_FIELDS)])
    alarm_edges = [
        {"wall_s": int(r[EV_WALL]) / 1e9,
         "detector": {DET_BURN: "p99_burn_rate",
                      DET_BACKLOG: "backlog_growth"}.get(
                          int(r[EV_AUX]), f"det:{int(r[EV_AUX])}"),
         "subject": int(r[EV_SUBJECT])}
        for r in all_journals if int(r[EV_KIND]) == EV_ALARM]
    phase_rows = [
        {"ordinal": int(r[EV_SUBJECT]),
         "kind": PHASE_KIND_NAMES[int(r[EV_AUX])]
         if 0 <= int(r[EV_AUX]) < len(PHASE_KIND_NAMES)
         else f"kind:{int(r[EV_AUX])}",
         "planned_ms": int(r[EV_VALUE]),
         "wall_s": int(r[EV_WALL]) / 1e9}
        for r in events_rows if int(r[EV_KIND]) == EV_PHASE]
    for w in fault_windows:  # a clear that never ran = end of run
        if w["t_clear"] is None:
            w["t_clear"] = time.time()
        if w["t_install"] is None:
            w["t_install"] = w["t_clear"]
    scorecard = {
        "schema": SCHEMA_VERSION,
        "name": manifest["name"],
        "t0_wall": t_start,
        "t1_wall": time.time(),
        "manifest": {k: v for k, v in manifest.items()},
        "phases": phases_out,
        "drain": {"t0_wall": t_d0, "t1_wall": t_d1,
                  **drain, "lat_ms": lat_d},
        "exactly_once": {k: final[k] for k in
                         ("sent_unique", "acked_unique", "lost",
                          "duplicates", "dead_sessions")},
        "alarms": classify_alarms(watcher.alarms, phases_for_join,
                                  fault_windows),
        "alarm_edges": alarm_edges,
        "fault_windows": fault_windows,
        "phase_events": phase_rows,
        "event_counts": counts_by_kind(all_journals),
        "watch": {"samples": len(watcher.samples),
                  "poll_errors": watcher.poll_errors,
                  "alarm_counts": watcher.summary()["alarm_counts"]},
        "stage_tables": phase_stage_tables(trace_cols, phases_for_join),
    }
    scorecard["criteria"] = evaluate_criteria(scorecard)
    scorecard["ok"] = scorecard["criteria"]["ok"]
    return scorecard


def save_scorecard(scorecard: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(scorecard, f, indent=1, sort_keys=True)
        f.write("\n")
