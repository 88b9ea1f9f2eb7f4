"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # on a machine with a TPU: exit 0
    JAX_PLATFORMS=cpu python chip_smoke.py --dry-cpu   # debug the command

ONE process owns the chip for the whole run and drives the system's
main paths through the entry points a user calls, at full width:

* **Phase A — the device-resident consensus loop** (README "Sharded
  mode", the path the benchmark's pod cells measure): ``ShardedCluster``
  + ``begin_resident`` / ``run_resident`` / ``end_resident``, MinPaxos,
  N=5 majority, at ``minpaxos_tpu.deployments``' on-chip shape (g=256,
  w=4096, p=512: 1,048,576 concurrent instances). Healthy dispatches
  (the round's gated recovery section never runs: its gate's count
  reads 0), the quorum dead with slots in flight (the gate of the
  leader's retry must open) -> revived, kill one follower -> dead
  rounds -> revive -> reheal on the same compiled variant, drain. With
  more than one device visible the shard axis is laid over all of
  them. (The Mencius pod's recovery has its own program on the chip:
  tools/mencius_recovery.py.)
* **Phase B — the served path** (README "Distributed mode", BASELINE
  config 1): a master and three ``-min -durable`` replica servers in
  this process (every replica's step on the chip, fsync on), composed
  from the server binary's own flags at ``deployments.SERVER_SHAPE``;
  load arrives over localhost TCP from the normal client binary, a
  child that imports no JAX.

* **Phase C — served Mencius** (``cli/server.py -m``, the benchmark's
  configuration ``mencius3_durable``): three ``-m -durable`` replica
  servers at ``deployments.MENCIUS_SERVER_SHAPE``, every one a proposer;
  a few hundred requests on overlapping keys go round-robin over all
  three owners (the upstream client's ``-e``) and every reply is held
  to the merged log replayed slot by slot.

Every check compares against something independent of the code under
test: the proposal stream replayed on the host into a Python dict
(phase A), the client's exactly-once book, the invariant checker over
the three durable stores and the committed log replayed into a dict
(phase B) — acknowledged writes are read back from EVERY replica's
table, not a quorum's worth.

It exits 0 only if JAX's first device is a ``tpu`` and every check
held. It never falls back: no TPU -> non-zero exit before any compile,
and nothing is printed on stdout. It prints two stdout lines, each one
JSON object: first the record (versions, shapes, per-phase checks,
set-up and wall seconds of a smoke — not throughput or latency), then,
LAST, the verdict the chip check reads, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``
with the device as JAX reports it. ``--dry-cpu`` (tiny shapes, needs
``JAX_PLATFORMS=cpu``) is how the command is debugged off the chip; its
last line says ``"dry": true`` and carries no ``"ok"``.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import importlib.metadata
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

#: rounds per resident dispatch in phase A — ONE compiled variant of
#: the k-round scan serves every leg, because the smoke has to fit the
#: chip tool's time limit: at this shape each variant compiled for
#: 4-5 minutes on a v5e and a round ran for ~6 s (PR 21). The dead
#: leg stays at k = 2 rounds, a gap k*p inside the leader's retention
#: (w//2 slots), so this smoke's victim is healed by catch-up rows as it
#: always was and the schedule keeps inside the chip tool's time limit;
#: an outage beyond retention is healed on the device by a state
#: transfer since PR 33 (parallel/sharded.py ``transfer_round``), which
#: the benchmark's cell ``pod128_kill_recover`` runs.
K_ROUNDS = 2
HEALTHY_DISPATCHES = 3
#: no-proposal dispatches with the quorum dead and slots in flight:
#: the leader's stall counter has to reach RETRY_STALL_TICKS, four
#: rounds after the frontier's last move, for its retry to run
STALLED_DISPATCHES = 4
RECOVERY_DISPATCHES = 4
#: no-proposal dispatches allowed for the drain: until nothing is in
#: flight AND every replica has committed and executed what the leader
#: has (catch-up serves one peer per round, ~catchup_rows/2 slots, so
#: at 2p it only keeps pace with a full-rate stream: the revived
#: follower's gap closes once the proposals stop)
MAX_DRAIN_DISPATCHES = 12
#: shards whose whole KV table is held to the host replay (seeded pick)
REFERENCE_SHARDS = 4

#: phase B load (dry mode: a toy)
CLIENT_Q, CLIENT_KEYS = 20_000, 100_000
DRY_CLIENT_Q, DRY_CLIENT_KEYS = 2_000, 1_000
DRY_SERVER_SHAPE = ["-window", "1024", "-inbox", "1024", "-kvpow2", "12",
                    "-execbatch", "512"]
BOOT_TIMEOUT_S = 600.0
#: phase C load: enough for every owner to propose, cede and be ceded
#: to; few keys, so that the three owners' writes collide
MENCIUS_Q, MENCIUS_KEYS = 600, 48


_T0 = time.monotonic()


def _log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class _CompileMeter:
    """Counts what JAX compiled, from its own monitoring events — the
    per-phase set-up seconds the record line reports."""

    KEYS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
            "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
            "/jax/core/compile/backend_compile_duration": "compile_s"}

    def __init__(self) -> None:
        import jax

        self.durations: dict[str, list[float]] = {
            v: [] for v in self.KEYS.values()}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name: str, secs: float, **_kw) -> None:
        key = self.KEYS.get(name)
        if key:
            self.durations[key].append(secs)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        """Totals since the last take()."""
        comp = sorted(self.durations["compile_s"], reverse=True)
        out = {"compilations": len(comp),
               "persistent_cache_hits": self.cache_hits,
               "largest_compile_s": [round(c, 1) for c in comp[:4]]}
        for key, vals in self.durations.items():
            out[key] = round(sum(vals), 1)
            vals.clear()
        self.cache_hits = 0
        return out


def _memory() -> list[dict]:
    """Per-device allocator counters, where the backend reports them
    (a TPU does; XLA:CPU returns None)."""
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out


def _bytes_by_device(tree) -> dict[int, int]:
    """Bytes of ``tree``'s leaves held on each device, from the arrays'
    own addressable shards."""
    import jax

    held: dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return held


# ------------------------------------------------------------ phase A

def phase_a(meter: _CompileMeter, on_tpu: bool, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from minpaxos_tpu import deployments
    from minpaxos_tpu.ops.kvstore import LIVE, kv_lookup
    from minpaxos_tpu.ops.workload import propose_batch_host
    from minpaxos_tpu.parallel import make_mesh
    from minpaxos_tpu.parallel.sharded import ShardedCluster, shard_cursors

    g, w, p = (deployments.TPU_SHAPE if on_tpu
               else deployments.CPU_SHAPE)[:3]
    cfg, key_space = deployments.headline_config(on_tpu, w, p)
    n_dev = len(jax.devices())
    mesh = (make_mesh(n_shard_devices=n_dev, n_replica_devices=1)
            if n_dev > 1 else None)
    victim = 2
    out: dict = {
        "shape": {"n_shards": g, "window": w, "proposals": p,
                  "rounds_per_dispatch": K_ROUNDS,
                  "n_replicas": cfg.n_replicas, "q1": cfg.quorum1,
                  "q2": cfg.quorum2, "inbox": cfg.inbox,
                  "kv_pow2": cfg.kv_pow2, "key_space": key_space,
                  "concurrent_instances": g * w,
                  "shard_devices": n_dev},
        "checks": {}}
    checks = out["checks"]
    t_phase = time.perf_counter()

    sc = ShardedCluster(cfg, g, ext_rows=p, mesh=mesh,
                        key_space=key_space, seed=seed)
    sc.elect(0)
    start_committed = sc.committed()[0]
    _log(f"A: init + elect {time.perf_counter() - t_phase:.1f}s")
    sc.begin_resident()

    dispatches: list[dict] = []

    def run(k: int, n_prop: int, tag: str) -> tuple[int, int]:
        r0 = sc._seed
        t0 = time.perf_counter()
        committed, in_flight = sc.run_resident(k, n_prop)
        wall = time.perf_counter() - t0
        dispatches.append({"tag": tag, "round0": r0, "k": k,
                           "proposals": n_prop, "wall_s": round(wall, 3)})
        _log(f"A: {tag}: {k} rounds x {n_prop} proposals in {wall:.2f}s "
             f"(committed {committed}, in flight {in_flight})")
        return committed, in_flight

    # healthy dispatches; the first call is compile + run (set-up)
    run(K_ROUNDS, p, "healthy0_first_call")
    out["setup"] = dict(meter.take(),
                        wall_s=round(time.perf_counter() - t_phase, 1))
    for i in range(1, HEALTHY_DISPATCHES):
        run(K_ROUNDS, p, f"healthy{i}")
    gates_healthy = sc.resident_tiers()["gates"]
    # leave fewer replicas than a quorum alive, nothing offered: what
    # is in flight cannot commit, so the leader's retry must run
    lost = range(cfg.quorum2 - 1, cfg.n_replicas)
    for r in lost:
        sc.kill(r)
    for i in range(STALLED_DISPATCHES):
        run(K_ROUNDS, 0, f"stalled{i}")
    gates_stalled = sc.resident_tiers()["gates"]
    for r in lost:
        sc.revive(r)
    sc.kill(victim)
    run(K_ROUNDS, p, "dead")
    leader_at_revive = np.asarray(shard_cursors(cfg, sc.leader, sc.ss)[0])
    victim_at_revive = np.asarray(sc.ss.states.committed_upto[:, victim])
    sc.revive(victim)
    rehealed_after = None
    for i in range(RECOVERY_DISPATCHES):
        run(K_ROUNDS, p, f"recovery{i}")
        vup = np.asarray(sc.ss.states.committed_upto[:, victim])
        if rehealed_after is None and (vup >= leader_at_revive).all():
            rehealed_after = (i + 1) * K_ROUNDS
    for i in range(MAX_DRAIN_DISPATCHES):
        committed, in_flight = run(K_ROUNDS, 0, f"drain{i}")
        upto = np.asarray(sc.ss.states.committed_upto)      # [G, R]
        executed = np.asarray(sc.ss.states.executed_upto)   # [G, R]
        converged = bool((upto == upto[:, :1]).all()
                         and (executed == upto).all())
        if in_flight == 0 and converged:
            break
    # the scan must not compile again; what shows here is the fault
    # leg's small probes (set_alive, cursor slices)
    out["after_setup"] = meter.take()

    injected = sum(d["k"] * d["proposals"] for d in dispatches) * g
    # which tier the rounds of all the legs above took, and in how
    # many of them each gated section ran (sharded_round)
    out["tiers"] = sc.resident_tiers()
    out["gates_healthy"], out["gates_stalled"] = gates_healthy, gates_stalled
    hist = sc.end_resident()
    dropped = int(np.asarray(sc.ss.states.kv.dropped).sum())
    checks["victim_fell_behind"] = bool(
        (victim_at_revive < leader_at_revive).all())
    checks["victim_rehealed"] = rehealed_after is not None
    checks["drained_in_flight_0"] = in_flight == 0
    checks["committed_equals_injected"] = (
        committed - start_committed == injected)
    checks["frontiers_equal_all_replicas"] = converged
    checks["latency_hist_holds_every_commit"] = (
        int(hist.sum()) == injected and int(hist[-1]) == 0)
    checks["kv_dropped_0"] = dropped == 0
    checks["gates_idle_while_healthy"] = not any(gates_healthy.values())
    checks["gates_ran_while_stalled"] = all(gates_stalled.values())
    out.update(injected=injected, committed=committed - start_committed,
               rehealed_within_rounds=rehealed_after,
               latency_rounds_p50=int(np.searchsorted(
                   np.cumsum(hist), hist.sum() / 2) + 1),
               dispatches=dispatches)

    # -- the plain reference: replay the same (seed, round) proposal
    # stream on the host into a dict per sampled shard, and hold EVERY
    # replica's whole table for that shard to it (all five, not a
    # quorum's worth; the full key space, so no invented key passes)
    shards = sorted(np.random.default_rng(seed).choice(
        g, size=min(REFERENCE_SHARDS, g), replace=False).tolist())
    ref: dict[int, dict[int, int]] = {s: {} for s in shards}
    for d in dispatches:
        if not d["proposals"]:
            continue
        n = d["proposals"]
        for r in range(d["round0"], d["round0"] + d["k"]):
            b = propose_batch_host(cfg.n_replicas, g, p, n, sc.leader, r,
                                   seed, key_space)
            for s in shards:
                ref[s].update(zip(b.key_lo[s, sc.leader, :n].tolist(),
                                  b.val_lo[s, sc.leader, :n].tolist()))
    all_keys = jnp.arange(key_space, dtype=jnp.int32)
    lookup = jax.jit(jax.vmap(
        lambda kv: kv_lookup(kv, jnp.zeros_like(all_keys), all_keys)))
    kv_ok, digest = True, hashlib.sha256(upto.tobytes())
    for s in shards:
        kv_s = jax.tree_util.tree_map(lambda x: x[s], sc.ss.states.kv)
        found, v_hi, v_lo = (np.asarray(x) for x in lookup(kv_s))
        want_found = np.zeros(key_space, bool)
        want_val = np.zeros(key_space, np.int32)
        want_found[list(ref[s])] = True
        want_val[list(ref[s])] = list(ref[s].values())
        live = np.asarray((kv_s.slot == LIVE).sum(axis=-1))
        kv_ok &= bool((found == want_found).all()
                      and (np.where(found, v_lo, 0) == want_val).all()
                      and (v_hi == 0).all()
                      and (live == len(ref[s])).all())
        digest.update(v_lo.tobytes())
    checks["kv_matches_host_replay_all_replicas"] = kv_ok
    out["reference"] = {"shards": shards,
                        "keys_per_shard": [len(ref[s]) for s in shards]}
    # equal across a one-chip and a four-chip run: the workload is a
    # pure function of (seed, round)
    out["state_digest"] = digest.hexdigest()[:16]

    held = _bytes_by_device(sc.ss)
    total = sum(held.values())
    out["state_bytes_by_device"] = {str(k): v for k, v in sorted(held.items())}
    out["memory"] = _memory()
    if n_dev > 1:
        checks["state_split_evenly_over_devices"] = (
            len(held) == n_dev
            and all(abs(v - total / n_dev) <= 0.02 * total / n_dev
                    for v in held.values()))
    want_platform = jax.devices()[0].platform
    checks["state_on_device"] = all(
        d.platform == want_platform
        for d in sc.ss.states.kv.val.devices())
    out["wall_s"] = round(time.perf_counter() - t_phase, 1)
    return out


# ------------------------------------------- phases B and C: served

def _served_cluster(protocol_flag: str, shape: list, key_range: int):
    """``(cluster, cfg, protocol)``: a master and three ``<protocol_flag>
    -durable`` replica servers in this process, over a fresh store —
    exactly what ``python -m minpaxos_tpu.cli.server <protocol_flag>
    -durable <shape>`` would compile and run: the binary's own flag
    parser. Returns once the cluster's own boot wait has held."""
    from minpaxos_tpu.chaos.campaign import ChaosCluster
    from minpaxos_tpu.cli import server as server_cli

    store = ROOT / ".chip_smoke_store"
    shutil.rmtree(store, ignore_errors=True)
    store.mkdir()
    args = server_cli.build_parser().parse_args(
        [protocol_flag, "-durable", *shape, "-keyhint", str(key_range),
         "-storedir", str(store)])
    cfg = server_cli.config_from_args(args, 3)
    flags = dataclasses.asdict(server_cli.flags_from_args(args))
    for owned in ("durable", "store_dir"):  # ChaosCluster passes these
        flags.pop(owned)
    protocol = server_cli.protocol_from_args(args)
    cluster = ChaosCluster(n=3, store_dir=str(store), durable=True,
                           tick_s=flags.pop("tick_s"), flags=flags,
                           cfg=cfg, boot_timeout_s=BOOT_TIMEOUT_S,
                           protocol=protocol)
    return cluster, cfg, protocol


def _wait_first_ticks(cluster) -> None:
    """warm_variants compiles every (k, narrow) step variant on each
    protocol thread before its first tick: serve only once all three
    have ticked."""
    deadline = time.monotonic() + BOOT_TIMEOUT_S
    while not all(s.stats["ticks"] > 0 for s in cluster.servers.values()):
        if time.monotonic() > deadline:
            raise TimeoutError("replicas never ticked after boot")
        time.sleep(0.05)


def _wait_converged(cluster) -> tuple[bool, list]:
    """Quiesce: every replica committed AND executed everything (under
    mencius: three equal MERGED frontiers)."""
    deadline = time.monotonic() + 60
    converged, snaps = False, []
    while not converged and time.monotonic() < deadline:
        time.sleep(0.05)
        snaps = [s.snapshot for s in cluster.servers.values()]
        converged = (len({s["frontier"] for s in snaps}) == 1
                     and all(s.get("executed") == s["frontier"]
                             for s in snaps))
    time.sleep(0.3)  # no in-flight appends under the checker
    return converged, snaps


def _served_shape(cfg, **more) -> dict:
    return {"n_replicas": cfg.n_replicas, "window": cfg.window,
            "inbox": cfg.inbox, "kv_pow2": cfg.kv_pow2,
            "exec_batch": cfg.exec_batch, "durable_fsync": True, **more}


def phase_b(meter: _CompileMeter, on_tpu: bool) -> dict:
    import jax
    import numpy as np

    from minpaxos_tpu.deployments import SERVER_SHAPE
    from minpaxos_tpu.ops.kvstore import LIVE, kv_lookup
    from minpaxos_tpu.ops.packed import split_i64
    from minpaxos_tpu.runtime.client import gen_workload
    from minpaxos_tpu.verify.invariants import check_cluster
    from minpaxos_tpu.wire.messages import Op

    q, key_range = ((CLIENT_Q, CLIENT_KEYS) if on_tpu
                    else (DRY_CLIENT_Q, DRY_CLIENT_KEYS))
    t_phase = time.perf_counter()
    cluster, cfg, _ = _served_cluster(
        "-min", SERVER_SHAPE if on_tpu else DRY_SERVER_SHAPE, key_range)
    _log(f"B: leader prepared after {time.perf_counter() - t_phase:.1f}s")
    out: dict = {
        "shape": _served_shape(cfg, requests=q, key_range=key_range,
                               write_pct=50),
        "checks": {}}
    checks = out["checks"]
    cli = None
    try:
        _wait_first_ticks(cluster)
        out["setup"] = dict(meter.take(),
                            wall_s=round(time.perf_counter() - t_phase, 1))
        _log(f"B: 3 replicas serving after {out['setup']['wall_s']}s")

        # -- load: the normal client binary, a JAX-free child
        cmd = [sys.executable, "-m", "minpaxos_tpu.cli.client",
               "-mport", str(cluster.mport), "-q", str(q),
               "-sr", str(key_range), "-w", "50", "-check",
               "-timeout", "300"]
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                              text=True, timeout=600)
        out["client_wall_s"] = round(time.perf_counter() - t0, 2)
        out["client_tail"] = proc.stdout.strip().splitlines()[-3:]
        _log(f"B: client rc={proc.returncode}: {out['client_tail']}")
        if proc.returncode != 0:
            _log(proc.stderr[-2000:])
        checks["client_exactly_once"] = (
            proc.returncode == 0
            and f"{q}/{q} acked" in proc.stdout
            and "CHECK OK: exactly-once" in proc.stdout)

        # -- read every acknowledged write back through the served path
        # (the client binary's workload is a pure function of its flags)
        ops, keys, vals = gen_workload(q, conflict_pct=0,
                                       key_range=key_range, zipf_s=0.0,
                                       write_pct=50, seed=42)
        put_keys = np.unique(keys[ops == int(Op.PUT)])
        rb = len(put_keys)
        ops_all = np.concatenate([ops, np.full(rb, int(Op.GET), np.int64)])
        keys_all = np.concatenate([keys, put_keys])
        vals_all = np.concatenate([vals, np.zeros(rb, np.int64)])
        cli = cluster.client()
        st = cli.run_partition(np.arange(q, q + rb), ops_all, keys_all,
                               vals_all, timeout_s=300.0)
        checks["readback_all_acked_once"] = (
            st["acked"] == rb and st["duplicates"] == 0)

        checks["replicas_converged"], _ = _wait_converged(cluster)

        # the reply book the checker holds the log to: every PUT the
        # client binary reported acknowledged (an acked write absent
        # from the committed log is data loss) + the read-back replies
        with cli._lock:
            replies = dict(cli.replies)
        replies.update({int(c): {} for c in
                        np.nonzero(ops == int(Op.PUT))[0]})
        report = check_cluster(cluster.stores(), replies=replies,
                               workload=(ops_all, keys_all, vals_all))
        out["invariants"] = report.to_dict()
        checks["invariants_hold"] = report.ok
        checks["readback_gets_checked"] = report.checked_gets == rb

        # the committed log, replayed on the host into a dict: what
        # every replica's device table must hold
        log0 = cluster.servers[0].store
        rec = log0.read_range(0, log0.committed_prefix())
        want: dict[int, int] = {}
        for op, cid, key, val in zip(rec["op"].tolist(),
                                     rec["client_id"].tolist(),
                                     rec["key"].tolist(),
                                     rec["val"].tolist()):
            if cid >= 0 and op == int(Op.PUT):
                want[key] = val
        checks["log_holds_every_put_key"] = (
            sorted(want) == put_keys.tolist())
    finally:
        if cli is not None:
            cli._done = True
            cli.close_conn()
        cluster.stop()  # joins the protocol threads: state is quiescent

    # -- each replica's state lives on the device, and its table holds
    # the replayed log (acknowledged writes read back from all three)
    want_platform = jax.devices()[0].platform
    k_hi, k_lo = split_i64(np.asarray(list(want), np.int64))
    w_hi, w_lo = split_i64(np.asarray(list(want.values()), np.int64))
    on_device, tables_ok = True, True
    for rid, srv in sorted(cluster.servers.items()):
        kv = srv.state.kv
        on_device &= all(d.platform == want_platform
                         for leaf in jax.tree_util.tree_leaves(srv.state)
                         for d in leaf.devices())
        found, v_hi, v_lo = (np.asarray(x) for x in
                             kv_lookup(kv, k_hi, k_lo))
        tables_ok &= bool(found.all() and (v_hi == w_hi).all()
                          and (v_lo == w_lo).all()
                          and int((kv.slot == LIVE).sum()) == len(want))
    checks["replica_state_on_device"] = on_device
    checks["kv_matches_log_replay_all_replicas"] = tables_ok
    out["memory"] = _memory()
    out["serve"] = meter.take()
    out["ticks"] = {str(r): s.stats["ticks"]
                    for r, s in sorted(cluster.servers.items())}
    shutil.rmtree(cluster.store_dir, ignore_errors=True)
    out["wall_s"] = round(time.perf_counter() - t_phase, 1)
    return out


# ------------------------------------------------------------ phase C

def phase_c(meter: _CompileMeter, on_tpu: bool) -> dict:
    import threading

    import numpy as np

    from minpaxos_tpu.deployments import MENCIUS_SERVER_SHAPE
    from minpaxos_tpu.runtime.client import MultiClient, gen_workload
    from minpaxos_tpu.verify.invariants import check_cluster
    from minpaxos_tpu.wire.messages import Op

    n, q = 3, MENCIUS_Q
    t_phase = time.perf_counter()
    cluster, cfg, protocol = _served_cluster(
        "-m", MENCIUS_SERVER_SHAPE if on_tpu else DRY_SERVER_SHAPE,
        MENCIUS_KEYS)
    out: dict = {
        "shape": _served_shape(cfg, protocol=protocol, requests=q,
                               key_range=MENCIUS_KEYS, write_pct=50),
        "checks": {}}
    checks = out["checks"]
    mc = None
    try:
        _wait_first_ticks(cluster)
        out["setup"] = dict(meter.take(),
                            wall_s=round(time.perf_counter() - t_phase, 1))
        _log(f"C: 3 owners serving after {out['setup']['wall_s']}s")
        checks["every_replica_runs_mencius"] = all(
            s.protocol == "mencius" for s in cluster.servers.values())

        # -- load: one connection an owner, in this process (the client
        # imports no JAX); owner 0 gets half, so the others cede turns
        ops, keys, vals = gen_workload(q, conflict_pct=0,
                                       key_range=MENCIUS_KEYS, zipf_s=0.0,
                                       write_pct=50, seed=35)
        mc = MultiClient(cluster.maddr, check=True, mode="rr")
        parts = [np.nonzero(np.isin(np.arange(q) % 4, own))[0]
                 for own in ((0, 1), (2,), (3,))]
        results: list = [None] * n
        threads = [threading.Thread(
            target=lambda r=r: results.__setitem__(
                r, mc.clients[r].run_partition(parts[r], ops, keys, vals,
                                               timeout_s=300.0)),
            daemon=True) for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=330.0)
        out["acked_by_owner"] = [r and r["acked"] for r in results]
        checks["all_acked_once"] = (
            out["acked_by_owner"] == [len(p) for p in parts]
            and sum(r["duplicates"] for r in results) == 0)

        checks["merged_frontiers_converged"], snaps = _wait_converged(
            cluster)

        # every reply against the merged log replayed slot by slot
        replies: dict = {}
        for c in mc.clients:
            with c._lock:
                replies.update(c.replies)
        report = check_cluster(cluster.stores(), replies=replies,
                               workload=(ops, keys, vals))
        out["invariants"] = report.to_dict()
        checks["invariants_hold"] = report.ok
        checks["every_get_checked"] = (
            report.checked_gets == int((ops == int(Op.GET)).sum()))
        stats = [s.stats for _, s in sorted(cluster.servers.items())]
        out["owner_counters"] = [
            {k: st[k] for k in ("client_proposals", "command_slots",
                                "noop_slots", "dispatches")} for st in stats]
        checks["every_owner_proposed"] = (
            [st["client_proposals"] for st in stats]
            == [len(p) for p in parts])
        checks["slots_add_up"] = all(
            st["command_slots"] == q
            and st["command_slots"] + st["noop_slots"]
            == snaps[0]["frontier"] + 1 for st in stats)
    finally:
        if mc is not None:
            mc.close()
        cluster.stop()
    out["serve"] = meter.take()
    shutil.rmtree(cluster.store_dir, ignore_errors=True)
    out["wall_s"] = round(time.perf_counter() - t_phase, 1)
    return out


# --------------------------------------------------------------- main

def verdict(held: bool, device: dict, dry: bool) -> dict:
    """The last stdout line. On the chip it has exactly the keys the
    chip check reads; a dry run's has no ``"ok"`` to be read as a pass."""
    if dry:
        return {"dry": True, "checks_held": held, "device": device}
    return {"ok": held, "device": device}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="workload seed (all data is made from it)")
    ap.add_argument("--dry-cpu", action="store_true",
                    help="debug the command off the chip: tiny shapes, "
                         "requires JAX_PLATFORMS=cpu, never a pass")
    args = ap.parse_args(argv)
    if args.dry_cpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("chip_smoke: --dry-cpu needs JAX_PLATFORMS=cpu set "
              "explicitly", file=sys.stderr)
        return 2

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    want = "cpu" if args.dry_cpu else "tpu"
    if dev.platform != want:
        print(f"chip_smoke: needs a {want} device, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    on_tpu = dev.platform == "tpu"

    from minpaxos_tpu.native import build as native_build
    from minpaxos_tpu.utils.backend import enable_compile_cache

    prebuilt = os.path.exists(native_build.OUT)
    native = ("absent (pure-Python fallbacks)"
              if native_build.build(quiet=True) is None
              else "prebuilt" if prebuilt else "built from clock.cpp")
    cache_dir = enable_compile_cache()
    meter = _CompileMeter()
    _log(f"device {device}; native library: {native}; compile cache: "
         f"{cache_dir}")

    result: dict = {
        "device": device,
        "versions": {n: importlib.metadata.version(n)
                     for n in ("jax", "jaxlib", "libtpu", "numpy")},
        "seed": args.seed, "native_library": native,
        "compile_cache_dir": cache_dir,
        "note": "seconds here are set-up and wall times of a smoke, "
                "not throughput or latency"}
    result["phase_a"] = phase_a(meter, on_tpu, args.seed)
    _log(f"A: checks {result['phase_a']['checks']}")
    result["phase_b"] = phase_b(meter, on_tpu)
    _log(f"B: checks {result['phase_b']['checks']}")
    result["phase_c"] = phase_c(meter, on_tpu)
    _log(f"C: checks {result['phase_c']['checks']}")
    result["wall_s"] = round(time.monotonic() - _T0, 1)
    held = all(all(result[ph]["checks"].values())
               for ph in ("phase_a", "phase_b", "phase_c"))
    print(json.dumps({"record": "chip_smoke", "checks_held": held,
                      **result}), flush=True)
    print(json.dumps(verdict(held, device, args.dry_cpu)), flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
