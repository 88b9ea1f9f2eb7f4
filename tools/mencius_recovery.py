"""The Mencius pod through an idle owner and a dead one, on the chip.

No cell of the benchmark has a fault in it (PERF.md section 7), so
this is where the rounds that are NOT steady get a reading:
``mencius5_pod_64k``'s deployment (``deployments.side_shapes``
``mencius_64k``, the benchmark's key space and rounds a dispatch)
through ``ShardedCluster.run_resident``, in phases of a few dispatches
each: every owner loaded; owner 2 offered nothing (it cedes: SKIP rows
flow); owner 1 dead (its slots block the frontier until its successor
takes them over); revived; drained. Per phase: wall ms a round (each
dispatch ends on a readback that blocks), commands committed, which
kernel the rounds took and in how many each recovery gate was open.

    python tools/mencius_recovery.py [--tree DIR] [--replay] [--toy]

``--tree``: import the program from another checkout (the parent's,
unpacked beside this one), to read both on one chip. ``--replay``:
step the first two groups through ``jax.vmap(cluster_step_impl)`` on
the host's CPU backend over the same schedule and hold every leaf of
their final state to the pod's. ``--toy``: a shape for a CPU rehearsal
(no number of it is a measurement). Prints one JSON line; exits 1 if a
check fails.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import pathlib
import sys
import time

K_ROUNDS = 8  # the benchmark's rounds_per_dispatch for this deployment
KEY_SPACE = 8192
IDLE, DEAD = 2, 1
#: (phase, dispatches, owners offered nothing, replica dead)
PHASES = [("steady", 3, (), None), ("idle_owner", 3, (IDLE,), None),
          ("steady_again", 1, (), None), ("dead_owner", 4, (DEAD,), DEAD),
          ("revived", 3, (), None)]
MAX_DRAIN = 24


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree")
    ap.add_argument("--replay", action="store_true")
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--seed", type=int, default=3000031901)
    args = ap.parse_args()
    sys.path.insert(0, args.tree or str(
        pathlib.Path(__file__).resolve().parent.parent))

    import jax
    import numpy as np

    from minpaxos_tpu.deployments import side_shapes
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig
    from minpaxos_tpu.parallel import sharded
    from minpaxos_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()  # the benchmark's: its Mencius cell's program
    if args.toy:
        cfg, groups, ext, key_space = MinPaxosConfig(
            n_replicas=5, window=512, inbox=256, exec_batch=40, kv_pow2=10,
            catchup_rows=16, recovery_rows=16, noop_delay=8), 4, 8, 512
    else:
        cfg, groups, ext, _, _ = side_shapes(True)["mencius_64k"]
        key_space = KEY_SPACE
    seed = args.seed % 0x7FFFFFFF
    owners = cfg.n_replicas
    sc = sharded.ShardedCluster(cfg, groups, ext_rows=ext, protocol="mencius",
                                key_space=key_space, seed=seed)
    schedule = []  # (round0, per-owner counts, alive) of every dispatch

    def dispatch(idle=(), dead=None):
        counts = [0 if o in idle else ext for o in range(owners)]
        alive = [r != dead for r in range(owners)]
        schedule.append((sc._seed, counts, alive))
        t0 = time.monotonic()
        out = sc.run_resident(K_ROUNDS, counts)
        return out, time.monotonic() - t0

    t_start = time.monotonic()
    sc.begin_resident()
    for _ in range(2):  # compile, then warm
        dispatch()
    setup_s = time.monotonic() - t_start
    phases = {}
    for name, n, idle, dead in PHASES:
        if dead is not None:
            sc.kill(dead)
        sc.begin_resident()  # this phase's tier and gate counts
        before = sc.command_counts()
        times = [dispatch(idle, dead)[1] for _ in range(n)]
        after, tiers = sc.command_counts(), sc.resident_tiers()
        phases[name] = {
            "ms_per_round": [1e3 * t / K_ROUNDS for t in times],
            "commands": after["commands"] - before["commands"],
            "noop_slots": after["noop_slots"] - before["noop_slots"],
            "rounds": tiers["rounds"],
            "kernel_small_rounds": tiers["kernel_small_rounds"],
            "route_small_rounds": tiers["route_small_rounds"],
            "gates": tiers.get("gates")}
        if dead is not None:
            sc.revive(dead)
    for drained in range(1, MAX_DRAIN + 1):
        (_, in_flight), _ = dispatch(idle=range(owners))
        if in_flight == 0:
            break
    counts = sc.command_counts()
    upto = np.asarray(sc.ss.states.committed_upto)
    # what the program did, for the record: the slow takeover and the
    # revived owner's trailing frontier are the parent's too
    observed = {"drained": in_flight == 0,
                "commands_uncommitted": counts["assigned"]
                - counts["commands"],
                "frontier_lag_slots": int((upto.max(axis=1)
                                           - upto.min(axis=1)).max())}
    checks = {"idle_owner_ceded": phases["idle_owner"]["noop_slots"] > 0}
    gates = {name: p["gates"] for name, p in phases.items()}
    if gates["steady"] is not None:  # a program with recovery gates
        checks["gates_shut_while_steady_or_ceding"] = not any(
            n for name in ("steady", "idle_owner", "steady_again")
            for n in gates[name].values())
        checks["gates_open_while_taking_over"] = all(
            gates["dead_owner"].values())
    out = {"tree": args.tree or ".", "seed": args.seed,
           "device": {"platform": jax.devices()[0].platform,
                      "kind": jax.devices()[0].device_kind},
           "shape": {"groups": groups, "window": cfg.window,
                     "inbox": cfg.inbox, "ext": ext,
                     "working_capacity": tiers["working_capacity"]},
           "setup_s": setup_s, "phases": phases, "drain_dispatches": drained,
           "commands": counts, "observed": observed}
    if args.replay:
        out["replay"], checks["pod_equals_host_replay"] = _replay(
            cfg, groups, ext, key_space, seed, schedule, sc.ss)
    out["checks"] = checks
    print(json.dumps(out))
    return 0 if all(checks.values()) else 1


def _digest(tree) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()[:16]


def _replay(cfg, groups, ext, key_space, seed, schedule, pod_ss, n=2):
    """The first ``n`` groups (groups share nothing) stepped on the
    host's CPU backend by ``jax.vmap(cluster_step_impl)``, which knows
    no tier and no gate, over the pod's schedule; the digests of both
    final states and whether every leaf is equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from minpaxos_tpu.models.cluster import cluster_step_impl
    from minpaxos_tpu.models.mencius import init_mencius, mencius_step_impl
    from minpaxos_tpu.parallel import sharded

    t0 = time.monotonic()
    with jax.default_device(jax.devices("cpu")[0]):
        step = jax.jit(jax.vmap(lambda cs, e: cluster_step_impl(
            cfg, cs, e, mencius_step_impl)[0]))
        ss = sharded.init_sharded(cfg, n, None, init_mencius)
        for round0, counts, alive in schedule:
            ss = ss._replace(alive=jnp.broadcast_to(
                jnp.asarray(alive), ss.alive.shape))
            for r in range(round0, round0 + K_ROUNDS):
                e = sharded.make_propose_ext(
                    cfg, groups, ext, jnp.asarray(counts, jnp.int32),
                    jnp.int32(-1), jnp.int32(r), jnp.int32(seed), key_space,
                    True)
                ss = step(ss, jax.tree_util.tree_map(lambda x: x[:n], e))
        want = jax.tree_util.tree_map(np.asarray, ss)
    got = jax.tree_util.tree_map(lambda x: np.asarray(x[:n]), pod_ss)
    equal = all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))
    return {"groups": n, "rounds": len(schedule) * K_ROUNDS,
            "pod": _digest(got), "host": _digest(want),
            "seconds": time.monotonic() - t0}, equal


if __name__ == "__main__":
    sys.exit(main())
