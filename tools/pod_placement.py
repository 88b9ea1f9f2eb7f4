"""Where the chip's compiler puts a pod dispatch's gathers, read WITHOUT
the chip.

The pod round is made of element gathers (PERF.md section 7 row 13),
and what one costs depends on whether XLA's memory-space assignment
places its output in the fast memory space (``S(1)`` in the optimised
HLO's layouts) or leaves it in HBM: 6.67 against 13-18 ms apiece in
``pod128_steady`` (PERF.md section 7 row 15). That placement is a
packing heuristic over the whole program: kernels of unequal structure
under the round's ``cond`` lost it in every kernel at once. This tool
compiles ``sharded_run_resident`` at a pod configuration's real shape
for a DESCRIBED v5e (nothing runs, no chip is needed; a minute or
three of this machine's CPU) and counts the gather fusions by
conditional branch and memory space, so that a change to the round's
structure can be checked before it costs a chip run. Its verdicts
matched the chip's in PR 31 (parent and the handed-in round: none in
HBM; three rounds with a steady kernel beside plain ones: 11-13 a
kernel and 11 of 13 a route in HBM, the rounds the chip had timed at
487-507 ms against 424.5).

    JAX_PLATFORMS=cpu python tools/pod_placement.py \\
        benchmarks/configs/minpaxos5_pod_share.json [--tree DIR] [--hlo OUT]

Prints one JSON line. It is a compile, never a measurement.
"""

from __future__ import annotations

import argparse
import collections
import hashlib
import inspect
import json
import os
import pathlib
import re
import sys
import time


def gather_placement(hlo: str) -> dict:
    """{branch path: {"S(1)": n, "HBM": n}} over the gather fusions
    (``kind=kCustom`` fusions whose op ends in ``/gather``) of at least
    100,000 elements in an optimised HLO text."""
    table = collections.defaultdict(collections.Counter)
    for line in hlo.splitlines():
        if " fusion(" not in line or "kind=kCustom" not in line:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        shape = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\](\S*)", line)
        if not op or not shape or not op.group(1).endswith("/gather"):
            continue
        elements = 1
        for d in filter(None, shape.group(2).split(",")):
            elements *= int(d)
        if elements < 100_000:
            continue
        branch = "/".join(re.findall(r"branch_\d", op.group(1))) or "-"
        table[branch]["S(1)" if "S(1)" in shape.group(3) else "HBM"] += 1
    return {b: dict(c) for b, c in sorted(table.items())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config", help="a pod configuration of benchmarks/configs")
    ap.add_argument("--tree", help="import the program from this checkout")
    ap.add_argument("--hlo", help="write the optimised HLO text here")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, args.tree or str(
        pathlib.Path(__file__).resolve().parent.parent))

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from minpaxos_tpu.models.minpaxos import MinPaxosConfig, init_replica
    from minpaxos_tpu.parallel import sharded

    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    c = json.loads(pathlib.Path(args.config).read_text())
    owners = c["runner"] == "pod_mencius"
    cfg = MinPaxosConfig(**{k: c[k] for k in (
        "n_replicas", "window", "inbox", "exec_batch", "kv_pow2",
        "catchup_rows", "recovery_rows") + ("noop_delay",) * owners})
    if owners:
        from minpaxos_tpu.models.mencius import init_mencius as init
        from minpaxos_tpu.models.mencius import mencius_step_impl as step
        ext_rows = c["proposals_per_owner"]
    else:
        from minpaxos_tpu.models.minpaxos import replica_step_impl as step
        init, ext_rows = init_replica, c["proposals_per_round"]
    groups, k = c["groups"], c["rounds_per_dispatch"]

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    ss = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(lambda: sharded.init_sharded(cfg, groups, None, init)))
    # ShardedCluster.run_resident's arguments, as shapes
    resident = {
        "cfg": cfg, "n_shards": groups, "ext_rows": ext_rows, "k_rounds": k,
        "ss": ss, "inject_round": i32(groups, cfg.window),
        "lat_hist": i32(sharded.LATENCY_BINS),
        "telemetry": i32(0, sharded.N_TEL_FIELDS), "tiers": i32(3),
        "n_proposals": i32(cfg.n_replicas) if owners else i32(),
        "leader": i32(), "round0": i32(), "seed": i32(), "step_impl": step,
        "key_space": c["key_space"], "substeps": 1, "tel_base": i32(),
        "counts": i32(sharded.N_COUNTS) if owners else None,
        "gate_opens": i32(len(getattr(step, "recovery_gates", ()))),
        # a step with round sections (px.state_transfer) carries their
        # counts; a tree or a step without them takes none
        "recovery": (i32(2 * len(step.round_sections) + 1)
                     if getattr(step, "round_sections", None) else None)}
    params = inspect.signature(sharded.sharded_run_resident).parameters
    t0 = time.monotonic()
    lowered = sharded.sharded_run_resident.lower(
        *[resident[p] for p in params])
    text = lowered.as_text()
    t1 = time.monotonic()
    hlo = lowered.compile().as_text()
    if args.hlo:
        pathlib.Path(args.hlo).write_text(hlo)
    print(json.dumps({
        "config": c["name"], "tree": args.tree or ".",
        "compiled_for": "v5e (described, not attached)",
        "lowered_bytes": len(text),
        "lowered_sha256": hashlib.sha256(text.encode()).hexdigest()[:16],
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(time.monotonic() - t1, 1),
        "gather_fusions": gather_placement(hlo)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
