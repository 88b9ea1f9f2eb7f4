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
        benchmarks/configs/minpaxos5_pod_share.json [--tree DIR] [--hlo OUT] \\
        [--scopes] [--from-hlo OUT]

Beside the placement it counts EVERY gather fusion, whatever its size,
by its innermost ``px.*`` scope (``gather_scopes``): which sections
still gather elements, e.g. the route's ``px.route.gather`` in the
kernels past ``ONEHOT_PAIRS`` (PR 38).

``--scopes`` names the device time that a trace files "under no scope"
(PERF.md section 5: the largest bucket of both pod rounds): a ``with
jax.named_scope`` is not missing there, the compiler gives a fusion its
ROOT's metadata or none, while the instructions fused into it keep
theirs. So the listing gives, for every fusion whose own ``op_name`` has
no ``px.`` component, its output shape and the ``px.*`` scopes of the
instructions inside its fused computation. ``--from-hlo`` reads a text
that ``--hlo`` wrote and compiles nothing.

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


def gather_scopes(hlo: str) -> dict:
    """{innermost ``px.*`` scope: n} over ALL gather fusions, whatever
    their size (``gather_placement`` counts only the window-sized:
    Mencius's small-tier route gathers ``s32[16,5,1152]`` lie below
    its 100,000 elements)."""
    found = collections.Counter()
    for line in hlo.splitlines():
        op = _op_name(line)
        if " fusion(" in line and "kind=kCustom" in line \
                and op.endswith("/gather"):
            found[_innermost_scope(op) or "-"] += 1
    return dict(sorted(found.items()))


def _innermost_scope(op_name: str) -> str | None:
    found = re.findall(r"px\.[a-z_0-9.]*[a-z_0-9]", op_name)
    return found[-1] if found else None


def _op_name(line: str) -> str:
    op = re.search(r'op_name="([^"]*)"', line)
    return op.group(1) if op else ""


def _computations(hlo: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    body = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\) -> .*\{$", line)
        if head:
            body = comps[head.group(1)] = []
        elif line.startswith("}"):
            body = None
        elif body is not None:
            body.append(line)
    return comps


def unscoped_fusions(hlo: str) -> list[dict]:
    """The device kernels whose OWN ``op_name`` holds no ``px.``
    component (a trace's scope reduction files their time "under no
    scope"): fusions, to which the compiler gives their root's metadata
    or none, and the copies, scatters and sorts it leaves unfused. Each
    kind of them once, largest compiler estimate first: ``op`` and
    output ``shape``, the ``branch`` path of the computation it stands
    in, how many such there are (``n``), the compiler's own
    ``estimated_cycles`` for one run of one (an estimate, not a time;
    0 where it gives none), ``root_op`` (the tail of its own
    ``op_name``), under ``inside`` the innermost ``px.*`` scope of
    every instruction fused into it that has one, nested fusions
    included, with counts, under ``feeds`` the scopes of the kernels
    that read its output (what a kernel with nothing inside, a copy,
    works for) and under ``combines`` what its scatters and reductions
    combine with (``scatter-add``): the one name no pass drops."""
    comps = _computations(hlo)
    fused = set(re.findall(r"calls=%([^,\s]+)", hlo))
    parent: dict[str, tuple[str, str]] = {}  # computation -> (caller, step)
    for name, lines in comps.items():
        for line in lines:
            for i, branch in enumerate(re.findall(
                    r"%([^,\s}]+)", "".join(re.findall(
                        r"branch_computations=\{([^}]*)\}", line)))):
                parent[branch] = (name, f"branch_{i}")
            for body in re.findall(r"body=%([^,\s}]+)", line):
                parent[body] = (name, "")

    def branch_of(comp: str) -> str:
        steps = []
        while comp in parent:
            comp, step = parent[comp]
            steps.append(step)
        return "/".join(filter(None, reversed(steps))) or "-"

    def scopes_inside(comp: str, into: collections.Counter) -> None:
        for line in comps.get(comp, ()):
            scope = _innermost_scope(_op_name(line))
            if scope:
                into[scope] += 1
            call = re.search(r" fusion\(.*calls=%([^,\s]+)", line)
            if call:
                scopes_inside(call.group(1), into)

    def combiners(comp: str) -> set:
        """What the scatters and reductions fused into ``comp`` combine
        with, by the name JAX gave the combiner's arguments
        (``scatter-add``, ``reduce_or``): it outlives the metadata."""
        found = set()
        for line in comps.get(comp, ()):
            for region in re.findall(r"to_apply=%([^,\s]+)", line):
                arg = re.search(r"\((\D[\w-]*?)\.?\d*: ", hlo[hlo.index(
                    "%" + region + " ("):][:200])
                found.add(arg.group(1) if arg else region)
            call = re.search(r" fusion\(.*calls=%([^,\s]+)", line)
            if call:
                found |= combiners(call.group(1))
        return found

    def scopes_of(line: str) -> collections.Counter:
        found = collections.Counter()
        scope = _innermost_scope(_op_name(line))
        call = re.search(r" fusion\(.*calls=%([^,\s]+)", line)
        if scope:
            found[scope] += 1
        elif call:
            scopes_inside(call.group(1), found)
        return found

    table: dict[tuple, dict] = {}
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            head = re.match(r"\s*(?:ROOT )?%(\S+) = (\(?\w+\[[\d,]*\])\S* "
                            r"(?:.*?\) )?([\w-]+)\(", line)
            if not head or not ("estimated_cycles" in line
                                or head.group(3) in ("fusion", "scatter",
                                                     "sort")):
                continue
            if _innermost_scope(_op_name(line)):
                continue
            inside = scopes_of(line) if head.group(3) == "fusion" \
                else collections.Counter()
            feeds = collections.Counter()
            uses = re.compile(r"[(\s]%" + re.escape(head.group(1)) + r"[,)]")
            for other in lines:
                if other is not line and uses.search(other.split(" = ", 1)[-1]):
                    feeds.update(scopes_of(other).keys())
            called = re.search(r"calls=%([^,\s]+)", line)
            kind = re.search(r"kind=(\w+)", line)
            cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
            key = (head.group(3) + (":" + kind.group(1) if kind else ""),
                   head.group(2), branch_of(comp),
                   tuple(sorted(inside.items())), tuple(sorted(feeds)))
            row = table.setdefault(key, {
                "op": key[0], "shape": key[1], "branch": key[2], "n": 0,
                "estimated_cycles": 0,
                "root_op": _op_name(line).rsplit("/", 1)[-1],
                "combines": sorted(combiners(called.group(1)))
                if called else [],
                "inside": dict(inside), "feeds": sorted(feeds)})
            row["n"] += 1
            row["estimated_cycles"] = max(
                row["estimated_cycles"], int(cycles.group(1)) if cycles else 0)
    return sorted(table.values(), key=lambda r: -r["estimated_cycles"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config", help="a pod configuration of benchmarks/configs")
    ap.add_argument("--tree", help="import the program from this checkout")
    ap.add_argument("--hlo", help="write the optimised HLO text here")
    ap.add_argument("--scopes", action="store_true",
                    help="also list the fusions whose own op_name has no "
                         "px.* scope, with the scopes of what they hold")
    ap.add_argument("--from-hlo", help="read an optimised HLO text that "
                                       "--hlo wrote, and compile nothing")
    args = ap.parse_args()
    if args.from_hlo:
        hlo = pathlib.Path(args.from_hlo).read_text()
        out = {"config": pathlib.Path(args.config).stem,
               "from_hlo": args.from_hlo,
               "gather_fusions": gather_placement(hlo),
               "gather_scopes": gather_scopes(hlo)}
        if args.scopes:
            out["unscoped_fusions"] = unscoped_fusions(hlo)
        print(json.dumps(out))
        return 0
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
    owners = c["runner"].startswith("pod_mencius")
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
    out = {
        "config": c["name"], "tree": args.tree or ".",
        "compiled_for": "v5e (described, not attached)",
        "lowered_bytes": len(text),
        "lowered_sha256": hashlib.sha256(text.encode()).hexdigest()[:16],
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(time.monotonic() - t1, 1),
        "gather_fusions": gather_placement(hlo),
        "gather_scopes": gather_scopes(hlo)}
    if args.scopes:
        out["unscoped_fusions"] = unscoped_fusions(hlo)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
