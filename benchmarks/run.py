"""The benchmark's one command: run one cell once, in this process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell, sets the system up and warms it (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints ONE JSON object as the last line of stdout:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(+ ``breakdown`` with ``--trace 1``) and, last, ``checks`` — each
number compared beside its limit. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.

Everything that belongs to one cell is a file found by the names in
``BENCHMARK.json``: ``configs/<configuration>.json`` (its ``runner``
names ``runners/<runner>.py``), ``workloads/<cell>.json`` and
``layer_metrics/<metric>.py``. There is no registry to edit.

It needs the chip: no TPU, or fewer chips than the cell asks for, is a
non-zero exit with nothing on stdout. ``--rehearse-cpu`` (needs
``JAX_PLATFORMS=cpu``) runs the configuration's toy ``rehearsal`` shape
to debug the command off the chip; its line says ``"rehearsal": true``
and no number in it is a measurement.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # process start, as near as Python can say

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.lib import manifest as mf  # noqa: E402

#: a traced run profiles this much of the window (the runner says which
#: part): the trace of a whole window is large and slows the host that
#: serves it
TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Tracer:
    """The profiler around part of a window, timed by the host's clock.
    Only the benchmark's own ``bench.`` annotations and the device's
    operations are wanted: Python call tracing is off."""

    def __init__(self, out_dir: pathlib.Path):
        self.dir = out_dir / "trace"
        self.window_s = 0.0
        self._t0 = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._t0 = time.monotonic()

    def stop(self) -> None:
        import jax

        self.window_s = time.monotonic() - self._t0
        jax.profiler.stop_trace()


class Context:
    """What the harness hands a runner."""

    def __init__(self, config: dict, workload: dict, seed: int,
                 seconds: float, scratch: pathlib.Path, meter,
                 tracer: Tracer | None, rehearsal: bool, control=None):
        self.config, self.workload = config, workload
        self.seed, self.seconds = seed, seconds
        self.scratch, self.meter, self.tracer = scratch, meter, tracer
        self.rehearsal, self.control = rehearsal, control
        self.trace_seconds = min(seconds, TRACE_SECONDS)
        self.log = log


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def load_cell(manifest: dict, name: str, rehearsal: bool):
    """``(cell entry, configuration, workload)`` by the names in
    BENCHMARK.json; a rehearsal overlays each file's toy ``rehearsal``."""
    cell = mf.workload_entry(manifest, name)
    config = mf.read_json(mf.config_file(manifest, cell["config"]))
    workload = mf.read_json(mf.workload_file(cell["name"]))
    if rehearsal:
        config = {**config, **config.get("rehearsal", {})}
        workload = {**workload, **workload.get("rehearsal", {})}
    return cell, config, workload


def prepare_backend() -> str:
    """The program's native library and JAX's persistent compile cache
    (where ``JAX_COMPILATION_CACHE_DIR`` says, else the fixed
    ``<checkout>/.jax_cache``); returns the cache's directory."""
    import jax

    from minpaxos_tpu.native import build as native_build
    from minpaxos_tpu.utils.backend import enable_compile_cache

    native_build.build(quiet=True)
    cache_dir = enable_compile_cache()
    # the program keeps programs that compile in under half a second
    # out of the cache; a run's set-up builds some thirty such. Keep
    # all: after a checkout's first run, set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def layer_metrics(manifest: dict, cell: str, obs: dict) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in mf.metrics_of_cell(manifest, cell, "per_layer"):
        reader = load_module(mf.layer_metric_file(m["name"]),
                             "layer_metric_" + m["name"].replace(".", "_"))
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug the command off the chip at the toy "
                         "rehearsal shape; needs JAX_PLATFORMS=cpu; "
                         "never a measurement")
    ap.add_argument("--control", default="",
                    help="put benchmarks/controls/<name>.py in the "
                         "program's place at the comparison: the run "
                         "has to come out not correct")
    args = ap.parse_args(argv)

    if args.rehearse_cpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("bench: --rehearse-cpu needs JAX_PLATFORMS=cpu set "
              "explicitly", file=sys.stderr)
        return 2
    manifest = mf.load()
    cell, config, workload = load_cell(manifest, args.workload,
                                       args.rehearse_cpu)

    device = device_info()
    want = "cpu" if args.rehearse_cpu else "tpu"
    if device["platform"] != want or device["count"] < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} {want} "
              f"device(s); JAX found {device}; nothing was run",
              file=sys.stderr)
        return 2

    from benchmarks.lib.compile_meter import CompileMeter

    cache_dir = prepare_backend()
    meter = CompileMeter()
    scratch = ROOT / ".bench_scratch" / f"{cell['name']}-{os.getpid()}"
    shutil.rmtree(scratch, ignore_errors=True)
    scratch.mkdir(parents=True)
    tracer = Tracer(scratch) if args.trace else None
    log(f"cell {cell['name']} seed {args.seed} on {device}; compile "
        f"cache {cache_dir}")

    runner_mod = load_module(
        ROOT / "benchmarks" / "runners" / f"{config['runner']}.py",
        f"runner_{config['runner']}")
    control = args.control and load_module(
        ROOT / "benchmarks" / "controls" / f"{args.control}.py",
        f"control_{args.control}")
    ctx = Context(config, workload, args.seed, args.seconds, scratch, meter,
                  tracer, args.rehearse_cpu, control or None)
    runner = runner_mod.Runner(ctx)
    try:
        runner.setup()
        setup_compiles = meter.take()
        log(f"set-up done: {setup_compiles}")
        t_window = runner.window()
        in_window = meter.take()
        log(f"window closed: {in_window['compilations']} compilations "
            f"inside it")
        setup_s = t_window - _T0
        device["memory_peak_bytes"] = memory_peak_bytes()
        e2e = dict(runner.end_to_end(), setup_s=setup_s)
        counters = dict(runner.counters(),
                        compiles_in_window=in_window["compilations"])
        log(f"counters: {counters}")
        obs = {"counters": counters,
               "config": config, "workload": workload,
               "device_kind": device["kind"], "trace": None}
        breakdown = None
        if tracer is not None:
            from benchmarks.lib.xplane import reduce_trace

            t_r = time.monotonic()
            obs["trace"] = summary = reduce_trace(str(tracer.dir),
                                                  tracer.window_s)
            log(f"trace reduced in {time.monotonic() - t_r:.1f}s: "
                f"{summary['n_events'] if summary['devices'] else 0} "
                f"device events, busy {summary['busy_s']:.3f}s of "
                f"{summary['window_s']:.3f}s; {summary['trace_bytes']} "
                f"bytes; lines {summary['lines_seen']}")
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
        # the reference runs last: the peak is read, the window closed
        numbers, limits, attempted, failed = runner.check()
    finally:
        runner.close()
        shutil.rmtree(scratch, ignore_errors=True)

    if tracer is not None:
        metrics = layer_metrics(manifest, cell["name"], obs)
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in mf.metrics_of_cell(manifest, cell["name"],
                                               "end_to_end")}
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(numbers[k] <= limits[k] for k in limits)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse_cpu:
        result["rehearsal"] = True
    if args.control:
        result["control"] = args.control
    result["seconds_total"] = time.monotonic() - _T0
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
