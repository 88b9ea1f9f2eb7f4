"""BENCHMARK.json: loading, and the static rules it must keep.

``validate`` returns a list of faults (empty = valid). It holds the
manifest to the limits the driver states — names, units, lengths, the
keys each entry may have, which cell reports what — and every file the
manifest names to being there. It is run on the sandbox before any chip
time is spent (PR 22 was refused for one over-long ``source``).
"""

from __future__ import annotations

import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmarks"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
#: what ``reduced`` may never name: the source's shapes
WIDTH_RE = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head_size"
                      r"|key_bytes|value_bytes|write_pct|key_dist")
MAX_RUN_SECONDS = 51


def load(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def read_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def config_file(manifest: dict, name: str,
                root: pathlib.Path = ROOT) -> pathlib.Path:
    for c in manifest["configs"]:
        if c["name"] == name:
            return root / c["file"]
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def workload_entry(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in manifest['workloads']]}")


def workload_file(name: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "benchmarks" / "workloads" / f"{name}.json"


def layer_metric_file(name: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    """``layer_metrics/<name>.py``; a metric split by what its cells
    report (``device_idle_pct.served`` / ``.pod``) may share the reader
    of its first part, ``layer_metrics/device_idle_pct.py``."""
    own = root / "benchmarks" / "layer_metrics" / f"{name}.py"
    if own.is_file() or "." not in name:
        return own
    return own.with_name(name.rsplit(".", 1)[0] + ".py")


def metrics_of_cell(manifest: dict, cell: str, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if section == "end_to_end":
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def _line(s, what: str, faults: list[str], limit: int = 200) -> None:
    if not (isinstance(s, str) and 1 <= len(s) <= limit and s.isascii()
            and s.isprintable()):
        faults.append(f"{what}: must be 1 to {limit} printable ASCII "
                      f"characters on one line, not {s!r:.80}")


def _name(s, what: str, faults: list[str]) -> None:
    if not (isinstance(s, str) and NAME_RE.match(s)):
        faults.append(f"{what}: {s!r} is not a name")


def _keys(entry: dict, allowed: set, optional: set, what: str,
          faults: list[str]) -> None:
    have = set(entry)
    if have - allowed - optional or allowed - have:
        faults.append(f"{what}: keys {sorted(have)} are not "
                      f"{sorted(allowed)} (+ {sorted(optional)})")


def validate(manifest: dict, root: pathlib.Path = ROOT) -> list[str]:
    f: list[str] = []
    if set(manifest) != TOP_KEYS:
        f.append(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
        return f
    if len(json.dumps(manifest)) > 64 * 1024:
        f.append("manifest over 64 KiB")
    cmd, paths = manifest["command"], manifest["paths"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        f.append("command: a list of 1 to 32 strings")
    for word in cmd:
        _line(word, "command word", f)
        if word.startswith("/") or ".." in word.split("/"):
            f.append(f"command word {word!r} leaves the repo")
        if (root / word).exists() and not any(
                word == p or word.startswith(p + "/") for p in paths):
            f.append(f"command names {word!r}, a file outside paths")
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        f.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH_RE.match(p) or p.startswith("/") or ".." in p.split("/"):
            f.append(f"path {p!r} is not a relative path of the repo")
        elif not (root / p).is_dir():
            f.append(f"path {p!r} is not a directory")
        else:
            for sub in (root / p).rglob("*"):
                rel = str(sub.relative_to(root))
                if "__pycache__" in rel or rel.endswith(".pyc"):
                    continue
                if not PATH_RE.match(rel):
                    f.append(f"file {rel!r} is not named from a name's "
                             f"characters")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= MAX_RUN_SECONDS):
        f.append(f"run_seconds {rs!r}: a whole number 1..{MAX_RUN_SECONDS}")

    configs = manifest["configs"]
    if not 1 <= len(configs) <= 24:
        f.append("configs: 1 to 24")
    files = set()
    for c in configs:
        what = f"config {c.get('name')}"
        _keys(c, CONFIG_KEYS, set(), what, f)
        _name(c.get("name"), what, f)
        _line(c.get("source"), f"{what}: source", f)
        _line(c.get("why"), f"{what}: why", f)
        red = c.get("reduced", [])
        if len(red) > 16:
            f.append(f"{what}: reduced has over 16 keys")
        for k in red:
            _name(k, f"{what}: reduced key", f)
            if WIDTH_RE.search(k):
                f.append(f"{what}: reduced names a shape, {k!r}")
        file = c.get("file", "")
        if file in files:
            f.append(f"{what}: file {file!r} is another configuration's")
        files.add(file)
        if not any(file.startswith(p + "/") for p in paths):
            f.append(f"{what}: file {file!r} is not under paths")
        elif not (root / file).is_file():
            f.append(f"{what}: file {file!r} is missing")
        else:
            body = read_json(root / file)
            if body.get("source") != c["source"]:
                f.append(f"{what}: the file's source differs")
            if sorted(body.get("reduced", [])) != sorted(red):
                f.append(f"{what}: the file's reduced list differs")
            if "runner" not in body or not (
                    root / "benchmarks" / "runners"
                    / f"{body['runner']}.py").is_file():
                f.append(f"{what}: no runner file for "
                         f"{body.get('runner')!r}")
    names = [c.get("name") for c in configs]
    if len(set(names)) != len(names):
        f.append("two configurations share a name")

    cells = manifest["workloads"]
    if not 1 <= len(cells) <= 24:
        f.append("workloads: 1 to 24")
    pairs = set()
    for w in cells:
        what = f"workload {w.get('name')}"
        _keys(w, WORKLOAD_KEYS, set(), what, f)
        for k in ("name", "config", "traffic"):
            _name(w.get(k), f"{what}: {k}", f)
        _line(w.get("why"), f"{what}: why", f)
        if w.get("chips") not in (1, 4):
            f.append(f"{what}: chips is 1 or 4")
        if w.get("config") not in names:
            f.append(f"{what}: unknown config {w.get('config')!r}")
        if (w.get("config"), w.get("traffic")) in pairs:
            f.append(f"{what}: its (config, traffic) pair appears twice")
        pairs.add((w.get("config"), w.get("traffic")))
        wf = workload_file(w.get("name", ""), root)
        if not wf.is_file():
            f.append(f"{what}: no file {wf.relative_to(root)}")
        elif read_json(wf).get("config") != w.get("config"):
            f.append(f"{what}: its file names another configuration")
    cell_names = [w.get("name") for w in cells]
    if len(set(cell_names)) != len(cell_names):
        f.append("two workloads share a name")
    for n in names:
        if n not in {w.get("config") for w in cells}:
            f.append(f"config {n}: used by no cell")
    if sum(w.get("chips") == 4 for w in cells) > max(1, len(cells) // 2):
        f.append("over half the cells ask for 4 chips")

    e2e, layers = manifest["end_to_end"], manifest["per_layer"]
    if not 1 <= len(e2e) <= 16:
        f.append("end_to_end: 1 to 16 metrics")
    if not 1 <= len(layers) <= 128:
        f.append("per_layer: 1 to 128 metrics")
    for m in e2e + layers:
        is_e2e = "bound" in m or "layer" not in m
        what = f"metric {m.get('name')}"
        _keys(m, E2E_KEYS if is_e2e else LAYER_KEYS, {"workloads"}, what, f)
        _name(m.get("name"), what, f)
        if not (isinstance(m.get("unit"), str) and UNIT_RE.match(m["unit"])):
            f.append(f"{what}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            f.append(f"{what}: better is lower or higher")
        allowed = {"host_clock", "device_trace"} if is_e2e else SOURCES
        if m.get("source") not in allowed:
            f.append(f"{what}: source {m.get('source')!r}")
        for cell in m.get("workloads", []):
            if cell not in cell_names:
                f.append(f"{what}: lists unknown cell {cell!r}")
        if is_e2e:
            b = m.get("bound")
            if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
                f.append(f"{what}: bound {b!r} is not in 0.01..0.25")
        else:
            _line(m.get("layer"), f"{what}: layer", f)
            if m.get("moves") not in {x.get("name") for x in e2e}:
                f.append(f"{what}: moves unknown metric {m.get('moves')!r}")
            if not layer_metric_file(m.get("name", ""), root).is_file():
                f.append(f"{what}: no reader file in layer_metrics/")
            if "_roofline" in m.get("name", "") and m.get("unit") != "%":
                f.append(f"{what}: a roofline share has the unit %")
    all_names = [m.get("name") for m in e2e + layers]
    if len(set(all_names)) != len(all_names):
        f.append("two metrics share a name")
    if "setup_s" not in {m.get("name") for m in e2e}:
        f.append("end_to_end lacks setup_s")
    for cell in cell_names:
        mine = [m["name"] for m in metrics_of_cell(manifest, cell,
                                                   "end_to_end")]
        if "setup_s" not in mine or len(mine) < 2:
            f.append(f"cell {cell}: reports {mine}; needs setup_s and one "
                     f"other end-to-end metric")
        lay = metrics_of_cell(manifest, cell, "per_layer")
        if not lay:
            f.append(f"cell {cell}: reports no per-layer metric")
        for m in lay:
            if m["moves"] not in mine:
                f.append(f"cell {cell}: {m['name']} moves {m['moves']}, "
                         f"which the cell does not report")
    return f
