"""The deployment shapes (minpaxos_tpu/deployments.py) and what points at
them: every stated shape is held to the sizing rules the chip taught,
scripts import the package and never the other way round, and every
command a document tells an operator to run exists.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import re
from pathlib import Path

import pytest

from minpaxos_tpu import deployments

REPO = Path(__file__).resolve().parents[1]

#: rows a leader-based inbox holds beside the round's proposals (the
#: appendices ``headline_config``'s docstring lists)
GOSSIP_ROWS = 64


def _baseline_instances() -> dict[int, int]:
    """BASELINE.json config number -> the instance count its line names
    ("1k sequential instances", "64k instances", "1M instances")."""
    unit = {"k": 1 << 10, "M": 1 << 20}
    out = {}
    lines = json.loads((REPO / "BASELINE.json").read_text())["configs"]
    for i, line in enumerate(lines, start=1):
        m = re.search(r"(\d+)([kM]) (?:\w+ )?instances", line)
        if m:
            out[i] = int(m.group(1)) * unit[m.group(2)]
    return out


#: case -> the BASELINE.json config whose instance count it is held to
#: on the chip (None: the CPU harness shape)
SHAPES = {"headline_tpu": 5, "headline_cpu": None, "paxos_sequential": 2,
          "paxos_64k": 3, "mencius_64k": 4}


def _shape(case: str):
    """(cfg, groups, proposals per round [per owner], protocol,
    key_space or None) of one stated shape."""
    if case.startswith("headline"):
        on_tpu = case == "headline_tpu"
        g, w, p, _k = (deployments.TPU_SHAPE if on_tpu
                       else deployments.CPU_SHAPE)
        cfg, key_space = deployments.headline_config(on_tpu, w, p)
        return cfg, g, p, "minpaxos", key_space
    cfg, g, p, _k, protocol = deployments.side_shapes(True)[case]
    return cfg, g, p, protocol, None


@pytest.mark.parametrize("case", sorted(SHAPES))
def test_every_stated_shape_keeps_the_sizing_rules(case):
    baseline_config = SHAPES[case]
    cfg, groups, p, protocol, key_space = _shape(case)
    assert cfg.window & (cfg.window - 1) == 0
    if baseline_config is not None:
        assert groups * cfg.window == _baseline_instances()[baseline_config]
    if protocol == "mencius":
        # every owner proposes p a round; each of the four peers sends
        # its COMMIT / catch-up chunk, which must outrun one owner
        round_rows = cfg.n_replicas * p
        appendices = (cfg.n_replicas - 1) * cfg.catchup_rows \
            + cfg.recovery_rows
        assert cfg.catchup_rows > p
    else:
        round_rows = p
        appendices = 2 * cfg.catchup_rows + cfg.recovery_rows + GOSSIP_ROWS
    assert cfg.inbox >= round_rows + appendices
    assert cfg.exec_batch >= round_rows
    if key_space is not None:  # the headline: PR 21's two findings
        assert cfg.catchup_rows >= 2 * p
        assert 1 << cfg.kv_pow2 == 4 * key_space
        assert cfg.inbox == round_rows + appendices


def _imported_roots(path: Path) -> set[str]:
    roots = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            roots.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            roots.add((node.module or "").split(".")[0])
    return roots


def test_nothing_imports_the_root_scripts():
    """The arrow points one way: scripts import the package. ``bench``
    and ``bench_tcp`` are names kept for tests/benchmarks only."""
    files = [REPO / "chip_smoke.py", REPO / "__graft_entry__.py",
             *sorted((REPO / "tools").glob("*.py")),
             *sorted((REPO / "minpaxos_tpu").rglob("*.py"))]
    assert len(files) > 50
    bad = {str(f.relative_to(REPO)): sorted(hit) for f in files
           if (hit := _imported_roots(f) & {"bench", "bench_tcp"})}
    assert not bad


DOCUMENTS = ["README.md", "OBSERVABILITY.md", "ROBUSTNESS.md", "VERIFY.md",
             "ANALYSIS.md", "tools/run_tier1.sh",
             ".claude/skills/verify/SKILL.md"]
#: ``python -m <module>``, ``python <path>.py``, and a tool named by
#: its path alone (ROBUSTNESS.md writes ``tools/chaos.py --smoke``)
_COMMAND = re.compile(r"\bpython3?\s+(?:-m\s+([\w.]+)|([\w./-]+\.py)\b)"
                      r"|\b(tools/[\w-]+\.py)\b")
_MAIN_GUARD = re.compile(r"""^if __name__ == ["']__main__["']:""", re.M)


def _runnable(module: str | None, path: str | None) -> bool:
    """Is ``python -m module`` / ``python path`` a program of this
    checkout (a file with a ``__main__`` guard), or an installed
    module?"""
    if module is not None:
        parts = module.split(".")
        if not (REPO / parts[0]).exists():
            return importlib.util.find_spec(parts[0]) is not None
        base = REPO.joinpath(*parts)
        file = base / "__main__.py" if base.is_dir() \
            else base.with_suffix(".py")
    else:
        file = REPO / path
    return file.is_file() and bool(_MAIN_GUARD.search(file.read_text()))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_command_a_document_names_exists(document):
    text = (REPO / document).read_text()
    commands = {m.group(0): (m.group(1), m.group(2) or m.group(3))
                for m in _COMMAND.finditer(text)}
    assert commands, f"{document} names no command: is the pattern stale?"
    missing = sorted(c for c, (mod, path) in commands.items()
                     if not _runnable(mod, path))
    assert not missing
