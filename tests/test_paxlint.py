"""paxlint analyzer suite: every rule fires on its seeded violation,
stays quiet on the clean idiom, and the real tree is clean.

Fixtures are in-memory Projects (minpaxos_tpu/analysis/core.py), so a
seeded violation and a real one travel exactly the same code path the
CLI uses; one subprocess test pins the tools/lint.py exit-code and
--json contract that tools/run_tier1.sh and future benches rely on.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from minpaxos_tpu.analysis import Project, run_passes
from minpaxos_tpu.analysis import wire_contract as wc
from minpaxos_tpu.analysis.wire_golden import (
    GOLDEN_HEADER_FMT,
    GOLDEN_KINDS,
    GOLDEN_MAX_FRAME_ROWS,
)

REPO = Path(__file__).resolve().parents[1]


def rules_of(violations):
    return {v.rule for v in violations}


def lint_src(path: str, src: str, rule: str):
    return run_passes(Project({path: src}), (rule,))


# ---------------------------------------------------------------- trace


TRACE_BAD = '''
import jax
import numpy as np

@jax.jit
def step(state):
    if state > 0:                 # traced branch
        pass
    n = int(state)                # host coercion
    m = state.sum().item()        # host sync
    a = np.asarray(state)         # device -> host pull
    for i in range(state):        # traced iteration
        pass
    return n, m, a
'''

TRACE_CLEAN = '''
import jax
import jax.numpy as jnp

@jax.jit
def step(cfg, state):
    if cfg.explicit_commit:        # static config branch
        state = state + 1
    if getattr(state, "leader_id", None) is not None:  # structural
        pass
    w = state.shape[0]             # structural read
    if w > 4:                      # branch on a python int
        state = state * 2
    for name in state._asdict().items():  # container of tracers
        pass
    return jnp.where(state > 0, state, -state)
'''


def test_trace_hazard_fires_on_seeded_violations():
    vs = lint_src("minpaxos_tpu/models/fix.py", TRACE_BAD, "trace-hazard")
    msgs = "\n".join(v.msg for v in vs)
    assert len(vs) == 5, vs
    for needle in ("`if`", "`int()`", "`.item()`", "`np.asarray`", "`for`"):
        assert needle in msgs, f"missing {needle}: {msgs}"


def test_trace_hazard_quiet_on_clean_idiom():
    assert lint_src("minpaxos_tpu/models/ok.py", TRACE_CLEAN,
                    "trace-hazard") == []


def test_trace_hazard_follows_calls_across_modules():
    helper = '''
def helper(v):
    return v.item()
'''
    entry = '''
import jax
from minpaxos_tpu.ops.helper import helper

@jax.jit
def entry(x):
    return helper(x)
'''
    vs = run_passes(Project({
        "minpaxos_tpu/ops/helper.py": helper,
        "minpaxos_tpu/models/entry.py": entry,
    }), ("trace-hazard",))
    assert any(v.path.endswith("helper.py") for v in vs), vs


def test_trace_hazard_ops_package_numpy_needs_suppression():
    src = '''
import numpy as np

def host_helper(x):
    return np.asarray(x)
'''
    vs = lint_src("minpaxos_tpu/ops/h.py", src, "trace-hazard")
    assert len(vs) == 1 and "device-kernel package" in vs[0].msg
    # models/ has host harnesses (cluster.py): no package-wide rule
    assert lint_src("minpaxos_tpu/models/h.py", src, "trace-hazard") == []
    # the suppression syntax clears it
    sup = src.replace(
        "return np.asarray(x)",
        "return np.asarray(x)  # paxlint: disable=trace-hazard -- host")
    assert lint_src("minpaxos_tpu/ops/h.py", sup, "trace-hazard") == []


# ------------------------------------------------------------ recompile


def test_recompile_hazard_fires():
    src = '''
import jax, functools

_REGISTRY = {}

def f(x, buf=[]):
    return x

@functools.partial(jax.jit, static_argnums=(1,))
def g(x, opts={}):
    return _REGISTRY and x
'''
    vs = lint_src("minpaxos_tpu/ops/r.py", src, "recompile-hazard")
    msgs = "\n".join(v.msg for v in vs)
    assert "mutable default for `buf`" in msgs
    # `opts` trips both the mutable-default and the unhashable-static
    # checks on one line; violations dedup per (path, line, rule), so
    # exactly one of the two messages survives
    assert "`opts`" in msgs
    assert "mutable module global `_REGISTRY`" in msgs


def test_recompile_hazard_quiet_on_clean_idiom():
    src = '''
import jax, functools
import jax.numpy as jnp

_BIG = jnp.int32(2 ** 30)          # immutable device constant: fine

@functools.partial(jax.jit, static_argnums=0)
def g(cfg, x, k=1, extra=None):
    return x + _BIG
'''
    assert lint_src("minpaxos_tpu/ops/ok.py", src, "recompile-hazard") == []


def test_recompile_hazard_static_argnums_out_of_range():
    src = '''
import jax

def f(x):
    return x

g = jax.jit(f, static_argnums=(3,))
'''
    vs = lint_src("minpaxos_tpu/ops/r2.py", src, "recompile-hazard")
    assert any("out of range" in v.msg for v in vs), vs


# ----------------------------------------------------------------- wire


def _real_wire():
    msgs = (REPO / "minpaxos_tpu/wire/messages.py").read_text()
    codec = (REPO / "minpaxos_tpu/wire/codec.py").read_text()
    return msgs, codec


def test_wire_contract_clean_on_real_tree():
    msgs, codec = _real_wire()
    assert wc.check(msgs, codec, GOLDEN_KINDS, GOLDEN_HEADER_FMT,
                    GOLDEN_MAX_FRAME_ROWS) == []


def test_wire_contract_collision_and_renumber():
    msgs, codec = _real_wire()
    drift = msgs.replace("SKIP = 28", "SKIP = 24")  # collides PREPARE_INST
    vs = wc.check(drift, codec, GOLDEN_KINDS, GOLDEN_HEADER_FMT,
                  GOLDEN_MAX_FRAME_ROWS)
    assert any("collision" in v.msg for v in vs), vs
    assert any("renumbered" in v.msg for v in vs), vs


def test_wire_contract_removed_kind_and_width_drift():
    msgs, codec = _real_wire()
    vs = wc.check(msgs.replace("SKIP = 28", "SKIPPED = 28"), codec,
                  GOLDEN_KINDS, GOLDEN_HEADER_FMT, GOLDEN_MAX_FRAME_ROWS)
    assert any("removed" in v.msg for v in vs), vs
    # widen READ's cmd_id: packed row width drifts 12 -> 16 bytes
    wide = msgs.replace('np.dtype([("cmd_id", "<i4"), ("key", "<i8")])',
                        'np.dtype([("cmd_id", "<i8"), ("key", "<i8")])')
    assert wide != msgs
    vs = wc.check(wide, codec, GOLDEN_KINDS, GOLDEN_HEADER_FMT,
                  GOLDEN_MAX_FRAME_ROWS)
    assert any("width drift" in v.msg for v in vs), vs


def test_wire_contract_codec_header_and_bound():
    msgs, codec = _real_wire()
    vs = wc.check(msgs, codec.replace('"<BI"', '"<BH"'), GOLDEN_KINDS,
                  GOLDEN_HEADER_FMT, GOLDEN_MAX_FRAME_ROWS)
    assert any("header format" in v.msg for v in vs), vs
    vs = wc.check(msgs, codec.replace("1 << 22", "1 << 20"), GOLDEN_KINDS,
                  GOLDEN_HEADER_FMT, GOLDEN_MAX_FRAME_ROWS)
    assert any("MAX_FRAME_ROWS" in v.msg for v in vs), vs


def test_wire_contract_new_kind_appends_cleanly():
    msgs, codec = _real_wire()
    grown = msgs.replace("    SKIP = 28",
                         "    SKIP = 28\n    SNAPSHOT = 29")
    vs = wc.check(grown, codec, GOLDEN_KINDS, GOLDEN_HEADER_FMT,
                  GOLDEN_MAX_FRAME_ROWS)
    # appending with a fresh value breaks no append-only/collision
    # rule, but the new kind is nudged to finish the job in the same
    # PR: add a SCHEMAS entry (decodability) and record it in the
    # ledger (drift protection) — without the latter a later renumber
    # of SNAPSHOT would go unnoticed
    assert all("no SCHEMAS entry" in v.msg or "not recorded" in v.msg
               for v in vs), vs
    assert any("not recorded in the wire ledger" in v.msg for v in vs), vs
    reuse = msgs.replace("    SKIP = 28",
                         "    SKIP = 28\n    SNAPSHOT = 20")
    vs = wc.check(reuse, codec, GOLDEN_KINDS, GOLDEN_HEADER_FMT,
                  GOLDEN_MAX_FRAME_ROWS)
    assert any("reuses recorded opcode" in v.msg for v in vs), vs


# ---------------------------------------------------------- concurrency


CONC_BAD = '''
import threading, socket, time

class Transport:
    def __init__(self):
        self._lock = threading.Lock()
        self.peers = {}

    def start(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        self.peers[1] = object()       # unlocked write
        with self._lock:
            sock = socket.create_connection(("h", 1))  # blocking w/ lock

    def alive(self, q):
        with self._lock:               # peers IS lock-guarded elsewhere
            return q in self.peers
'''

CONC_CLEAN = '''
import threading

class Transport:
    def __init__(self):
        self._lock = threading.Lock()
        self.peers = {}

    def start(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        with self._lock:
            self.peers[1] = object()   # locked write
        conns = None
        with self._lock:
            conns = list(self.peers.values())
        for c in conns:
            c.flush()                  # blocking work outside the lock
'''


def test_concurrency_fires():
    vs = lint_src("minpaxos_tpu/runtime/transport.py", CONC_BAD,
                  "concurrency")
    msgs = "\n".join(v.msg for v in vs)
    assert "without holding the lock" in msgs
    assert "blocking call `create_connection`" in msgs


def test_concurrency_quiet_on_clean_idiom():
    assert lint_src("minpaxos_tpu/runtime/transport.py", CONC_CLEAN,
                    "concurrency") == []


def test_concurrency_constructor_exempt():
    # __init__ writes before any thread exists: not a race
    src = CONC_BAD.replace("self.peers[1] = object()       # unlocked write",
                           "pass")
    vs = lint_src("minpaxos_tpu/runtime/transport.py", src, "concurrency")
    assert all("without holding the lock" not in v.msg for v in vs), vs


def test_concurrency_out_of_scope_file_ignored():
    # replica.py is single-owner by design; the lock-discipline checks
    # scope to transport/master/cli (replica.py gets the donated-state
    # check instead — below)
    assert lint_src("minpaxos_tpu/runtime/replica.py", CONC_BAD,
                    "concurrency") == []


# coalescer cv discipline (ISSUE 15): the ingress coalescer's wakeup
# condition variable counts as a lock for the blocking-under-lock rule
# — a socket read while holding self._cv would stall every client
# reader's enqueue behind one peer's TCP timeout. cv.wait itself is
# exempt (it releases the lock while parked).

CV_BAD = '''
import threading, socket

class IngressCoalescer:
    def __init__(self):
        self._cv = threading.Condition()
        self._items = []

    def get(self, sock):
        with self._cv:
            data = sock.recv(4096)     # blocking read under the cv
            self._items.append(data)
            return self._items.pop(0)
'''

CV_CLEAN = '''
import threading, socket

class IngressCoalescer:
    def __init__(self):
        self._cv = threading.Condition()
        self._items = []

    def put(self, item):
        with self._cv:
            self._items.append(item)
            self._cv.notify()          # kick: O(1) under the cv

    def get(self, sock):
        with self._cv:
            while not self._items:
                self._cv.wait(0.05)    # releases the cv while parked
            item = self._items.pop(0)
        data = sock.recv(4096)         # blocking work outside the cv
        return item, data
'''


def test_concurrency_cv_blocking_read_fires():
    vs = lint_src("minpaxos_tpu/runtime/batches.py", CV_BAD,
                  "concurrency")
    msgs = "\n".join(v.msg for v in vs)
    assert "blocking call `recv` while holding a lock" in msgs, vs


def test_concurrency_cv_clean_coalescer_quiet():
    assert lint_src("minpaxos_tpu/runtime/batches.py", CV_CLEAN,
                    "concurrency") == []


def test_concurrency_real_coalescer_clean():
    # the shipped coalescer must satisfy its own lint: nothing
    # blocking under self._cv in runtime/batches.py
    src = (Path(__file__).resolve().parents[1]
           / "minpaxos_tpu/runtime/batches.py").read_text()
    vs = lint_src("minpaxos_tpu/runtime/batches.py", src, "concurrency")
    assert vs == [], vs


# donated-state: self.state's buffers are donated into the jitted step;
# only the protocol thread (_run and what it calls) may touch them —
# the pipelined tick loop doubles the in-flight references, so the
# single-owner convention is machine-checked, not just documented.

STATE_BAD = '''
import threading

class ReplicaServer:
    def start(self):
        threading.Thread(target=self._run, daemon=True).start()
        threading.Thread(target=self._control_loop, daemon=True).start()

    def _run(self):
        while True:
            self._tick()

    def _tick(self):
        self.state = self.step(self.state)   # owner thread: fine

    def _control_loop(self):
        self._answer()

    def _answer(self):
        return int(self.state.committed_upto)  # foreign-thread read
'''


def test_concurrency_donated_state_read_fires():
    vs = lint_src("minpaxos_tpu/runtime/replica.py", STATE_BAD,
                  "concurrency")
    assert len(vs) == 1, vs
    assert "`self.state` touched in `_answer`" in vs[0].msg
    assert "donated" in vs[0].msg


def test_concurrency_donated_state_owner_thread_ok():
    # the same access pattern minus the control-thread read is clean:
    # _run/_tick own the state (and methods no thread reaches, like a
    # stop() on the main thread, are exempt)
    src = STATE_BAD.replace(
        "        return int(self.state.committed_upto)"
        "  # foreign-thread read",
        "        return dict(self.snapshot)")
    assert lint_src("minpaxos_tpu/runtime/replica.py", src,
                    "concurrency") == []


def test_concurrency_donated_state_scoped_to_replica():
    # the check keys on the replica runtime's donation contract; the
    # same shape elsewhere (no donated buffers) must stay quiet
    assert lint_src("minpaxos_tpu/runtime/transport.py", STATE_BAD,
                    "concurrency") == []


# --------------------------------------------------------- wall-honesty


def test_wall_honesty_fires():
    src = '''
def step(cfg, state, inbox, tick_inc=1):
    return state._replace(stall_ticks=state.stall_ticks + 1)
'''
    vs = lint_src("minpaxos_tpu/models/m.py", src, "wall-honesty")
    assert len(vs) == 1 and "stall_ticks" in vs[0].msg


def test_wall_honesty_quiet_on_clean_idiom():
    src = '''
import jax.numpy as jnp

def step(cfg, state, inbox, tick_inc=1):
    return state._replace(
        tick=state.tick + tick_inc,
        stall_ticks=jnp.where(state.crt_inst > 0,
                              state.stall_ticks + tick_inc, 0))

def thresholds(cfg, state):
    # reads and config comparisons are not updates
    return (state.stall_ticks >= cfg.noop_delay,
            (4 + 2) * cfg.noop_delay)
'''
    assert lint_src("minpaxos_tpu/models/m.py", src, "wall-honesty") == []


def test_wall_honesty_scoped_to_models():
    src = "x = state.stall_ticks + 1\n"
    assert lint_src("minpaxos_tpu/runtime/r.py", src, "wall-honesty") == []


def test_wall_honesty_registry_advance_fires_in_runtime():
    """The paxmon extension: a tick-named registry counter advanced by
    a literal in runtime/ counts fused device substeps as wall ticks —
    must carry tick_inc (obs/metrics.py wall-honesty contract)."""
    src = '''
class R:
    def _tick(self, k):
        self._c_ticks.inc(1)
'''
    vs = lint_src("minpaxos_tpu/runtime/rep.py", src, "wall-honesty")
    assert len(vs) == 1 and "registry counter" in vs[0].msg, vs
    assert "_c_ticks" in vs[0].msg


def test_wall_honesty_registry_metric_name_string_fires():
    # the counter-ish identity can live in the metric NAME string
    src = 'def f(reg, n):\n    reg.counter("stall_ticks").inc(n)\n'
    vs = lint_src("minpaxos_tpu/models/m2.py", src, "wall-honesty")
    assert len(vs) == 1 and "stall_ticks" in vs[0].msg, vs


def test_wall_honesty_registry_advance_clean_idioms():
    """tick_inc-spelled advances and event counters (not tick-named)
    advance freely; suppression clears a deliberate site."""
    src = '''
class R:
    def _tick(self, k, n_rows):
        tick_inc = 1
        self._c_ticks.inc(tick_inc)
        self._c_fused_substeps.inc(k)       # substeps, not wall ticks
        self._c_proposals.inc(n_rows)
        self.metrics.counter("idle_skips").inc(1)
        self._pending.add((1, 2))           # a set, not a counter
'''
    assert lint_src("minpaxos_tpu/runtime/rep.py", src,
                    "wall-honesty") == []
    sup = ('def f(reg):\n'
           '    reg.counter("stall_ticks").inc(2)'
           '  # paxlint: disable=wall-honesty -- replay\n')
    assert lint_src("minpaxos_tpu/models/m2.py", sup,
                    "wall-honesty") == []


# --------------------------------------------------------- broad-except


def test_broad_except_fires_and_reraise_exempt():
    src = '''
def f():
    try:
        g()
    except Exception:
        pass

def h():
    try:
        g()
    except Exception as e:
        raise RuntimeError("wrapped") from e
'''
    vs = lint_src("minpaxos_tpu/runtime/x.py", src, "broad-except")
    assert len(vs) == 1 and vs[0].line == 5, vs


def test_broad_except_quiet_on_narrow_handlers():
    src = '''
def f():
    try:
        g()
    except (OSError, ValueError):
        pass
'''
    assert lint_src("minpaxos_tpu/runtime/x.py", src, "broad-except") == []


# ---------------------------------------------------- quorum-certificate


QUORUM_BAD = '''
class FlexCfg:
    @property
    def q1(self):
        return (self.n_replicas + 1) // 2

    @property
    def q2(self):
        return (self.n_replicas + 1) // 2
'''

QUORUM_CLEAN = '''
class Cfg:
    @property
    def majority(self):
        return self.n_replicas // 2 + 1


def step(cfg, state, n_votes):
    majority = cfg.majority          # delegation: certified at source
    return n_votes >= majority
'''


def test_quorum_certificate_rejects_non_intersecting_pair():
    vs = lint_src("minpaxos_tpu/models/flex.py", QUORUM_BAD,
                  "quorum-certificate")
    assert any("NON-INTERSECTING" in v.msg for v in vs), vs
    # the refutation names a concrete disjoint witness pair
    assert any("disjoint quorums" in v.msg for v in vs), vs


def test_quorum_certificate_quiet_on_certified_majority():
    assert lint_src("minpaxos_tpu/models/ok.py", QUORUM_CLEAN,
                    "quorum-certificate") == []


def test_quorum_certificate_flags_uncovered_and_literal():
    # intersecting but absent from the ledger: must be appended.
    # (q = n itself became a certified formula when quorum_fast landed,
    # so probe with ceil(3n/4) — fast-paxos-ish, intersects with
    # itself, but (4, 4) at n=5 is not a ledger row)
    src = ("class C:\n    @property\n    def quorum(self):\n"
           "        return (self.n_replicas * 3 + 3) // 4\n")
    vs = lint_src("minpaxos_tpu/models/u.py", src, "quorum-certificate")
    assert any("not covered by a certified entry" in v.msg for v in vs), vs
    # fixed literal compared against a vote count
    lit = "def f(state):\n    return state.n_votes >= 1\n"
    vs = lint_src("minpaxos_tpu/ops/l.py", lit, "quorum-certificate")
    assert any("fixed literal" in v.msg for v in vs), vs


def test_quorum_certificate_unrecognizable_formula_flagged():
    src = ("class C:\n    @property\n    def majority(self):\n"
           "        return mystery()\n")
    vs = lint_src("minpaxos_tpu/models/m.py", src, "quorum-certificate")
    assert any("cannot certify" in v.msg for v in vs), vs


def test_quorum_certificate_scoped_to_device_packages():
    # the same bad pair outside ops//models/ is out of scope
    assert lint_src("minpaxos_tpu/runtime/flex.py", QUORUM_BAD,
                    "quorum-certificate") == []


# ------------------------------------------------------------ lock-order


LOCK_CYCLE = '''
import threading

class Transport:
    def __init__(self):
        self._peers_lock = threading.Lock()
        self._stats_lock = threading.Lock()

    def send(self):
        with self._peers_lock:
            with self._stats_lock:
                pass

    def report(self):
        with self._stats_lock:
            self._count()

    def _count(self):
        with self._peers_lock:
            pass
'''

LOCK_ORDERED = '''
import threading

class Transport:
    def __init__(self):
        self._peers_lock = threading.Lock()
        self._stats_lock = threading.Lock()

    def send(self):
        with self._peers_lock:
            with self._stats_lock:
                pass

    def report(self):
        with self._peers_lock:          # same order everywhere
            with self._stats_lock:
                self._count()

    def _count(self):
        pass
'''

LOCK_CROSS = '''
import threading

class Transport:
    def __init__(self, master):
        self._lock = threading.Lock()
        self.master = Master()

    def send(self):
        with self._lock:
            pass

    def deliver(self):
        with self._lock:
            self.master.on_frame()

class Master:
    def __init__(self):
        self._lock = threading.Lock()
        self.transport = Transport(self)

    def on_frame(self):
        with self._lock:
            pass

    def fanout(self):
        with self._lock:
            self.transport.send()
'''


def test_lock_order_cycle_fires():
    vs = lint_src("minpaxos_tpu/runtime/transport.py", LOCK_CYCLE,
                  "lock-order")
    assert len(vs) == 1 and "lock-order cycle" in vs[0].msg, vs
    assert "_peers_lock" in vs[0].msg and "_stats_lock" in vs[0].msg


def test_lock_order_quiet_on_consistent_order():
    assert lint_src("minpaxos_tpu/runtime/transport.py", LOCK_ORDERED,
                    "lock-order") == []


def test_lock_order_cross_class_cycle_fires():
    """The production shape: master holds its lock fanning out through
    transport methods that take the transport lock, while a transport
    read loop holds its lock calling back into the master."""
    vs = lint_src("minpaxos_tpu/runtime/master.py", LOCK_CROSS,
                  "lock-order")
    assert len(vs) == 1, vs
    assert "Transport._lock" in vs[0].msg and "Master._lock" in vs[0].msg


def test_lock_order_nested_inside_branches_tracked():
    # the with->if->with nesting must still build the edge
    src = LOCK_CYCLE.replace(
        "        with self._stats_lock:\n            self._count()",
        "        with self._stats_lock:\n"
        "            if True:\n                self._count()")
    vs = lint_src("minpaxos_tpu/runtime/transport.py", src, "lock-order")
    assert len(vs) == 1, vs


def test_lock_order_scoped_to_runtime():
    assert lint_src("minpaxos_tpu/cli/x.py", LOCK_CYCLE, "lock-order") == []


def test_lock_order_sees_through_match_statements():
    """Code-review regression: locks taken inside `match` case arms
    (whose bodies live in match_case objects, not plain stmt bodies)
    still build graph edges."""
    src = LOCK_CYCLE.replace(
        "    def report(self):\n        with self._stats_lock:\n"
        "            self._count()",
        "    def report(self, kind):\n        match kind:\n"
        "            case 1:\n                with self._stats_lock:\n"
        "                    self._count()")
    vs = lint_src("minpaxos_tpu/runtime/transport.py", src, "lock-order")
    assert len(vs) == 1 and "lock-order cycle" in vs[0].msg, vs


def test_quorum_certificate_zero_literal_is_emptiness_not_quorum():
    # `> 0` / `>= 0` against a vote count is an emptiness guard; a
    # quorum size is always >= 1, so zero never flags
    src = ("def f(state):\n"
           "    a = state.n_votes > 0\n"
           "    b = 0 < state.pv_cnt\n"
           "    return a and b\n")
    assert lint_src("minpaxos_tpu/ops/z.py", src,
                    "quorum-certificate") == []


def test_lock_order_duplicate_class_names_both_analyzed():
    """Code-review regression: two runtime/ files each defining a class
    with the SAME name must not shadow each other — a cycle inside
    either one still fires, and the report qualifies the node names so
    the two classes' locks don't merge into phantom edges."""
    clean = LOCK_ORDERED  # class Transport, consistent order
    vs = run_passes(Project({
        "minpaxos_tpu/runtime/a.py": clean,
        "minpaxos_tpu/runtime/b.py": LOCK_CYCLE,  # also class Transport
    }), ("lock-order",))
    assert len(vs) == 1 and vs[0].path.endswith("b.py"), vs
    assert "b:Transport" in vs[0].msg, vs  # stem-qualified node label


# --------------------------------------------- single-parse / shared graph


def test_single_parse_and_one_graph_build_across_all_passes():
    """The lint perf contract: one ast.parse per file, one structural
    module walk per device file, ONE jit call-graph fixed point per
    invocation — no matter how many passes consult it (trace-hazard
    and recompile-hazard both do)."""
    from minpaxos_tpu.analysis.jitgraph import DEVICE_PREFIXES

    project = Project.from_root(REPO)
    run_passes(project)  # every registered pass
    n_device = sum(1 for p in project.files if p.startswith(DEVICE_PREFIXES))
    assert project.stats["ast_parses"] == len(project.files)
    assert project.stats["module_walks"] == n_device
    assert project.stats["graph_builds"] == 1, project.stats
    # a second full run re-uses everything — no new parses, no rebuild
    run_passes(project)
    assert project.stats["ast_parses"] == len(project.files)
    assert project.stats["module_walks"] == n_device
    assert project.stats["graph_builds"] == 1


def test_passes_share_one_prefix_scope():
    from minpaxos_tpu.analysis import recompile_hazard, trace_hazard
    from minpaxos_tpu.analysis.jitgraph import DEVICE_PREFIXES

    assert trace_hazard.GRAPH_PREFIXES is DEVICE_PREFIXES
    assert recompile_hazard.PREFIXES is DEVICE_PREFIXES


# ----------------------------------------------------- framework pieces


def test_suppression_comment_line_covers_next_code_line():
    src = '''
def f():
    try:
        g()
    # paxlint: disable=broad-except -- best-effort by design
    except Exception:
        pass
'''
    assert lint_src("minpaxos_tpu/runtime/x.py", src, "broad-except") == []


def test_suppression_comment_line_skips_blank_lines():
    src = '''
import numpy as np

def f(x):
    # paxlint: disable=trace-hazard -- host helper

    return np.asarray(x)
'''
    assert lint_src("minpaxos_tpu/ops/h.py", src, "trace-hazard") == []


def test_suppression_disable_file_works_anywhere():
    src = ("def f():\n    pass\n" * 8
           + "# paxlint: disable-file=broad-except\n"
           + "def g():\n    try:\n        f()\n"
             "    except Exception:\n        pass\n")
    assert lint_src("minpaxos_tpu/runtime/x.py", src, "broad-except") == []


def test_trace_hazard_item_on_static_config_ok():
    src = '''
import jax

@jax.jit
def step(cfg, state):
    n = cfg.table.item()     # static config read: trace-time, fine
    return state + n
'''
    assert lint_src("minpaxos_tpu/models/ok2.py", src, "trace-hazard") == []


def test_concurrency_manual_acquire_release_not_a_race():
    src = CONC_BAD.replace(
        "        self.peers[1] = object()       # unlocked write",
        "        self._lock.acquire(timeout=1.0)\n"
        "        try:\n"
        "            self.peers[1] = object()\n"
        "        finally:\n"
        "            self._lock.release()")
    vs = lint_src("minpaxos_tpu/runtime/transport.py", src, "concurrency")
    assert all("without holding the lock" not in v.msg for v in vs), vs


def test_parse_error_is_a_violation():
    vs = run_passes(Project({"minpaxos_tpu/ops/bad.py": "def f(:\n"}))
    assert any(v.rule == "parse" for v in vs), vs


def test_unknown_rule_raises():
    try:
        run_passes(Project({}), ("no-such-rule",))
    except KeyError as e:
        assert "no-such-rule" in str(e)
    else:
        raise AssertionError("expected KeyError")


# ------------------------------------------------------- the real tree


def test_whole_repo_is_clean():
    """The acceptance gate: the shipped tree has zero violations (true
    positives were fixed; deliberate host-side/best-effort sites carry
    visible suppressions)."""
    project = Project.from_root(REPO)
    assert run_passes(project) == []


def test_cli_exit_codes_and_json(tmp_path):
    """tools/lint.py: exit 0 + --json on the clean tree; nonzero on a
    tree with a seeded violation (the run_tier1.sh contract)."""
    out = subprocess.run(
        [sys.executable, str(REPO / "tools/lint.py"), "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["clean"] is True and payload["violations"] == []

    bad = tmp_path / "minpaxos_tpu" / "models"
    bad.mkdir(parents=True)
    (bad / "seeded.py").write_text(
        "def step(state, tick_inc):\n"
        "    return state.stall_ticks + 1\n")
    out = subprocess.run(
        [sys.executable, str(REPO / "tools/lint.py"),
         "--root", str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["counts"].get("wall-honesty") == 1, payload


# ------------------------------------------------------- resident-loop


RESIDENT_BAD = '''
import numpy as np
import jax

def helper(state):
    return np.asarray(state)       # device -> host pull, one hop away

# paxlint: resident-loop
def run_resident_dispatch(state):
    y = helper(state)              # transitive: flagged in helper
    jax.block_until_ready(state)   # blocks the measured loop
    n = state.sum().item()         # host sync
    return y, n
'''

RESIDENT_CLEAN = '''
import functools

import jax
import jax.numpy as jnp

def kernel(state):
    k = int(7)                     # literal coercion: not a readback
    return jnp.where(state > 0, state, -state) + k

# paxlint: resident-loop
def run_resident_dispatch(state):
    step = functools.partial(kernel)
    out = jax.vmap(step)(state)    # bare-reference edge, still clean
    return out

def host_tool(x):
    import numpy as np
    return np.asarray(x)           # unmarked host code may sync freely
'''


def test_resident_loop_fires_on_seeded_violations():
    vs = lint_src("minpaxos_tpu/parallel/fx.py", RESIDENT_BAD,
                  "resident-loop")
    msgs = "\n".join(v.msg for v in vs)
    assert len(vs) == 3, vs
    assert any(v.path.endswith("fx.py") and v.line == 6 for v in vs), \
        "np.asarray must be flagged in the REACHED helper, not the root"
    for needle in ("np.asarray", "block_until_ready", ".item()"):
        assert needle in msgs, f"missing {needle}: {msgs}"


def test_resident_loop_quiet_on_clean_idiom_and_unmarked_host_code():
    assert lint_src("minpaxos_tpu/parallel/ok.py", RESIDENT_CLEAN,
                    "resident-loop") == []


def test_resident_loop_scalar_readback_needs_suppression():
    """int()/float() in a MARKED dispatch wrapper is a scalar readback
    and must carry the sanctioning suppression; with it, clean."""
    src = '''
# paxlint: resident-loop
def run_resident_dispatch(committed):
    return int(committed)
'''
    vs = lint_src("minpaxos_tpu/parallel/rb.py", src, "resident-loop")
    assert len(vs) == 1 and "scalar readback" in vs[0].msg
    ok = src.replace(
        "return int(committed)",
        "return int(committed)  # paxlint: disable=resident-loop -- ok")
    assert lint_src("minpaxos_tpu/parallel/rb.py", ok,
                    "resident-loop") == []


def test_resident_loop_follows_cross_module_and_method_edges():
    """The real topology: a marked METHOD calling a jitted module
    function in another module that hides the sync."""
    kernel = '''
import numpy as np

def fused_dispatch(state):
    return np.asarray(state)
'''
    wrapper = '''
from minpaxos_tpu.ops.fused import fused_dispatch

class Cluster:
    # paxlint: resident-loop
    def run_resident(self, k):
        return fused_dispatch(self.ss)
'''
    vs = run_passes(Project({
        "minpaxos_tpu/ops/fused.py": kernel,
        "minpaxos_tpu/parallel/wrap.py": wrapper,
    }), ("resident-loop",))
    assert len(vs) == 1 and vs[0].path.endswith("fused.py"), vs
    assert "run_resident" in vs[0].msg  # names the responsible root


def test_resident_loop_flags_mid_window_telemetry_readback():
    """The paxray discipline (ISSUE 9): the telemetry ring's readback
    (np.asarray of the device buffer) is post-window host code — a
    call of it FROM the marked dispatch root ("just peeking" at the
    ring between measured dispatches) must be flagged through the
    self-method edge; the unmarked post-window reader alone is
    clean."""
    peeking = '''
import numpy as np

class Cluster:
    # paxlint: resident-loop
    def run_resident(self, k):
        rows = self.resident_telemetry()   # mid-window peek: a sync
        return rows

    def resident_telemetry(self):
        return np.asarray(self._telemetry)
'''
    vs = lint_src("minpaxos_tpu/parallel/peek.py", peeking,
                  "resident-loop")
    assert len(vs) == 1 and "np.asarray" in vs[0].msg, vs
    assert "run_resident" in vs[0].msg  # names the responsible root
    disciplined = peeking.replace(
        "        rows = self.resident_telemetry()   # mid-window peek: a sync\n"
        "        return rows", "        return 0")
    assert lint_src("minpaxos_tpu/parallel/peek.py", disciplined,
                    "resident-loop") == []


def test_resident_loop_real_suppression_is_load_bearing():
    """The ONE sanctioned per-dispatch scalar readback in the real
    tree (ShardedCluster.run_resident) is actually guarded: stripping
    its suppression must produce exactly the int() readback
    violations, nothing else."""
    files = {p: (REPO / p).read_text() for p in (
        "minpaxos_tpu/parallel/sharded.py",
        "minpaxos_tpu/ops/workload.py",
        "minpaxos_tpu/models/cluster.py",
        "minpaxos_tpu/models/minpaxos.py",
    )}
    marker = "# paxlint: disable=resident-loop -- sanctioned scalar readback"
    assert marker in files["minpaxos_tpu/parallel/sharded.py"]
    assert run_passes(Project(files), ("resident-loop",)) == []
    files["minpaxos_tpu/parallel/sharded.py"] = files[
        "minpaxos_tpu/parallel/sharded.py"].replace(marker, "#")
    vs = run_passes(Project(files), ("resident-loop",))
    assert vs and all(v.rule == "resident-loop"
                      and "scalar readback" in v.msg for v in vs), vs


def test_resident_loop_flags_a_mid_window_tier_counter_read():
    """The tier counts (PR 27) ride the donated carry of the real
    resident dispatch; their readback, ``resident_tiers``, is
    post-window host code like the histogram's. A dispatch root that
    peeked at it would be a third per-dispatch readback: flagged."""
    path = "minpaxos_tpu/parallel/sharded.py"
    src = (REPO / path).read_text()
    assert run_passes(Project({path: src}), ("resident-loop",)) == []
    hook = "        self._seed += k_rounds\n        # the per-dispatch scalar"
    assert src.count(hook) == 1
    peek = src.replace(hook, "        self.resident_tiers()\n" + hook)
    vs = run_passes(Project({path: peek}), ("resident-loop",))
    assert vs and all("run_resident" in v.msg for v in vs), vs


# ----------------------------------------------------------- spec-sync


SPEC_MINI = '''
ABSTRACT_ACTIONS = ("Phase1a", "Phase1b", "Phase2a", "Phase2b",
                    "Commit", "Skip", "Stutter")
MSGKIND_ACTIONS = {
    "PREPARE": ("Phase1a", "Phase1b"),
    "ACCEPT": ("Phase2a", "Phase2b"),
}
'''

SPEC_KERNEL = '''
def step(kind, MsgKind):
    p = kind == int(MsgKind.PREPARE)
    a = kind == int(MsgKind.ACCEPT)
    return p, a
'''

SPEC_SYNC_BAD = '''
from minpaxos_tpu.wire.messages import MsgKind

def step(kind):
    return kind == int(MsgKind.RECONF)
'''


def lint_spec_pair(kernel_src, spec_src=SPEC_MINI):
    return run_passes(Project({
        "minpaxos_tpu/verify/spec.py": spec_src,
        "minpaxos_tpu/models/kernel.py": kernel_src,
    }), ("spec-sync",))


def test_spec_sync_quiet_when_table_matches_kernel():
    assert lint_spec_pair(SPEC_KERNEL) == []


def test_spec_sync_flags_unmapped_kernel_kind():
    src = SPEC_KERNEL.replace(
        "return p, a",
        "r = kind == int(MsgKind.RECONF)\n"
        "    r2 = kind == int(MsgKind.RECONF)  # same kind: one report\n"
        "    return p, a, r, r2")
    vs = lint_spec_pair(src)
    assert len(vs) == 1 and vs[0].rule == "spec-sync", vs
    assert vs[0].path.endswith("kernel.py")
    assert "MsgKind.RECONF" in vs[0].msg and "MSGKIND_ACTIONS" in vs[0].msg


def test_spec_sync_flags_stale_table_entry():
    vs = lint_spec_pair("def step(kind, MsgKind):\n"
                        "    return kind == int(MsgKind.PREPARE)\n")
    assert len(vs) == 1 and "stale" in vs[0].msg, vs
    assert "'ACCEPT'" in vs[0].msg and vs[0].path.endswith("spec.py")


def test_spec_sync_flags_unknown_abstract_action():
    spec = SPEC_MINI.replace('"ACCEPT": ("Phase2a", "Phase2b"),',
                             '"ACCEPT": ("Teleport",),')
    vs = lint_spec_pair(SPEC_KERNEL, spec)
    assert len(vs) == 1 and "Teleport" in vs[0].msg, vs
    assert vs[0].path.endswith("spec.py")


def test_spec_sync_table_must_stay_pure_literal():
    spec = ('ABSTRACT_ACTIONS = ("Phase1a",)\n'
            'MSGKIND_ACTIONS = dict(PREPARE=("Phase1a",))\n')
    vs = lint_spec_pair("def step(kind, MsgKind):\n"
                        "    return kind == int(MsgKind.PREPARE)\n", spec)
    assert len(vs) == 1 and "pure" in vs[0].msg and "literal" in vs[0].msg


def test_spec_sync_missing_table_is_a_violation():
    vs = lint_spec_pair(SPEC_KERNEL, 'ABSTRACT_ACTIONS = ("Phase1a",)\n')
    assert len(vs) == 1 and "MSGKIND_ACTIONS" in vs[0].msg, vs


def test_spec_sync_host_side_cluster_exempt():
    """models/cluster.py routes client replies (environment outputs,
    not consensus transitions) — its MsgKind compares are out of
    scope by design."""
    vs = run_passes(Project({
        "minpaxos_tpu/verify/spec.py": SPEC_MINI,
        "minpaxos_tpu/models/kernel.py": SPEC_KERNEL,
        "minpaxos_tpu/models/cluster.py":
            "def route(kind, MsgKind):\n"
            "    return kind == int(MsgKind.PROPOSE_REPLY)\n",
    }), ("spec-sync",))
    assert vs == []


def test_spec_sync_silent_without_both_sides():
    """Fixture projects that carry only kernels or only the spec have
    nothing to sync (keeps every OTHER rule's fixtures quiet)."""
    assert run_passes(Project(
        {"minpaxos_tpu/models/kernel.py": SPEC_SYNC_BAD},
        ), ("spec-sync",)) == []
    assert run_passes(Project(
        {"minpaxos_tpu/verify/spec.py": SPEC_MINI},
        ), ("spec-sync",)) == []


def test_spec_sync_real_table_is_load_bearing():
    """The real tree is clean, and deleting one real table entry fires
    exactly the unmapped-kind violation for that kind — the pass is
    reading the actual correspondence, not rubber-stamping."""
    files = {p: (REPO / p).read_text() for p in (
        "minpaxos_tpu/verify/spec.py",
        "minpaxos_tpu/models/minpaxos.py",
        "minpaxos_tpu/models/mencius.py",
        "minpaxos_tpu/models/cluster.py",
    )}
    assert run_passes(Project(files), ("spec-sync",)) == []
    files["minpaxos_tpu/verify/spec.py"] = files[
        "minpaxos_tpu/verify/spec.py"].replace('    "SKIP": ("Skip",),\n',
                                               "")
    vs = run_passes(Project(files), ("spec-sync",))
    assert vs and all(v.rule == "spec-sync" for v in vs), vs
    assert any("MsgKind.SKIP" in v.msg
               and v.path.endswith("mencius.py") for v in vs), vs


_CLI_SEEDS = {
    "trace-hazard": ("minpaxos_tpu/models/seed.py", TRACE_BAD),
    "recompile-hazard": ("minpaxos_tpu/ops/seed.py",
                         "def f(x, buf=[]):\n    return buf\n"),
    "wire-contract": ("minpaxos_tpu/wire/messages.py", None),  # drifted
    "concurrency": ("minpaxos_tpu/runtime/transport.py", CONC_BAD),
    "wall-honesty": ("minpaxos_tpu/models/seed.py",
                     "def step(state, tick_inc):\n"
                     "    return state.stall_ticks + 1\n"),
    "broad-except": ("minpaxos_tpu/utils/seed.py",
                     "def f():\n    try:\n        g()\n"
                     "    except Exception:\n        pass\n"),
    "quorum-certificate": ("minpaxos_tpu/models/flex.py", QUORUM_BAD),
    "lock-order": ("minpaxos_tpu/runtime/transport.py", LOCK_CYCLE),
    "resident-loop": ("minpaxos_tpu/parallel/seed.py", RESIDENT_BAD),
    "spec-sync": ("minpaxos_tpu/models/seed.py", SPEC_SYNC_BAD),
}


@pytest.mark.parametrize("rule", sorted(_CLI_SEEDS))
def test_cli_nonzero_on_each_seeded_rule(tmp_path, rule):
    """Acceptance: tools/lint.py exits nonzero on a seeded violation
    of EVERY rule, and attributes it to that rule."""
    rel, src = _CLI_SEEDS[rule]
    if src is None:  # wire drift: real registry with SKIP renumbered
        src = (REPO / rel).read_text().replace("SKIP = 28", "SKIP = 24")
    dst = tmp_path / rel
    dst.parent.mkdir(parents=True, exist_ok=True)
    dst.write_text(src)
    if rule == "spec-sync":  # needs the real table alongside the seed
        spec_rel = "minpaxos_tpu/verify/spec.py"
        spec_dst = tmp_path / spec_rel
        spec_dst.parent.mkdir(parents=True, exist_ok=True)
        spec_dst.write_text((REPO / spec_rel).read_text())
    out = subprocess.run(
        [sys.executable, str(REPO / "tools/lint.py"), "--root",
         str(tmp_path), "--rules", rule, "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["counts"].get(rule, 0) >= 1, payload
