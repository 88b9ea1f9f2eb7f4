"""paxmc: bounded model checking of the protocol kernels themselves.

The reference codebase ships a 718-line TLA+ spec because Paxos safety
bugs hide in interleavings no test reaches — but a spec certifies the
*spec*, not the code. paxmc explores the REAL compiled step functions
(``models/minpaxos.py replica_step_impl`` — which is also classic
paxos under ``explicit_commit`` — and ``models/mencius.py
mencius_step_impl``) at a small configuration (N=3 replicas, an
8-slot window, one message per step), under every interleaving the
bounds admit, and holds every reached state to the same invariant
predicates the chaos campaigns check on live TCP clusters
(``verify/invariants.py``).

**Network model.** The runtime's transport is TCP: per directed link,
frames arrive in order (``runtime/transport.py``; the chaos shim's
``reorder`` policy is explicitly an attack on stragglers *across*
links). The checker models exactly that: one FIFO queue per directed
link (replica->replica plus client->replica ingress), and an
adversarial scheduler that at every step chooses among

* **deliver** the head of any nonempty link (one protocol substep of
  the destination replica),
* **drop** the head (bounded by ``Bounds.drops`` — a lost frame),
* **duplicate** the head (deliver without consuming, bounded by
  ``Bounds.dups``),
* **reorder** (deliver the SECOND frame of a link first, bounded by
  ``Bounds.reorders`` — the chaos shim's cross-TCP-stream case),
* an **internal tick** of any replica (empty inbox: retry, catch-up,
  gossip machinery; bounded per replica by ``Bounds.internal``),
* an **election** (``become_leader`` on an electable replica, bounded
  by ``Bounds.elections`` — the classic two-leaders gauntlet).

Exploration is breadth-first with canonical state hashing: a state is
the tuple (all replicas' device arrays, all link queues, remaining
budgets), hashed by content; revisits are pruned, so commuting
interleavings collapse and the first counterexample found is minimal
in action count. Within the bounds the search is EXHAUSTIVE: it
terminates by draining the frontier, and ``McResult.drained`` says so
(a result with ``drained=False`` hit ``max_states``/
``max_transitions`` and certifies only the explored prefix).

**Counterexamples** are serializable action traces
(``Counterexample.to_dict``): deterministic to replay
(``replay_counterexample`` re-executes the trace and re-derives the
violation through the same invariant predicates), and exportable as a
``chaos.FaultPlan`` schedule (``counterexample_faultplan``) whose
blocked links reproduce the trace's dropped-message pattern on a live
TCP cluster — static analysis and chaos confirming each other.

**Seeded mutants** (``majority_override``) break the quorum threshold
on purpose — e.g. q=1 at N=3, the non-intersecting configuration the
paxlint ``quorum-certificate`` pass exists to keep out of the tree —
and the checker demonstrates the resulting split-brain commit as a
concrete trace (tests/test_paxmc.py pins this end-to-end).

CLI: ``tools/mc.py`` (``--smoke`` is the tier-1 gate, MC.json the
tracked artifact). Docs: VERIFY.md.
"""

from __future__ import annotations

import hashlib
import time
from collections import deque
from dataclasses import asdict, dataclass, field

import numpy as np

import jax

from minpaxos_tpu.models.mencius import init_mencius, mencius_step_impl
from minpaxos_tpu.models.minpaxos import (
    COMMITTED,
    MinPaxosConfig,
    MsgBatch,
    become_leader,
    init_replica,
    replica_step_impl,
)
from minpaxos_tpu.ops.packed import join_i64, split_i64
from minpaxos_tpu.verify import invariants
from minpaxos_tpu.wire.messages import MsgKind, Op

PROTOCOLS = ("minpaxos", "classic", "mencius")

#: counterexample serialization format tag (tests/fixtures/mc_*.json)
CE_FORMAT = "paxmc-ce-v1"

#: client pseudo-source id in link keys (client ingress queues)
CLIENT = -1


@dataclass(frozen=True)
class Bounds:
    """The exploration bounds. Defaults are the tier-1 smoke bounds
    for the elected-leader protocols (measured to drain in ~20 s on
    the 1-core CI host — 6 435 states / 18 809 transitions — while
    still reaching multi-replica commits, a second concurrent
    election, and every single-drop/single-dup schedule at depth 5);
    ``tools/mc.py`` carries the per-protocol smoke variants."""

    max_depth: int = 5  # actions along any path
    drops: int = 1  # head-of-link drops per path
    dups: int = 1  # head-of-link duplications per path
    reorders: int = 0  # cross-stream reorders per path
    internal: int = 1  # internal ticks per replica per path
    elections: int = 1  # extra elections per path (beyond the boot one)
    electable: tuple[int, ...] = (1,)  # who the extra election may pick
    n_cmds: int = 2  # distinct client commands in the workload
    propose_to: tuple[int, ...] = (0,)  # ingress queues carrying them
    #: replicas never scheduled (a crashed server): nothing is
    #: delivered to them, they never tick, and they send nothing
    silent: tuple[int, ...] = ()
    max_states: int = 400_000  # hard backstop: stop exploring, not CI
    max_transitions: int = 2_000_000

    def to_dict(self) -> dict:
        return asdict(self)


def model_config(protocol: str, majority_override: int | None = None,
                 n_replicas: int = 3, q1: int = 0,
                 q2: int = 0) -> MinPaxosConfig:
    """The small-configuration protocol config the checker drives.

    window=8 holds every slot the bounded runs can touch with the
    window slide OFF (absolute slot == window index: canonical hashing
    never sees a shifted-but-equal state). ``majority_override``
    replaces the certified n//2+1 threshold with a raw quorum size —
    the seeded-mutant hook. The override lives in a SUBCLASS so the
    tuple payload (and therefore jit-cache equality) is untouched;
    explorers jit via per-instance closures, never via shared
    static-argnum caches, so an overridden config can never collide
    with a healthy one.

    ``q1``/``q2`` set the FLEXIBLE quorum fields directly (0 = the
    majority default) — the certified path (verified legs) and the
    planted non-intersecting-pair mutant (``tools/mc.py --mutant
    flex-broken``) both go through the real config fields the kernels
    compile, with no host-side ``validate_config_quorums`` in the way.
    """
    if protocol not in PROTOCOLS:
        raise ValueError(f"unknown protocol {protocol!r}; "
                         f"have {PROTOCOLS}")
    base = dict(
        n_replicas=n_replicas, window=8, inbox=8, exec_batch=4,
        kv_pow2=3, catchup_rows=2, recovery_rows=2, noop_delay=2,
        slide_window=False, gossip_ticks=1, q1=q1, q2=q2,
        explicit_commit=(protocol == "classic"))
    if majority_override is None:
        return MinPaxosConfig(**base)
    cls = type("MutantQuorumConfig", (MinPaxosConfig,), {
        # override every threshold view: the legacy `majority` (what
        # tests pin) and the quorum1/quorum2 properties the kernels
        # now actually read (flexible-quorum sites)
        "majority": property(lambda self: majority_override),
        "quorum1": property(lambda self: majority_override),
        "quorum2": property(lambda self: majority_override),
        "__doc__": "MinPaxosConfig with a seeded quorum threshold",
    })
    return cls(**base)


def _to_np(tree):
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


def _row_tuple(cols: dict, i: int) -> tuple[int, ...]:
    return tuple(int(cols[f][i]) for f in MsgBatch._fields)


@dataclass
class Counterexample:
    """A violating interleaving: the action trace from the initial
    state plus the invariant report it produces."""

    protocol: str
    bounds: Bounds
    majority_override: int | None
    trace: list[dict]
    report: dict
    states_explored: int = 0
    # flexible-quorum config (0/0 = majority defaults; replay rebuilds
    # the exact mutant config from these) — optional in the format so
    # pre-flexible fixtures keep loading
    q1: int = 0
    q2: int = 0
    n_replicas: int = 3
    # paxref extensions (ISSUE 17), all optional in the format:
    # kind "invariant" (the original safety CEs) | "refinement"
    # (verify/refine.py — a concrete step with no abstract
    # counterpart) | "lasso" (verify/liveness.py — trace[loop_start:]
    # is a fair non-progress cycle). `mutant` names a planted kernel
    # mutation replay must re-install ("skip-quorum2",
    # "dueling-leaders").
    kind: str = "invariant"
    mutant: str | None = None
    loop_start: int | None = None

    def to_dict(self) -> dict:
        return {"format": CE_FORMAT, "protocol": self.protocol,
                "bounds": self.bounds.to_dict(),
                "majority_override": self.majority_override,
                "q1": self.q1, "q2": self.q2,
                "n_replicas": self.n_replicas,
                "trace": self.trace, "report": self.report,
                "states_explored": self.states_explored,
                "kind": self.kind, "mutant": self.mutant,
                "loop_start": self.loop_start}

    @classmethod
    def from_dict(cls, d: dict) -> "Counterexample":
        if d.get("format") != CE_FORMAT:
            raise ValueError(f"not a {CE_FORMAT} counterexample: "
                             f"format={d.get('format')!r}")
        loop = d.get("loop_start")
        return cls(protocol=d["protocol"], bounds=Bounds(**d["bounds"]),
                   majority_override=d.get("majority_override"),
                   q1=int(d.get("q1", 0)), q2=int(d.get("q2", 0)),
                   n_replicas=int(d.get("n_replicas", 3)),
                   trace=list(d["trace"]), report=dict(d["report"]),
                   states_explored=int(d.get("states_explored", 0)),
                   kind=str(d.get("kind", "invariant")),
                   mutant=d.get("mutant"),
                   loop_start=None if loop is None else int(loop))


@dataclass
class McResult:
    protocol: str
    bounds: Bounds
    majority_override: int | None
    q1: int = 0
    q2: int = 0
    n_replicas: int = 3
    states: int = 0
    transitions: int = 0
    max_depth_seen: int = 0
    drained: bool = False
    invariants_checked: tuple[str, ...] = (
        "slot-agreement", "validity", "frontier-monotonic")
    counterexample: Counterexample | None = None
    wall_s: float = 0.0

    @property
    def ok(self) -> bool:
        return self.counterexample is None

    def to_dict(self) -> dict:
        return {"protocol": self.protocol, "bounds": self.bounds.to_dict(),
                "majority_override": self.majority_override,
                "q1": self.q1, "q2": self.q2,
                "n_replicas": self.n_replicas,
                "states": self.states, "transitions": self.transitions,
                "max_depth_seen": self.max_depth_seen,
                "drained": self.drained,
                "invariants_checked": list(self.invariants_checked),
                "ok": self.ok,
                "counterexample": (None if self.counterexample is None
                                   else self.counterexample.to_dict()),
                "wall_s": round(self.wall_s, 2)}


class Explorer:
    """One bounded exhaustive exploration of one protocol."""

    #: True in explorers whose check_edge is not a no-op — run() then
    #: pays the edge check even on seen-state-pruned transitions
    _edge_checked = False

    def __init__(self, protocol: str, bounds: Bounds | None = None,
                 majority_override: int | None = None, q1: int = 0,
                 q2: int = 0, n_replicas: int = 3):
        self.protocol = protocol
        self.bounds = bounds or Bounds()
        self.majority_override = majority_override
        self.q1, self.q2 = q1, q2
        self.cfg = model_config(protocol, majority_override,
                                n_replicas=n_replicas, q1=q1, q2=q2)
        self.R = self.cfg.n_replicas
        if protocol == "mencius":
            self._init, step_impl = init_mencius, mencius_step_impl
        else:
            self._init, step_impl = init_replica, replica_step_impl
        cfg = self.cfg
        # per-instance jit closure: the config is baked into the trace,
        # so a mutant threshold can never alias a healthy kernel in a
        # shared static-argnum cache (model_config docstring)
        self._step = jax.jit(lambda st, box: step_impl(cfg, st, box))
        # the workload table (cmd_id == index), shared with validity
        n = self.bounds.n_cmds
        self.w_ops = np.full(n, int(Op.PUT), np.int32)
        self.w_keys = np.arange(n, dtype=np.int64)
        self.w_vals = np.arange(n, dtype=np.int64) * 7 + 1001

    # ---------------------------------------------------- initial state

    def initial(self) -> tuple:
        """(states, links, budgets): boot all replicas, run the boot
        election on replica 0 (minpaxos/classic; mencius needs none),
        and stage the client workload on the ingress queues."""
        states = [_to_np(self._init(self.cfg, i)) for i in range(self.R)]
        links: dict[tuple[int, int], tuple] = {}
        if self.protocol != "mencius":
            st0, prep = become_leader(self.cfg, states[0])
            states[0] = _to_np(st0)
            cols = {f: np.asarray(getattr(prep, f))
                    for f in MsgBatch._fields}
            row = _row_tuple(cols, 0)
            for r in range(1, self.R):
                links[(0, r)] = (row,)
        k_hi, k_lo = split_i64(self.w_keys)
        v_hi, v_lo = split_i64(self.w_vals)
        for c in range(self.bounds.n_cmds):
            row = dict(zip(MsgBatch._fields, [0] * 12))
            row.update(kind=int(MsgKind.PROPOSE), src=-1, op=int(Op.PUT),
                       key_hi=int(k_hi[c]), key_lo=int(k_lo[c]),
                       val_hi=int(v_hi[c]), val_lo=int(v_lo[c]),
                       cmd_id=c, client_id=1)
            rt = tuple(int(row[f]) for f in MsgBatch._fields)
            for to in self.bounds.propose_to:
                links[(CLIENT, to)] = links.get((CLIENT, to), ()) + (rt,)
        budgets = (self.bounds.drops, self.bounds.dups,
                   self.bounds.reorders,
                   (self.bounds.internal,) * self.R, self.bounds.elections)
        return tuple(states), links, budgets

    # ------------------------------------------------------- mechanics

    def _inbox(self, row: tuple[int, ...] | None) -> MsgBatch:
        cols = {f: np.zeros(1, np.int32) for f in MsgBatch._fields}
        if row is not None:
            for f, v in zip(MsgBatch._fields, row):
                cols[f][0] = v
        return MsgBatch(**cols)

    def _expand_outbox(self, links: dict, outbox, src: int) -> dict:
        """Append the step's emitted rows onto the link queues (dst -1
        = broadcast to every other replica, -2 = client-bound, ignored
        here — replies are not part of the safety state)."""
        msgs = _to_np(outbox.msgs)
        dst = np.asarray(outbox.dst)
        cols = {f: getattr(msgs, f) for f in MsgBatch._fields}
        live = np.nonzero(cols["kind"] != 0)[0]
        if not live.size:
            return links
        links = dict(links)
        for i in live:
            d = int(dst[i])
            if d == -2 or d == src:
                continue
            row = _row_tuple(cols, int(i))
            targets = ([r for r in range(self.R) if r != src]
                       if d == -1 else [d] if 0 <= d < self.R else [])
            for t in targets:
                if t not in self.bounds.silent:
                    links[(src, t)] = links.get((src, t), ()) + (row,)
        return links

    def _apply_step(self, states: tuple, links: dict, to: int,
                    row: tuple | None) -> tuple[tuple, dict]:
        # the new state stays as jax arrays: feeding them back into the
        # next jit call skips the numpy->device transfer, and hashing /
        # invariant extraction read them zero-copy via np.asarray (CPU
        # backend) — measured ~30% of the per-transition budget
        st, outbox, _execr = self._step(states[to], self._inbox(row))
        states = states[:to] + (st,) + states[to + 1:]
        return states, self._expand_outbox(links, outbox, to)

    def _apply(self, node: tuple, action: dict) -> tuple:
        """One action -> successor (states, links, budgets)."""
        states, links, (drops, dups, reorders, internal, elects) = node
        a = action["a"]
        if a == "deliver":
            src, to = action["link"]
            q = links[(src, to)]
            links = {**links}
            if len(q) == 1:
                del links[(src, to)]
            else:
                links[(src, to)] = q[1:]
            states, links = self._apply_step(states, links, to, q[0])
        elif a == "drop":
            src, to = action["link"]
            q = links[(src, to)]
            links = {**links}
            if len(q) == 1:
                del links[(src, to)]
            else:
                links[(src, to)] = q[1:]
            drops -= 1
        elif a == "dup":
            src, to = action["link"]
            states, links = self._apply_step(states, links, to,
                                             links[(src, to)][0])
            dups -= 1
        elif a == "reorder":
            src, to = action["link"]
            q = links[(src, to)]
            links = {**links, (src, to): (q[0],) + q[2:]}
            states, links = self._apply_step(states, links, to, q[1])
            reorders -= 1
        elif a == "tick":
            r = action["r"]
            internal = internal[:r] + (internal[r] - 1,) + internal[r + 1:]
            states, links = self._apply_step(states, links, r, None)
        elif a == "elect":
            r = action["r"]
            st, prep = become_leader(self.cfg, states[r])
            states = states[:r] + (_to_np(st),) + states[r + 1:]
            cols = {f: np.asarray(getattr(prep, f))
                    for f in MsgBatch._fields}
            row = _row_tuple(cols, 0)
            links = {**links}
            for peer in range(self.R):
                if peer != r:
                    links[(r, peer)] = links.get((r, peer), ()) + (row,)
            elects -= 1
        else:
            raise ValueError(f"unknown action {action!r}")
        return states, links, (drops, dups, reorders, internal, elects)

    def _actions(self, node: tuple) -> list[dict]:
        states, links, (drops, dups, reorders, internal, elects) = node
        out: list[dict] = []
        for link in sorted(links):
            if link[1] in self.bounds.silent:
                continue
            out.append({"a": "deliver", "link": list(link)})
            if drops > 0:
                out.append({"a": "drop", "link": list(link)})
            if dups > 0:
                out.append({"a": "dup", "link": list(link)})
            if reorders > 0 and len(links[link]) >= 2:
                out.append({"a": "reorder", "link": list(link)})
        for r in range(self.R):
            if internal[r] > 0 and r not in self.bounds.silent:
                out.append({"a": "tick", "r": r})
        if elects > 0 and self.protocol != "mencius":
            for r in self.bounds.electable:
                out.append({"a": "elect", "r": r})
        return out

    # ------------------------------------------------------ canonical

    def _key(self, node: tuple) -> bytes:
        states, links, budgets = node
        h = hashlib.blake2b(digest_size=16)
        for st in states:
            for leaf in jax.tree_util.tree_leaves(st):
                h.update(np.asarray(leaf).tobytes())
        h.update(repr(sorted(links.items())).encode())
        h.update(repr(budgets).encode())
        return h.digest()

    # ------------------------------------------------------ invariants

    def _records(self, st) -> tuple[np.ndarray, int]:
        """Committed slot records for one replica state (window slide
        is off, so window index == absolute slot)."""
        status = np.asarray(st.status)
        idx = np.nonzero(status >= COMMITTED)[0]
        base = int(st.window_base)
        return invariants.make_records(
            base + idx.astype(np.int64),
            np.asarray(st.op)[idx],
            join_i64(np.asarray(st.key_hi)[idx], np.asarray(st.key_lo)[idx]),
            join_i64(np.asarray(st.val_hi)[idx], np.asarray(st.val_lo)[idx]),
            np.asarray(st.cmd_id)[idx],
            np.asarray(st.client_id)[idx],
        ), int(st.committed_upto)

    def check_invariants(self, states: tuple, stepped: int | None = None,
                         pre_frontier: int | None = None
                         ) -> invariants.CheckReport:
        """The shared predicate suite over one model state (the same
        functions chaos runs over live stores — verify/invariants.py)."""
        report = invariants.CheckReport()
        recs: dict[int, np.ndarray] = {}
        fronts: dict[int, int] = {}
        for r, st in enumerate(states):
            recs[r], fronts[r] = self._records(st)
        invariants.check_slot_agreement(recs, fronts, report)
        for r in recs:
            invariants.check_validity(recs[r], self.w_ops, self.w_keys,
                                      self.w_vals, report,
                                      who=f"replica {r}")
        if stepped is not None and pre_frontier is not None:
            invariants.check_frontier_monotonic(
                {stepped: [pre_frontier, fronts[stepped]]}, report)
        return report

    @staticmethod
    def _stepped_replica(action: dict) -> int | None:
        if action["a"] in ("deliver", "dup", "reorder"):
            return action["link"][1]
        if action["a"] == "tick":
            return action["r"]
        return None  # drop / elect never advance a frontier

    # ------------------------------------------------------ paxref hooks

    def check_edge(self, pre_node: tuple, action: dict, post_node: tuple,
                   report: invariants.CheckReport) -> None:
        """Per-edge hook: called for EVERY explored transition (run and
        replay) with the pre/post cluster states. The base explorer
        checks nothing here; ``verify/refine.py``'s RefinementExplorer
        overrides it to hold each concrete step to the abstract spec
        (violations appended to ``report`` fail the edge exactly like
        an invariant breach)."""

    def _make_ce(self, trace: list[dict], report: dict,
                 states_explored: int) -> Counterexample:
        """Counterexample factory — subclasses stamp their kind/mutant
        so replay can rebuild the same explorer."""
        return Counterexample(
            self.protocol, self.bounds, self.majority_override, trace,
            report, states_explored=states_explored, q1=self.q1,
            q2=self.q2, n_replicas=self.R)

    # ------------------------------------------------------ exploration

    def run(self, log=None) -> McResult:
        """Breadth-first exhaustive exploration within the bounds."""
        b = self.bounds
        res = McResult(self.protocol, b, self.majority_override,
                       q1=self.q1, q2=self.q2, n_replicas=self.R)
        t0 = time.monotonic()
        root = self.initial()
        report = self.check_invariants(root[0])
        if not report.ok:  # a broken initial state: depth-0 violation
            res.counterexample = self._make_ce([], report.to_dict(), 1)
            res.wall_s = time.monotonic() - t0
            return res
        seen = {self._key(root)}
        # queue entries: (depth, node, trace-as-parent-chain index)
        parents: list[tuple[int, dict | None]] = [(-1, None)]
        queue: deque = deque([(0, root, 0)])
        res.states = 1
        next_log = 5000
        while queue:
            depth, node, pid = queue.popleft()
            res.max_depth_seen = max(res.max_depth_seen, depth)
            if depth >= b.max_depth:
                continue
            for action in self._actions(node):
                res.transitions += 1
                if res.transitions > b.max_transitions:
                    res.wall_s = time.monotonic() - t0
                    return res  # drained stays False
                stepped = self._stepped_replica(action)
                pre = (int(node[0][stepped].committed_upto)
                       if stepped is not None else None)
                nxt = self._apply(node, action)
                key = self._key(nxt)
                if key in seen:
                    # the STATE was certified when first reached, but a
                    # refinement explorer must still check this EDGE —
                    # a step into a good state can itself be an
                    # unmapped abstract transition
                    if self._edge_checked:
                        report = invariants.CheckReport()
                        self.check_edge(node, action, nxt, report)
                        if not report.ok:
                            trace = [action]
                            p = pid
                            while p >= 0:
                                par, act = parents[p]
                                if act is not None:
                                    trace.append(act)
                                p = par
                            trace.reverse()
                            res.counterexample = self._make_ce(
                                trace, report.to_dict(), res.states)
                            res.wall_s = time.monotonic() - t0
                            return res
                    continue
                seen.add(key)
                res.states += 1
                report = self.check_invariants(nxt[0], stepped, pre)
                self.check_edge(node, action, nxt, report)
                if not report.ok:
                    trace = [action]
                    p = pid
                    while p >= 0:
                        par, act = parents[p]
                        if act is not None:
                            trace.append(act)
                        p = par
                    trace.reverse()
                    res.counterexample = self._make_ce(
                        trace, report.to_dict(), res.states)
                    res.wall_s = time.monotonic() - t0
                    return res
                if res.states >= b.max_states:
                    res.wall_s = time.monotonic() - t0
                    return res  # drained stays False
                parents.append((pid, action))
                queue.append((depth + 1, nxt, len(parents) - 1))
            if log is not None and res.states >= next_log:
                next_log += 5000
                log(f"[paxmc] {self.protocol}: {res.states} states, "
                    f"{res.transitions} transitions, depth "
                    f"{res.max_depth_seen}")
        res.drained = True
        res.wall_s = time.monotonic() - t0
        return res


# ------------------------------------------------------------- replay

def replay_counterexample(ce: Counterexample | dict,
                          ) -> tuple[bool, invariants.CheckReport]:
    """Re-execute a counterexample trace action by action and re-derive
    its violation through the shared invariant predicates. Returns
    (reproduced, the first failing report — or the final clean one).

    Deterministic by construction: the step functions are pure, the
    initial state depends only on (protocol, bounds, override), and
    the trace pins every scheduler choice — so a checked-in fixture
    (tests/fixtures/mc_*.json) replays bit-identically forever or
    fails the regression suite loudly.
    """
    if isinstance(ce, dict):
        ce = Counterexample.from_dict(ce)
    if ce.kind == "lasso":
        from minpaxos_tpu.verify.liveness import replay_lasso

        return replay_lasso(ce)
    ex = _explorer_for(ce)
    node = ex.initial()
    report = ex.check_invariants(node[0])
    if not report.ok:
        return True, report
    for action in ce.trace:
        stepped = Explorer._stepped_replica(action)
        pre = (int(node[0][stepped].committed_upto)
               if stepped is not None else None)
        prev = node
        node = ex._apply(node, action)
        report = ex.check_invariants(node[0], stepped, pre)
        ex.check_edge(prev, action, node, report)
        if not report.ok:
            return True, report
    return False, report


def _explorer_for(ce: Counterexample) -> Explorer:
    """Rebuild the explorer a counterexample was found by — the plain
    safety explorer for kind="invariant" fixtures, the refinement
    explorer (with its planted mutant re-installed) for
    kind="refinement" ones."""
    if ce.kind == "refinement":
        from minpaxos_tpu.verify.refine import RefinementExplorer

        return RefinementExplorer(
            ce.protocol, ce.bounds, ce.majority_override, q1=ce.q1,
            q2=ce.q2, n_replicas=ce.n_replicas, mutant=ce.mutant)
    return Explorer(ce.protocol, ce.bounds, ce.majority_override,
                    q1=ce.q1, q2=ce.q2, n_replicas=ce.n_replicas)


def counterexample_faultplan(ce: Counterexample | dict,
                             duration_s: float = 1.5) -> dict:
    """Project a counterexample onto a live-cluster chaos schedule.

    The trace's dropped/undelivered replica->replica frames become
    ``block``ed links in a :class:`~minpaxos_tpu.chaos.plan.FaultPlan`;
    returned as ``{"plan": <FaultPlan dict>, "events": [...]}`` in the
    campaign runner's event format, runnable against a real TCP
    cluster via ``tools/chaos.py --plan-file``. This is a projection,
    not a bisimulation: a live cluster cannot be forced through one
    exact interleaving, but the plan reproduces the trace's
    *communication pattern* (who could never hear whom), which is the
    part of a safety counterexample a deployment can probe.
    """
    if isinstance(ce, dict):
        ce = Counterexample.from_dict(ce)
    from minpaxos_tpu.chaos.plan import FaultPlan

    ex = Explorer(ce.protocol, ce.bounds, ce.majority_override,
                  q1=ce.q1, q2=ce.q2, n_replicas=ce.n_replicas)
    node = ex.initial()
    blocked: set[tuple[int, int]] = set()
    for action in ce.trace:
        if action["a"] == "drop":
            src, dst = action["link"]
            if src != CLIENT:
                blocked.add((src, dst))
        node = ex._apply(node, action)
    # links with frames still queued at the violation never delivered
    # them either — the live schedule blocks those too
    _states, links, _budgets = node
    for (src, dst), q in links.items():
        if q and src != CLIENT:
            blocked.add((src, dst))
    plan = FaultPlan(ex.R, seed=0)
    for src, dst in sorted(blocked):
        plan.set_link(src, dst, block=True)
    events = [(0.0, "install", plan.to_dict()),
              (float(duration_s), "clear", None)]
    return {"plan": plan.to_dict(), "events": events,
            "protocol": ce.protocol}
