"""Runner ``pod_mencius_fault``: runner ``pod_mencius``'s Mencius pod with
ONE OWNER DOWN in its window: owner ``victim`` of every group is killed
between two resident dispatches, at round ``healthy_rounds`` of the
window, and stays dead through the rest of the window and the drain.
The other owners stay loaded; the victim is offered nothing from the
kill on (its clients lost their server). Set-up, the dispatch loop's
two scalars and the end-to-end metrics are ``runners/pod_mencius.py``'s,
which it subclasses; the schedule loop is ``runners/pod_fault.py``'s.

The program is never told the schedule: its owners' successors find the
dead owner's turns blocking the merged frontier and revoke them
(``models/mencius.py`` section 7r).

A traced run profiles the ``trace_seconds`` that BEGIN with the dispatch
in which the victim is killed.

Which of the victim's commands survive is decided from the schedule,
not from the program: every command the victim was offered before the
kill. A command's ACCEPT leaves its owner in the round it is offered and
is delivered in the next (a kill silences what a replica sends from the
kill on, not what was already routed), so every live replica has
accepted the victim's commands of its last rounds, and a majority of
them is live: they must commit WITH their values. Its commands of the
last two rounds before the kill are the ones no live replica had seen
committed at the kill (the owner commits two rounds after it proposes);
``victim_values_lost`` counts them where they are missing.

A live owner may REFUSE a proposal: while the frontier waits for the
dead owner's turns the window fills, and a proposal beyond it is
answered at once with a refusal (a PROPOSE_REPLY) instead of a slot.
Its client was told; it is not lost. The dispatch loop reads, as it
always does, the commands committed and in flight, so the commands
given a slot are known a dispatch at a time, and so are the refusals:
the offered rows less the rows given a slot. A refused command must not
commit; one given a slot must. Where a dispatch refused some rows, which
ones is the program's business, so a key whose LAST offered write falls
in such a dispatch is left out of the comparison and counted
(``keys_undecided``, limit 0: the window lasts until every such key is
written again, but for a chance of one in a million).

``check`` drains with the victim still dead, then holds:

* ``uncommitted``: |commands committed - commands given a slot|, limit 0;
* ``in_flight_after_drain``, ``frontier_disagreements`` (the live
  replicas: one frontier, executed to it), ``kv_dropped``,
  ``slots_unaccounted``: as ``pod_mencius``, limit 0;
* ``table_mismatch``: every LIVE replica's whole table of the sampled
  groups against the plain reference (``lib/menciusstream.py``: each
  owner's stream, the victim's up to the kill), limit 0;
* ``victim_values_lost``, limit 0; ``keys_undecided``, limit 0;
* ``refused``: proposals refused in the window and the drain; and
  ``revoked_slots_off``: |no-op slots of the window a revocation made -
  the victim's turns the schedule puts in it|. Both limits were set
  from readings (PERF.md section 4).
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.lib import menciusstream
from benchmarks.lib.tables import dump_table
from benchmarks.runners import pod_mencius

#: the window lasts ``--seconds`` and at least ``min_rounds_after_kill``
#: rounds past the kill: long enough that the keys written in rounds
#: that refused are all written again, but for a chance of this many
#: in a million runs (a traced window, whose profiler slows its first
#: rounds, or a rehearsal on the CPU would close too early otherwise)
UNDECIDED_PER_MILLION = 1


def min_rounds_after_kill(workload: dict, config: dict, groups: int) -> int:
    """Rounds after which a key an owner last wrote in a round that
    refused is written again, but for a chance of
    ``UNDECIDED_PER_MILLION`` in a million over the sampled groups'
    keys: a round writes ``proposals_per_owner`` distinct keys of an
    owner's ``keys_per_owner``."""
    keys = groups * int(config["n_replicas"]) * int(config["keys_per_owner"])
    miss = 1 - int(workload["proposals_per_owner"]) / int(
        config["keys_per_owner"])
    return math.ceil(math.log(keys * 1e6 / UNDECIDED_PER_MILLION)
                     / -math.log(miss))
#: the victim's commands of this many rounds before the kill were
#: accepted but not yet committed when it died (its COMMIT leaves two
#: rounds after its ACCEPT)
UNCOMMITTED_AT_KILL = 2
#: ``refused``: what the first revocation costs, in rounds of the live
#: owners' load (PERF.md section 4: nine rounds and a part read on the
#: chip and at the rehearsal's shape)
REFUSED_ROUNDS = 16
#: ``revoked_slots_off``: the window's edges and the first revocation
#: leave out this many rounds of the victim's turns (PERF.md section 4)
REVOKED_SLACK_ROUNDS = 24


class Runner(pod_mencius.Runner):

    def setup(self) -> None:
        from minpaxos_tpu.models import mencius

        if not hasattr(mencius, "revoke_turns"):
            # ends at once, before anything is built or compiled
            raise SystemExit(
                "bench: this program's models/mencius.py has no "
                "revocation of a silent owner's turns (revoke_turns): "
                "with an owner dead its pod never drains within the "
                "cell's limit, so it cannot run a pod_mencius_fault "
                "configuration; nothing was run")
        w, c = self.ctx.workload, self.ctx.config
        k = int(c["rounds_per_dispatch"])
        self.victim = int(w["victim"])
        self.kill_at = int(w["healthy_rounds"])
        if self.kill_at % k:
            raise ValueError("the kill falls inside a dispatch of "
                             f"{k} rounds")
        if not 0 < self.victim < int(c["n_replicas"]):
            raise ValueError("the victim is an owner of the group other "
                             "than replica 0, whose frontier is counted")
        self.dead = False
        super().setup()
        # the alive mask's writer is a program too: built here, by a
        # write that changes nothing, not inside the window
        self.sc.revive(self.victim)

    def _dispatch(self, n_prop: int) -> tuple[int, int]:
        """Every owner is offered ``n_prop``; a dead victim nothing.
        The dispatch's record keeps the commands given a slot since
        boot: committed plus in flight."""
        committed, in_flight = pod_mencius.pod.Runner._dispatch(
            self, [0 if self.dead and o == self.victim else n_prop
                   for o in range(self.owners)])
        self.dispatches[-1]["assigned"] = committed + in_flight
        return committed, in_flight

    # ------------------------------------------------------- window

    def window(self) -> float:
        import jax

        ctx, tracer, sc = self.ctx, self.ctx.tracer, self.sc
        self.committed_open = sc.committed()[0]  # blocks: device idle
        self.traced_rounds = 0
        self.first_window_round = self.round
        c, w = self.ctx.config, self.ctx.workload
        last = self.kill_at + min_rounds_after_kill(
            w, c, min(int(w["reference_groups"]), int(c["groups"])))
        rounds, t_trace = 0, None
        t0 = time.monotonic()
        while True:
            if rounds == self.kill_at:
                sc.kill(self.victim)
                self.dead = True
                if tracer is not None:
                    tracer.start()
                    t_trace, trace_round0 = time.monotonic(), rounds
            with jax.profiler.TraceAnnotation("bench.pod_dispatch"):
                committed, in_flight = self._dispatch(self.p)
            rounds += self.k
            now = time.monotonic()
            if t_trace is not None and not self.traced_rounds \
                    and now - t_trace >= min(ctx.trace_seconds, ctx.seconds):
                tracer.stop()
                self.traced_rounds = rounds - trace_round0
            if now - t0 >= ctx.seconds and rounds >= last:
                break
        if t_trace is not None and not self.traced_rounds:
            tracer.stop()  # the window closed inside the traced part
            self.traced_rounds = rounds - trace_round0
        self.window_s = now - t0
        self.rounds = rounds
        self.committed_close, self.in_flight_close = committed, in_flight
        self.hist = sc.resident_hist()
        return t0

    def counters(self) -> dict:
        """``pod_mencius``'s, with the window's revocation counts from
        the same readback (``obs.process_pods()``): ``revoked_slots``,
        ``takeover_episodes``, ``stalled_rounds``; and what the window
        offered and refused, by the dispatch loop's own counts."""
        from minpaxos_tpu import obs

        counters = super().counters()
        entry = obs.process_pods()[-1]
        window = [d for d in self.dispatches
                  if d["round0"] >= self.first_window_round]
        offered = sum(d["k"] * sum(d["n"]) for d in window) \
            * self.ctx.config["groups"]
        return {**counters,
                "revoked_slots": entry["revoked_slots"],
                "takeover_episodes": entry["takeover_episodes"],
                "stalled_rounds": entry["stalled_rounds"],
                "offered_in_window": offered,
                "refused_in_window": sum(self._refused(d) for d in window)}

    def _refused(self, d: dict) -> int:
        i = self.dispatches.index(d)
        before = self.dispatches[i - 1]["assigned"] if i else 0
        return (d["k"] * sum(d["n"]) * self.ctx.config["groups"]
                - (d["assigned"] - before))

    # -------------------------------------------------------- check

    def check(self):
        from minpaxos_tpu import obs

        ctx, c, sc = self.ctx, self.ctx.config, self.sc
        g, owners, live = c["groups"], self.owners, [
            r for r in range(self.owners) if r != self.victim]
        k_round = self.first_window_round + self.kill_at
        after_kill = self.rounds - self.kill_at
        sc.resident_tiers()  # the window's counts, before the drain's
        revoked = obs.process_pods()[-1]["revoked_slots"]
        for i in range(int(ctx.workload["max_drain_dispatches"])):
            committed, in_flight = self._dispatch(0)
            upto = np.asarray(sc.ss.states.committed_upto)     # [G, R]
            executed = np.asarray(sc.ss.states.executed_upto)  # [G, R]
            mine = upto[:, live]
            disagree = int((mine != mine[:, :1]).sum()
                           + (executed[:, live] != mine).sum())
            if in_flight == 0 and disagree == 0:
                break
        ctx.log(f"drained after {i + 1} dispatches")
        counts = sc.command_counts()
        refused = sum(self._refused(d) for d in self.dispatches)
        ctx.log(f"command counts: {counts}; refused {refused}")
        a_round = self.p * g
        numbers = {
            "uncommitted": abs(committed - counts["assigned"]),
            "in_flight_after_drain": in_flight,
            "frontier_disagreements": disagree,
            "kv_dropped": int(np.asarray(sc.ss.states.kv.dropped).sum()),
            "slots_unaccounted": abs(
                int((upto[:, 0] + 1).sum())
                - counts["commands"] - counts["noop_slots"]),
            "refused": refused,
            "revoked_slots_off": abs(revoked - after_kill * a_round)}
        limits = {**pod_mencius.LIMITS, "victim_values_lost": 0,
                  "keys_undecided": 0,
                  "refused": REFUSED_ROUNDS * (owners - 1) * a_round,
                  "revoked_slots_off": REVOKED_SLACK_ROUNDS * a_round}
        # the plain reference: each owner's stream replayed on the host
        # for a seeded sample of groups, the victim's up to the kill,
        # against every LIVE replica's whole table of those groups
        sample = sorted(np.random.default_rng(ctx.seed).choice(
            g, size=min(int(ctx.workload["reference_groups"]), g),
            replace=False).tolist())
        uncertain = {r for d in self.dispatches if self._refused(d)
                     for r in range(d["round0"], d["round0"] + d["k"])}
        rounds_of_owner = [
            [r for d in self.dispatches if d["n"][o]
             for r in range(d["round0"], d["round0"] + d["k"])]
            for o in range(owners)]

        def replay(rounds_of_owner):
            return last_writes(self.seed32, rounds_of_owner, sample, self.p,
                               c["keys_per_owner"])

        kv = sc.ss.states.kv
        tables = {}
        for s in sample:
            arrs = [np.asarray(x[s]) for x in kv[:4]]  # each [R, ...]
            tables[s] = [dump_table(*(a[r] for a in arrs)) for r in live]
        evidence = {"tables": tables, "rounds_of_owner": rounds_of_owner,
                    "victim": self.victim, "kill_round": k_round,
                    "uncommitted_at_kill": UNCOMMITTED_AT_KILL,
                    "keys_per_owner": c["keys_per_owner"], "replay": replay}
        if ctx.control is not None:  # something else in the program's place
            evidence = ctx.control.apply(evidence)
        want = replay(rounds_of_owner)
        lost_rounds = set(range(k_round - UNCOMMITTED_AT_KILL, k_round))
        mismatch = lost = undecided = 0
        for s in sample:
            decided = {key: v & 0xFFFFFFFF
                       for key, (v, r) in want[s].items()
                       if r not in uncertain}
            undecided += len(want[s]) - len(decided)
            at_kill = {key for key, (_, r) in want[s].items()
                       if key // c["keys_per_owner"] == self.victim
                       and r in lost_rounds}
            for t in evidence["tables"][s]:
                got = {key: v for key, v in t.items()
                       if key in decided or key not in want[s]}
                mismatch += len(set(decided.items()) ^ set(got.items()))
                lost += sum(t.get(key) != decided[key] for key in at_kill)
        ctx.log(f"evidence: groups {sample}, {len(uncertain)} rounds with "
                f"refusals, victim's last round {k_round - 1}, "
                f"{[len(want[s]) for s in sample]} keys each")
        numbers.update(table_mismatch=mismatch, victim_values_lost=lost,
                       keys_undecided=undecided)
        in_window = sum(d["k"] * sum(d["n"]) for d in self.dispatches
                        if d["round0"] >= self.first_window_round) * g
        # a refused command was answered as such; the check holds the
        # refusals to their limit
        failed = min(numbers["uncommitted"], in_window)
        return numbers, limits, in_window, failed


def last_writes(seed: int, rounds_of_owner, groups, rows: int,
                keys_per_owner: int) -> dict[int, dict[int, tuple]]:
    """``menciusstream.replay`` with the round of each key's last write:
    ``{group: {key: (value, round)}}``. Owners' key ranges are disjoint,
    so a key's last write is its one owner's last, whatever the merge."""
    offered = [set(rounds) for rounds in rounds_of_owner]
    owners = len(offered)
    out: dict[int, dict[int, tuple]] = {int(g): {} for g in groups}
    for r in sorted(set().union(*offered)):
        keys, vals = menciusstream.owner_rows(seed, r, groups, owners, rows,
                                              keys_per_owner)
        for o in range(owners):
            if r in offered[o]:
                for i, g in enumerate(groups):
                    out[int(g)].update(
                        (key, (val, r)) for key, val in
                        zip(keys[i, o].tolist(), vals[i, o].tolist()))
    return out
