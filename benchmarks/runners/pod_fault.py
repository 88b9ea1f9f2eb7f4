"""Runner ``pod_fault``: runner ``pod``'s chip share of the sharded pod
with a FAULT SCHEDULE in its window: one follower of every group is
killed between two resident dispatches, stays dead for the cell's
``dead_rounds`` and is revived, all under the full proposal rate. Set-up,
the dispatch loop's two scalars, the end-to-end metrics and the plain
reference are ``runners/pod.py``'s, which it subclasses.

The schedule is by round index of the window (``healthy_rounds``, then
``dead_rounds``, then loaded rounds to the window's end) and is applied
by ``ShardedCluster.kill`` / ``revive``: device-side writes of the
``alive`` mask, nothing read back. The program is never told the
schedule: whether the revived replica needs a state transfer is decided
on the device from protocol state (models/minpaxos.py ``transfer_needs``).

A traced run profiles the ``trace_seconds`` that BEGIN with the dispatch
in which the victim is revived (the steady runner's first seconds would
hold no fault); ``traced_rounds`` counts those rounds.

``check`` is the steady cell's (five numbers, limit 0, the victim's
frontier and whole table among them) and three more that tie the result
to the schedule and to the mechanism:

* ``outage_rounds_off``: |rounds dispatched inside the window with the
  victim dead - ``dead_rounds``|, limit 0;
* ``victim_behind_at_close``: groups whose victim, at the window's
  close and before any drain, trails its leader by more than
  ``retention`` (window / 2) slots, limit 0: it recovers under load;
* ``transfers_off``: |state transfers the pod counted in the window -
  groups|: one install a group. Limit groups / 8 (PERF.md section 4
  has the readings it was set from).
"""

from __future__ import annotations

import time
import types

import numpy as np

from benchmarks.runners import pod

#: the window lasts ``--seconds`` and at least this many rounds past the
#: revive, so that a slow machine (a rehearsal on the CPU) still closes
#: its window on a recovery and not on an outage; on the chip the 30 s
#: window holds some thirty rounds after the revive and this never binds
MIN_RECOVERY_ROUNDS = 8


class Runner(pod.Runner):

    def setup(self) -> None:
        from minpaxos_tpu.parallel import sharded

        if not hasattr(sharded, "transfer_round"):
            # ends at once, before anything is built or compiled
            raise SystemExit(
                "bench: this program's parallel/sharded.py has no state "
                "transfer in the pod round (transfer_round): a replica "
                "dead for longer than retention stays frozen for good, so "
                "it cannot run a pod_fault configuration; nothing was run")
        w, k = self.ctx.workload, int(self.ctx.config["rounds_per_dispatch"])
        self.victim = int(w["victim"])
        self.kill_at = int(w["healthy_rounds"])
        self.revive_at = self.kill_at + int(w["dead_rounds"])
        if self.kill_at % k or self.revive_at % k:
            raise ValueError("the schedule falls inside a dispatch of "
                             f"{k} rounds")
        if not 0 < self.victim < int(self.ctx.config["n_replicas"]):
            raise ValueError("the victim is a follower (the leader is 0)")
        super().setup()
        # the alive mask's writer is a program too: built here, by a
        # write that changes nothing, not inside the window
        self.sc.revive(self.victim)

    # ------------------------------------------------------- window

    def window(self) -> float:
        import jax

        from minpaxos_tpu import obs

        ctx, tracer, sc = self.ctx, self.ctx.tracer, self.sc
        self.committed_open = sc.committed()[0]  # blocks: device idle
        self.traced_rounds = 0
        self.dead_dispatches = 0
        self.first_window_round = self.round
        last = self.revive_at + MIN_RECOVERY_ROUNDS
        rounds, dead, t_trace = 0, False, None
        t0 = time.monotonic()
        while True:
            if rounds == self.kill_at:
                sc.kill(self.victim)
                dead = True
            if rounds == self.revive_at:
                sc.revive(self.victim)
                dead = False
                if tracer is not None:
                    tracer.start()
                    t_trace, trace_round0 = time.monotonic(), rounds
            with jax.profiler.TraceAnnotation("bench.pod_dispatch"):
                committed, in_flight = self._dispatch(self.p)
            self.dead_dispatches += dead
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
        # the window's installs, as that readback left them; the drain
        # may add its own
        self.transfers = obs.process_pods()[-1]["state_transfers"]
        # where every replica stands as the window closes, before any
        # drain: the victim has to be back within its leader's window
        self.upto_close = np.asarray(sc.ss.states.committed_upto)  # [G, R]
        return t0

    def counters(self) -> dict:
        """The steady runner's, with the pod's recovery counts over the
        window from the same ``resident_tiers()`` readback
        (``obs.process_pods()``): ``state_transfers``,
        ``state_transfer_bytes``, ``lagging_rounds``; the round
        sections' gates (``px.state_transfer``) join ``gates``, which
        ``gate_open_pct.pod`` reads."""
        from minpaxos_tpu import obs

        counters = super().counters()
        entry = obs.process_pods()[-1]
        return {**counters,
                "gates": {**counters["gates"], **entry["round_gates"]},
                "state_transfers": entry["state_transfers"],
                "state_transfer_bytes": entry["state_transfer_bytes"],
                "lagging_rounds": entry["lagging_rounds"],
                "dead_rounds": self.dead_dispatches * self.k}

    # -------------------------------------------------------- check

    def check(self):
        ctx, c = self.ctx, self.ctx.config
        retention = c["window"] // 2
        behind = (self.upto_close[:, self.sc.leader]
                  - self.upto_close[:, self.victim])
        control = ctx.control
        if control is not None:
            # a control of this cell is told the schedule too
            kill_round = self.first_window_round + self.kill_at
            ctx.control = types.SimpleNamespace(apply=lambda ev: control.apply(
                {**ev, "victim": self.victim, "rounds_before_kill": [
                    r for r in ev["rounds"] if r < kill_round]}))
        try:
            numbers, limits, attempted, failed = super().check()
        finally:
            ctx.control = control
        numbers = {
            **numbers,
            "outage_rounds_off": abs(self.dead_dispatches * self.k
                                     - int(ctx.workload["dead_rounds"])),
            "victim_behind_at_close": int((behind > retention).sum()),
            "transfers_off": abs(self.transfers - c["groups"])}
        limits = {**limits, "outage_rounds_off": 0,
                  "victim_behind_at_close": 0,
                  "transfers_off": c["groups"] // 8}
        return numbers, limits, attempted, failed
