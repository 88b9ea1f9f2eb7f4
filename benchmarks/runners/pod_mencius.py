"""Runner ``pod_mencius``: the Mencius pod — G independent groups x R
replicas resident on the device, EVERY replica an owner that proposes
(rotating ownership, no election), driven through
``ShardedCluster.begin_resident`` / ``run_resident`` like runner ``pod``,
whose window, metrics and counters it inherits. Each owner's client
stream is generated on the device from (seed, round, group, owner).

What differs is what is counted and what the reference replays. The
merged log interleaves five owners' slots and holds no-op slots (ceded,
skipped), so the program reports client COMMANDS committed, its no-op
slots apart (``ShardedCluster.command_counts``). ``check`` drains, then
holds commands committed == commands injected, every slot up to the
frontier accounted for as a command or a no-op, equal frontiers and
executed prefixes on all R replicas of every group, and every replica's
whole KV table of a seeded sample of groups to a host replay of the
per-owner streams (``benchmarks/lib/menciusstream.py``, which imports
nothing of the program; owners' key ranges are disjoint, so it is exact
under any interleaving).
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import menciusstream
from benchmarks.lib.tables import dump_table
from benchmarks.runners import pod

LIMITS = {**pod.LIMITS, "slots_unaccounted": 0}


class Runner(pod.Runner):

    def setup(self) -> None:
        from minpaxos_tpu.models.minpaxos import MinPaxosConfig
        from minpaxos_tpu.parallel import sharded

        if not hasattr(sharded, "N_COUNTS"):
            # ends at once, before anything is built or compiled
            raise SystemExit(
                "bench: this program's parallel/sharded.py has no "
                "multi-owner stream or command accounting (N_COUNTS): it "
                "cannot run a pod_mencius configuration; nothing was run")
        ctx, c = self.ctx, self.ctx.config
        self.owners = int(c["n_replicas"])
        # the cell offers this many proposals per OWNER and round; its
        # proposals_per_round is the group's, which necessary_bytes reads
        self.p = int(ctx.workload["proposals_per_owner"])
        if self.p * self.owners != int(ctx.workload["proposals_per_round"]):
            raise ValueError("proposals_per_round is not owners x "
                             "proposals_per_owner")
        self.k = int(c["rounds_per_dispatch"])
        self.seed32 = ctx.seed % 0x7FFFFFFF  # the seed lane is 32 bits
        cfg = MinPaxosConfig(
            n_replicas=self.owners, window=c["window"], inbox=c["inbox"],
            exec_batch=c["exec_batch"], kv_pow2=c["kv_pow2"],
            catchup_rows=c["catchup_rows"], recovery_rows=c["recovery_rows"],
            noop_delay=c["noop_delay"])
        self.sc = sc = sharded.ShardedCluster(
            cfg, c["groups"], ext_rows=c["proposals_per_owner"],
            protocol="mencius", key_space=c["key_space"], seed=self.seed32)
        self.round = 0  # no election: the stream starts at round 0
        self.start_committed = sc.committed()[0]
        ctx.log("init done (rotating ownership: no election)")
        sc.begin_resident()
        for _ in range(int(ctx.workload["warm_dispatches"])):
            self._dispatch(self.p)
        # a fresh histogram and fresh window counts at the window's
        # start: commands already in flight stay out of the sample
        sc.begin_resident()

    def counters(self) -> dict:
        """The parent's, with the window's tier counts and its no-op
        slots from the pod's own entry in ``obs.process_pods()``: no
        accepted per-layer metric reads them yet (PERF.md section 7
        row 6), so the harness's ``counters:`` log line is where a run
        shows them."""
        from minpaxos_tpu import obs

        tiers = self.sc.resident_tiers()
        pod = obs.process_pods()[-1]
        return {**super().counters(),
                "kernel_small_rounds": tiers["kernel_small_rounds"],
                "route_small_rounds": tiers["route_small_rounds"],
                "tier_rounds": tiers["rounds"],
                "working_capacity": tiers["working_capacity"],
                "command_commits": pod["command_commits"],
                "noop_slots": pod["noop_slots"]}

    # -------------------------------------------------------- check

    def check(self):
        ctx, c, sc = self.ctx, self.ctx.config, self.sc
        g, owners = c["groups"], self.owners
        in_window = self.rounds * self.p * owners * g
        for i in range(int(ctx.workload["max_drain_dispatches"])):
            committed, in_flight = self._dispatch(0)
            upto = np.asarray(sc.ss.states.committed_upto)     # [G, R]
            executed = np.asarray(sc.ss.states.executed_upto)  # [G, R]
            disagree = int((upto != upto[:, :1]).sum()
                           + (executed != upto).sum())
            if in_flight == 0 and disagree == 0:
                break
        ctx.log(f"drained after {i + 1} dispatches")
        injected = sum(d["k"] * d["n"] for d in self.dispatches) * owners * g
        counts = sc.command_counts()
        ctx.log(f"command counts: {counts}; injected {injected}")
        numbers = {
            "uncommitted": abs(committed - self.start_committed - injected),
            "in_flight_after_drain": in_flight,
            "frontier_disagreements": disagree,
            "kv_dropped": int(np.asarray(sc.ss.states.kv.dropped).sum()),
            # every slot up to replica 0's frontier is a command or a
            # no-op slot: the two counts partition the log
            "slots_unaccounted": abs(
                int((upto[:, 0] + 1).sum())
                - counts["commands"] - counts["noop_slots"])}
        # the plain reference: each owner's stream replayed on the host
        # for a seeded sample of groups, against EVERY replica's whole
        # table of those groups
        sample = sorted(np.random.default_rng(ctx.seed).choice(
            g, size=min(int(ctx.workload["reference_groups"]), g),
            replace=False).tolist())
        rounds = [r for d in self.dispatches if d["n"]
                  for r in range(d["round0"], d["round0"] + d["k"])]

        def replay(rounds_of_owner):
            return menciusstream.replay(self.seed32, rounds_of_owner, sample,
                                        self.p, c["keys_per_owner"])

        kv = sc.ss.states.kv
        tables = {}
        for s in sample:
            arrs = [np.asarray(x[s]) for x in kv[:4]]  # each [R, ...]
            tables[s] = [dump_table(*(a[r] for a in arrs))
                         for r in range(owners)]
        evidence = {"want": replay([rounds] * owners), "tables": tables,
                    "rounds": rounds, "owners": owners, "replay": replay}
        if ctx.control is not None:  # something else in the program's place
            evidence = ctx.control.apply(evidence)
        ctx.log(f"evidence: groups {sample}, {len(rounds)} rounds x "
                f"{owners} owners, "
                f"{[len(evidence['want'][s]) for s in sample]} keys each")
        numbers["table_mismatch"] = sum(
            pod.table_mismatch({k: v & 0xFFFFFFFF
                                for k, v in evidence["want"][s].items()},
                               evidence["tables"][s]) for s in sample)
        failed = min(numbers["uncommitted"], in_window)
        return numbers, LIMITS, in_window, failed
