"""Runner ``pod``: one chip's share of the sharded pod — G independent
MinPaxos groups x R replicas resident on the device, driven through
``ShardedCluster.begin_resident`` / ``run_resident``; the proposal
stream is generated on the device from (seed, round).

The window drives ``run_resident`` dispatches of ``rounds_per_dispatch``
rounds back to back; the host reads two scalars a dispatch. ``check``
drains, then holds committed == injected, equal frontiers on all R
replicas of every group, and every replica's whole KV table of a seeded
sample of groups to a host replay of the same stream
(``benchmarks/lib/podstream.py``, which imports nothing of the program).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.lib import podstream
from benchmarks.lib.stats import hist_median_bin
from benchmarks.lib.tables import dump_table

#: ``ShardedCluster.elect`` delivers the PREPAREs and their replies: two
#: rounds of the (seed, round) stream, with no proposal in them, go by
#: before the first dispatch
ELECT_ROUNDS = 2

LIMITS = {"uncommitted": 0, "in_flight_after_drain": 0,
          "frontier_disagreements": 0, "kv_dropped": 0, "table_mismatch": 0}


def table_mismatch(want: dict[int, int], tables: list[dict[int, int]]) -> int:
    """(key, value) pairs by which the replicas' tables differ from the
    replay's dict, summed over the replicas."""
    items = set(want.items())
    return sum(len(items ^ set(t.items())) for t in tables)


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.sc = None
        self.dispatches: list[dict] = []  # every dispatch: round0, k, n

    # ------------------------------------------------------- set-up

    def setup(self) -> None:
        from minpaxos_tpu.models.minpaxos import MinPaxosConfig
        from minpaxos_tpu.parallel.sharded import ShardedCluster

        ctx, c = self.ctx, self.ctx.config
        # the cell offers this many proposals per group and round; the
        # configuration's number is the most a round can take
        self.p = int(ctx.workload["proposals_per_round"])
        self.k = int(c["rounds_per_dispatch"])
        # the workload's seed lane is 32 bits wide on the device
        self.seed32 = ctx.seed % 0x7FFFFFFF
        cfg = MinPaxosConfig(
            n_replicas=c["n_replicas"], window=c["window"], inbox=c["inbox"],
            exec_batch=c["exec_batch"], kv_pow2=c["kv_pow2"],
            catchup_rows=c["catchup_rows"], recovery_rows=c["recovery_rows"])
        self.sc = sc = ShardedCluster(cfg, c["groups"],
                                      ext_rows=c["proposals_per_round"],
                                      key_space=c["key_space"],
                                      seed=self.seed32)
        sc.elect(0)
        self.round = ELECT_ROUNDS  # the runner's own count of rounds run
        self.start_committed = sc.committed()[0]
        ctx.log("init + elect done")
        sc.begin_resident()
        for _ in range(int(ctx.workload["warm_dispatches"])):
            self._dispatch(self.p)
        # a fresh histogram at the window's start: slots already in
        # flight are left out of the latency sample
        sc.begin_resident()

    def _dispatch(self, n_prop: int) -> tuple[int, int]:
        self.dispatches.append({"round0": self.round, "k": self.k,
                                "n": n_prop})
        self.round += self.k
        return self.sc.run_resident(self.k, n_prop)

    # ------------------------------------------------------- window

    def window(self) -> float:
        import jax

        ctx, tracer = self.ctx, self.ctx.tracer
        self.committed_open = self.sc.committed()[0]  # blocks: device idle
        self.traced_rounds = 0
        rounds = 0
        if tracer is not None:
            tracer.start()
        t0 = time.monotonic()
        while True:
            with jax.profiler.TraceAnnotation("bench.pod_dispatch"):
                committed, in_flight = self._dispatch(self.p)
            rounds += self.k
            now = time.monotonic()
            if tracer is not None and not self.traced_rounds \
                    and now - t0 >= min(ctx.trace_seconds, ctx.seconds):
                tracer.stop()
                self.traced_rounds = rounds
            if now - t0 >= ctx.seconds:
                break
        self.window_s = now - t0
        self.rounds = rounds
        self.committed_close, self.in_flight_close = committed, in_flight
        self.hist = self.sc.resident_hist()
        return t0

    def end_to_end(self) -> dict:
        commits = self.committed_close - self.committed_open
        round_s = self.window_s / self.rounds
        return {"pod_commits_per_s": commits / self.window_s,
                "pod_commit_p50_ms":
                    hist_median_bin(self.hist) * round_s * 1e3}

    def counters(self) -> dict:
        return {"window_s": self.window_s, "rounds": self.rounds,
                "dispatches": self.rounds // self.k,
                "traced_rounds": self.traced_rounds,
                "commits_in_window": self.committed_close
                - self.committed_open,
                "latency_samples": int(np.asarray(self.hist).sum()),
                "p50_rounds": hist_median_bin(self.hist)}

    # -------------------------------------------------------- check

    def check(self):
        ctx, c, sc = self.ctx, self.ctx.config, self.sc
        g = c["groups"]
        in_window = self.rounds * self.p * g
        for i in range(int(ctx.workload["max_drain_dispatches"])):
            committed, in_flight = self._dispatch(0)
            upto = np.asarray(sc.ss.states.committed_upto)     # [G, R]
            executed = np.asarray(sc.ss.states.executed_upto)  # [G, R]
            disagree = int((upto != upto[:, :1]).sum()
                           + (executed != upto).sum())
            if in_flight == 0 and disagree == 0:
                break
        ctx.log(f"drained after {i + 1} dispatches")
        injected = sum(d["k"] * d["n"] for d in self.dispatches) * g
        numbers = {
            "uncommitted": abs(committed - self.start_committed - injected),
            "in_flight_after_drain": in_flight,
            "frontier_disagreements": disagree,
            "kv_dropped": int(np.asarray(sc.ss.states.kv.dropped).sum())}
        # the plain reference: the configuration's stream replayed on
        # the host for a seeded sample of groups, against EVERY
        # replica's whole table of those groups
        sample = sorted(np.random.default_rng(ctx.seed).choice(
            g, size=min(int(ctx.workload["reference_groups"]), g),
            replace=False).tolist())
        rounds = [r for d in self.dispatches if d["n"]
                  for r in range(d["round0"], d["round0"] + d["k"])]

        def replay(rounds):
            return podstream.replay(self.seed32, rounds, sample, self.p,
                                    c["key_space"])

        kv = sc.ss.states.kv
        tables = {}
        for s in sample:
            arrs = [np.asarray(x[s]) for x in kv[:4]]  # each [R, ...]
            tables[s] = [dump_table(*(a[r] for a in arrs))
                         for r in range(c["n_replicas"])]
        evidence = {"want": replay(rounds), "tables": tables,
                    "rounds": rounds, "replay": replay}
        if ctx.control is not None:  # something else in the program's place
            evidence = ctx.control.apply(evidence)
        ctx.log(f"evidence: groups {sample}, {len(rounds)} rounds, "
                f"{[len(evidence['want'][s]) for s in sample]} keys each")
        numbers["table_mismatch"] = sum(
            table_mismatch({k: v & 0xFFFFFFFF
                            for k, v in evidence["want"][s].items()},
                           evidence["tables"][s]) for s in sample)
        failed = min(numbers["uncommitted"], in_window)
        return numbers, LIMITS, in_window, failed

    def close(self) -> None:
        self.sc = None
