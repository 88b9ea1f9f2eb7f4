"""Runner ``served``: a master and N ``-min -durable`` replica servers
in this process (every replica's step on the chip, fsync on), composed
from the server binary's own flag parser; load arrives over localhost
TCP from the benchmark's JAX-free worker processes, open loop.

The window drives: client sessions -> master lookup -> leader
``ReplicaServer`` (transport, ingress coalescer, the packed step on the
chip, ``StableStore`` fsync, reply egress). ``check`` holds every reply,
all N store files as they lie on the disk, the fsyncs that made them
durable and all N device tables to the plain reference
(``benchmarks/lib/served_check.py``).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmarks.lib import served_check
from benchmarks.lib.fsync_ledger import FsyncLedger
from benchmarks.lib.loadgen import OpenLoopLoad, Traffic
from benchmarks.lib.stats import percentile
from benchmarks.lib.tables import dump_table

BOOT_TIMEOUT_S = 900.0
COUNTERS = ("ticks", "dispatches", "fused_substeps", "proposals",
            "committed", "executed", "idle_skips")
#: a load generator that was held up works its backlog off at this share
#: of the rate the configuration sustains (its swept knee): under it
#: with room, so that the generator's own stall overloads nothing
CATCHUP_KNEE_SHARE = 0.88
#: the window is read in this many slices (counters, fsync time, median
#: latency each), so that a run that reads slow says where and why
SLICES = 6


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cluster = None
        self.load = None
        self.phases: list[dict] = []  # every request sent, warm-up too
        self.snaps: dict[str, dict] = {}
        self.fsyncs = FsyncLedger()

    # ------------------------------------------------------- set-up

    def setup(self) -> None:
        from minpaxos_tpu.chaos.campaign import ChaosCluster
        from minpaxos_tpu.cli import server as server_cli

        ctx, cfg, wl = self.ctx, self.ctx.config, self.ctx.workload
        self.store = store = ctx.scratch / "store"
        store.mkdir()
        self.fsyncs.install()
        # exactly what `python -m minpaxos_tpu.cli.server <flags>` would
        # compile and run: the binary's own flag parser
        args = server_cli.build_parser().parse_args(
            [*cfg["server_flags"], "-storedir", str(store)])
        n = cfg["n_replicas"]
        flags = dataclasses.asdict(server_cli.flags_from_args(args))
        for owned in ("durable", "store_dir"):  # ChaosCluster passes these
            flags.pop(owned)
        self.cluster = ChaosCluster(
            n=n, store_dir=str(store), durable=True,
            tick_s=flags.pop("tick_s"), flags=flags,
            cfg=server_cli.config_from_args(args, n),
            boot_timeout_s=BOOT_TIMEOUT_S)
        deadline = time.monotonic() + BOOT_TIMEOUT_S
        while not all(s.stats["ticks"] > 0
                      for s in self.cluster.servers.values()):
            if time.monotonic() > deadline:
                raise TimeoutError("replicas never ticked after boot")
            time.sleep(0.05)
        ctx.log(f"{n} replicas serving")
        self._warm_table_probe(1 << args.kvpow2)
        self.traffic = Traffic(
            rate_hz=float(wl["rate_hz"]), key_range=int(cfg["key_range"]),
            write_pct=int(wl["write_pct"]), zipf_s=float(wl["zipf_s"]),
            burst_x=float(wl["burst_x"]),
            catchup_hz=CATCHUP_KNEE_SHARE * float(cfg["sustained_rate_hz"]))
        self.load = OpenLoopLoad(self.cluster.maddr, int(wl["sessions"]),
                                 int(wl["workers"]))
        self.load.start()
        # warm traffic at the cell's own rate: every step variant the
        # window will use has run, every session has been answered
        self.load.begin_phase(self.traffic, float(wl["warm_s"]),
                              ctx.seed ^ 0x5EED, 30.0)
        warm = self.load.end_phase()
        self.phases.append(warm)
        ctx.log(f"warm-up: {len(warm['cmd_id'])} requests, "
                f"{int(np.isnan(warm['t_reply']).sum())} unanswered")
        self._say_where_unanswered_are("warm-up", warm)

    @staticmethod
    def _warm_table_probe(capacity: int) -> None:
        """Every 1024th dispatch a replica counts its table's live
        slots on the device (``ReplicaServer._check_kv_load``): two
        small programs that the tick loop would otherwise first build
        inside the window, on the protocol thread. Build them now, at
        the table's shape, so that nothing compiles in the window."""
        import jax.numpy as jnp

        int((jnp.zeros(capacity, jnp.int32) == 1).sum())

    def _say_where_unanswered_are(self, phase: str, res: dict) -> None:
        """For the record of a run that fails: which workers' requests
        got no reply, and when they were due."""
        lost = np.isnan(res["t_reply"])
        if not lost.any():
            return
        cmds = res["cmd_id"][lost]
        due = res["t_due"][lost] - res["t_due"].min()
        self.ctx.log(
            f"{phase}: {int(lost.sum())} unanswered of "
            f"{len(lost)}; by worker "
            f"{np.bincount(cmds >> 27).tolist()}; due "
            f"{due.min():.3f}..{due.max():.3f}s into the phase; "
            f"rejects {res['rejects']}, retransmits {res['retransmits']}")

    # ------------------------------------------------------- window

    def _snap(self, tag: str) -> None:
        leader = self.cluster.servers[0]
        self.snaps[tag] = dict(leader.stats, t=time.monotonic())

    def window(self) -> float:
        import jax

        ctx, wl = self.ctx, self.ctx.workload
        # a traced run profiles the window's END and reads its counters
        # over the part before it: the profiler slows the tick while it
        # runs and stops it for seconds when it is stopped, and neither
        # belongs in a rate
        traced_s = min(ctx.trace_seconds, ctx.seconds / 2) \
            if ctx.tracer is not None else 0.0
        self.quiet_s = ctx.seconds - traced_s
        t0 = self.load.begin_phase(self.traffic, ctx.seconds, ctx.seed,
                                   float(wl["drain_timeout_s"]))
        for i in range(SLICES + 1):
            time.sleep(max(t0 + self.quiet_s * i / SLICES - time.monotonic(),
                           0.0))
            self._snap(f"slice{i}")
        self.snaps["open"] = self.snaps["slice0"]
        self.snaps["close"] = self.snaps[f"slice{SLICES}"]
        if ctx.tracer is not None:
            ctx.tracer.start()
            self._snap("trace_open")
            with jax.profiler.TraceAnnotation("bench.served_window"):
                time.sleep(max(t0 + ctx.seconds - time.monotonic(), 0.0))
            self._snap("trace_close")
            ctx.tracer.stop()
        self.result = self.load.end_phase()
        self.phases.append(self.result)
        self._say_where_unanswered_are("window", self.result)
        self.t0 = t0
        return t0

    def end_to_end(self) -> dict:
        r = self.result
        answered = ~np.isnan(r["t_reply"])
        lat_ms = (r["t_reply"][answered] - r["t_due"][answered]) * 1e3
        return {"reply_p50_ms": percentile(lat_ms, 50),
                "reply_p95_ms": percentile(lat_ms, 95)}

    def counters(self) -> dict:
        r, s = self.result, self.snaps
        close = self.t0 + self.quiet_s
        out = {"window_s": self.quiet_s,
               "requests": len(r["cmd_id"]),
               "acked_in_window": int((r["t_reply"] <= close).sum()),
               "gen_behind_max_s": float(r["behind_max_s"]),
               "rejects": r["rejects"], "retransmits": r["retransmits"],
               "duplicates": r["duplicates"], "failovers": r["failovers"],
               "warm_unanswered": int(sum(
                   np.isnan(p["t_reply"]).sum() for p in self.phases[:-1]))}
        for k in COUNTERS:
            out[f"leader_{k}"] = s["close"][k] - s["open"][k]
        out["leader_window_s"] = s["close"]["t"] - s["open"]["t"]
        # every fsync of the window, all replicas: count and time
        files = [self.fsyncs.of_file(str(f)) for f in self._store_files()]
        in_w = [(f["t_done"] > self.t0) & (f["t_done"] <= close)
                for f in files]
        out["fsyncs"] = int(sum(m.sum() for m in in_w))
        out["fsync_s"] = float(sum(f["seconds"][m].sum()
                                   for f, m in zip(files, in_w)))
        # the window by slices: a run that reads slow either is slow
        # throughout or carries one episode, and the leader's tick, its
        # fsyncs and the median latency say which layer it was in
        ok = ~np.isnan(r["t_reply"]) & (r["t_due"] < close)
        lat = (r["t_reply"] - r["t_due"])[ok]
        part = ((r["t_due"][ok] - self.t0) * SLICES / self.quiet_s).astype(int)
        edges = [s[f"slice{i}"] for i in range(SLICES + 1)]
        spans = list(zip(edges, edges[1:]))
        lead = files[0]

        def per_dispatch(a, b, what):
            return (b[what] - a[what]) / max(
                b["dispatches"] - a["dispatches"], 1)

        def fsync_ms(a, b):
            took = lead["seconds"][(lead["t_done"] > a["t"])
                                   & (lead["t_done"] <= b["t"])]
            return round(float(took.mean()) * 1e3, 2) if len(took) else None

        out["by_slice"] = {
            "p50_ms": [round(float(np.median(lat[part == i])) * 1e3, 1)
                       if (part == i).any() else None
                       for i in range(SLICES)],
            "leader_tick_ms": [round(per_dispatch(a, b, "t") * 1e3, 2)
                               for a, b in spans],
            "rows_per_dispatch": [round(per_dispatch(a, b, "proposals"), 1)
                                  for a, b in spans],
            "leader_fsync_ms": [fsync_ms(a, b) for a, b in spans]}
        if "trace_open" in s:
            out["traced_leader_dispatches"] = (
                s["trace_close"]["dispatches"] - s["trace_open"]["dispatches"])
        return out

    # -------------------------------------------------------- check

    def check(self):
        ctx = self.ctx
        servers = self.cluster.servers
        t_q = time.monotonic()
        deadline = t_q + float(ctx.workload["quiesce_timeout_s"])
        converged = False
        while not converged and time.monotonic() < deadline:
            time.sleep(0.05)
            snaps = [s.snapshot for s in servers.values()]
            converged = (len({s["frontier"] for s in snaps}) == 1
                         and all(s.get("executed") == s["frontier"]
                                 for s in snaps))
        ctx.log(f"quiesced: {converged} after "
                f"{time.monotonic() - t_q:.1f}s, frontiers "
                f"{[s['frontier'] for s in snaps]}, each replica's leader "
                f"{[s['leader'] for s in snaps]}")
        time.sleep(0.3)  # no append in flight under the reader
        # the disk, read before anything is stopped: a stop would flush
        # what a replica still held back
        paths = self._store_files()
        files = [f.read_bytes() for f in paths]
        fsyncs = [self.fsyncs.of_file(str(f)) for f in paths]
        self.close()  # joins the protocol threads
        tables = [dump_table(*srv.state.kv[:4])
                  for _, srv in sorted(servers.items())]
        requests = {k: np.concatenate([p[k] for p in self.phases])
                    for k in ("cmd_id", "op", "key", "val", "t_sent",
                              "t_reply", "reply_val")}
        # the window's requests are the ones that are owed an answer;
        # the warm-up's are history the replay needs
        requests["in_window"] = np.concatenate(
            [np.full(len(p["cmd_id"]), p is self.result)
             for p in self.phases])
        evidence = {"requests": requests, "files": files, "fsyncs": fsyncs,
                    "tables": tables,
                    "quorum": int(ctx.config["n_replicas"]) // 2 + 1}
        if ctx.control is not None:  # something else in the program's place
            evidence = ctx.control.apply(evidence)
        ctx.log(f"evidence: {len(requests['cmd_id'])} requests, files of "
                f"{[len(x) for x in files]} bytes with "
                f"{[len(f['size']) for f in fsyncs]} fsyncs, "
                f"{[len(t) for t in tables]} table entries")
        numbers = served_check.compare(**evidence)
        r = self.result
        failed = int(np.isnan(r["t_reply"]).sum())
        return numbers, served_check.LIMITS, len(r["cmd_id"]), failed

    def _store_files(self) -> list:
        """``stable-store-replica<id>`` of every replica, by id: the
        documented name of a replica's durable log."""
        return [self.store / f"stable-store-replica{i}"
                for i in range(int(self.ctx.config["n_replicas"]))]

    def close(self) -> None:
        self.fsyncs.remove()
        load, cluster = self.load, self.cluster
        self.load = self.cluster = None  # neither stop is idempotent
        if load is not None:
            load.stop()
        if cluster is not None:
            cluster.stop()
