"""Runner ``served_mencius``: runner ``served``'s composition — a master
and N ``-durable`` replica servers in this process, every replica's
step on the chip, every fsync real, built from the server binary's own
flag parser — started with ``-m``: every replica proposes into its own
slots, and a command is answered when the MERGED frontier passes it.
The window, the end-to-end metrics and the slices are
``runners/served.py``'s, which it subclasses.

What differs, because no replica is "the leader":

* the load is ``lib/ownerload.py``'s: session ``i`` on replica
  ``i mod N`` for the whole run (the upstream client's ``-e``), the
  schedule and everything else ``lib/loadgen.py``'s;
* counters are read from every replica: ``leader_*`` stay replica 0's
  (one owner of N), ``owner_*`` are lists by replica, and
  ``noop_slots`` / ``command_slots`` are the slots replica 0 executed in
  the window by kind;
* ``check`` is ``lib/served_mencius_check.py``'s: the served cells'
  eight numbers over the merged log and ``slots_unaccounted``.

A program that cannot start ``-m`` through this composition, or lacks
the counters and the paxtrace stage the cell's metrics read, ends at
once: a clean non-zero exit before anything is built.
"""

from __future__ import annotations

import dataclasses
import inspect
import time

import numpy as np

from benchmarks.lib import progobs, served_mencius_check
from benchmarks.lib.loadgen import Traffic
from benchmarks.lib.ownerload import OwnerSpreadLoad
from benchmarks.lib.tables import dump_table
from benchmarks.runners import served

#: by replica, over the window: the tick cadence and what the log is
#: made of
OWNER_COUNTERS = ("dispatches", "proposals", "client_proposals",
                  "noop_slots", "command_slots", "executed",
                  # what a size too small shows as: rows the window
                  # refused, rows the admission gate shed
                  "proposals_rejected", "coalesce_admission_rejects")


def missing_in_program() -> str:
    """What this runner needs of the program and does not find ('' when
    everything is there)."""
    from minpaxos_tpu.chaos.campaign import ChaosCluster
    from minpaxos_tpu.cli import server as server_cli
    from minpaxos_tpu.obs import trace

    lacks = []
    if "protocol" not in inspect.signature(ChaosCluster.__init__).parameters:
        lacks.append("chaos/campaign.py ChaosCluster takes no protocol "
                     "(it cannot start -m)")
    if not hasattr(server_cli, "protocol_from_args"):
        lacks.append("cli/server.py has no protocol_from_args")
    if not hasattr(trace, "ST_OWN_COMMIT"):
        lacks.append("obs/trace.py has no own_commit stage (and "
                     "runtime/replica.py no client_proposals / noop_slots "
                     "/ command_slots counters)")
    return "; ".join(lacks)


class Runner(served.Runner):

    def setup(self) -> None:
        lacks = missing_in_program()
        if lacks:
            # ends at once, before anything is built or compiled
            raise SystemExit(
                f"bench: this program cannot run a served_mencius "
                f"configuration: {lacks}; nothing was run")
        from minpaxos_tpu.chaos.campaign import ChaosCluster
        from minpaxos_tpu.cli import server as server_cli

        ctx, cfg, wl = self.ctx, self.ctx.config, self.ctx.workload
        self.store = store = ctx.scratch / "store"
        store.mkdir()
        self.fsyncs.install()
        # exactly what `python -m minpaxos_tpu.cli.server <flags>` would
        # compile and run, its protocol included
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
            boot_timeout_s=served.BOOT_TIMEOUT_S,
            protocol=server_cli.protocol_from_args(args))
        # the cluster's own boot wait is true by convention under
        # mencius (no leader to prepare): this one holds
        deadline = time.monotonic() + served.BOOT_TIMEOUT_S
        while not all(s.stats["ticks"] > 0
                      for s in self.cluster.servers.values()):
            if time.monotonic() > deadline:
                raise TimeoutError("replicas never ticked after boot")
            time.sleep(0.05)
        ctx.log(f"{n} replicas serving, protocol "
                f"{self.cluster.servers[0].protocol}")
        self._warm_table_probe(1 << args.kvpow2)
        self.traffic = Traffic(
            rate_hz=float(wl["rate_hz"]), key_range=int(cfg["key_range"]),
            write_pct=int(wl["write_pct"]), zipf_s=float(wl["zipf_s"]),
            burst_x=float(wl["burst_x"]),
            catchup_hz=served.CATCHUP_KNEE_SHARE
            * float(cfg["sustained_rate_hz"]))
        self.load = OwnerSpreadLoad(self.cluster.maddr, int(wl["sessions"]),
                                    int(wl["workers"]))
        self.load.start()
        # warm traffic at the cell's own rate, mix and placement: every
        # step variant the window will use has run on every owner
        self.load.begin_phase(self.traffic, float(wl["warm_s"]),
                              ctx.seed ^ 0x5EED, 30.0)
        warm = self.load.end_phase()
        self.phases.append(warm)
        ctx.log(f"warm-up: {len(warm['cmd_id'])} requests, "
                f"{int(np.isnan(warm['t_reply']).sum())} unanswered")
        self._say_where_unanswered_are("warm-up", warm)

    # ------------------------------------------------------- window

    def _snap(self, tag: str) -> None:
        super()._snap(tag)  # replica 0's, with the instant
        self.snaps[tag]["owners"] = [
            s.stats for _, s in sorted(self.cluster.servers.items())]

    def counters(self) -> dict:
        out = super().counters()
        opened, closed = (self.snaps[t]["owners"] for t in ("open", "close"))
        for k in OWNER_COUNTERS:
            out[f"owner_{k}"] = [b.get(k, 0) - a.get(k, 0)
                                 for a, b in zip(opened, closed)]
        # rows that found an inbox full, since boot: a size reading
        out["owner_inbox_dropped"] = [
            s.inbox.dropped for _, s in sorted(self.cluster.servers.items())]
        out["noop_slots"] = out["owner_noop_slots"][0]
        out["command_slots"] = out["owner_command_slots"][0]
        out["owner_tick_ms"] = self._owner_tick_ms()
        return out

    def _owner_tick_ms(self) -> dict:
        """By replica, the median milliseconds of each phase of a loaded
        dispatch (``lib/progobs.py`` over one replica's recorder rows at
        a time): for the record of a run, PERF.md's breakdown of the
        three owners' ticks. No manifest metric reads it: PR 26's
        ``tick_*`` entries are listed for the single-leader cells."""
        newest = {e["replica"]: e for e in progobs.collection() or []}
        return {field[:-3]: [
            progobs.tick_median_ms(field, [newest[r]]) if r in newest
            else None for r in sorted(self.cluster.servers)]
            for field in ("wait_us", "drain_us", "enqueue_us",
                          "readback_us", "persist_us", "fsync_us",
                          "dispatch_us", "reply_us")}

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
            # every replica's MERGED frontier, and all of it executed
            converged = (len({s["frontier"] for s in snaps}) == 1
                         and all(s.get("executed") == s["frontier"]
                                 for s in snaps))
        ctx.log(f"quiesced: {converged} after "
                f"{time.monotonic() - t_q:.1f}s, merged frontiers "
                f"{[s['frontier'] for s in snaps]}, executed "
                f"{[s.get('executed') for s in snaps]}")
        time.sleep(0.3)  # no append in flight under the reader
        # the disk, read before anything is stopped: a stop would flush
        # what a replica still held back
        paths = self._store_files()
        files = [f.read_bytes() for f in paths]
        fsyncs = [self.fsyncs.of_file(str(f)) for f in paths]
        self.close()  # joins the protocol threads
        by_id = [srv for _, srv in sorted(servers.items())]
        tables = [dump_table(*srv.state.kv[:4]) for srv in by_id]
        requests = {k: np.concatenate([p[k] for p in self.phases])
                    for k in ("cmd_id", "op", "key", "val", "t_sent",
                              "t_reply", "reply_val")}
        requests["in_window"] = np.concatenate(
            [np.full(len(p["cmd_id"]), p is self.result)
             for p in self.phases])
        evidence = {"requests": requests, "files": files, "fsyncs": fsyncs,
                    "tables": tables,
                    "quorum": int(ctx.config["n_replicas"]) // 2 + 1,
                    "noops_counted": [srv.stats["noop_slots"]
                                      for srv in by_id]}
        if ctx.control is not None:  # something else in the program's place
            evidence = ctx.control.apply(evidence)
        ctx.log(f"evidence: {len(requests['cmd_id'])} requests, files of "
                f"{[len(x) for x in files]} bytes with "
                f"{[len(f['size']) for f in fsyncs]} fsyncs, "
                f"{[len(t) for t in tables]} table entries, no-op slots "
                f"counted {evidence['noops_counted']}")
        numbers = served_mencius_check.compare(**evidence)
        r = self.result
        failed = int(np.isnan(r["t_reply"]).sum())
        return numbers, served_mencius_check.LIMITS, len(r["cmd_id"]), failed
