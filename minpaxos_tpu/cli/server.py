"""Replica server binary — reference src/server/server.go flags (:19-34).

The reference's protocol selector flags are honored: ``-min`` (MinPaxos,
the default and only active path in the reference too — server.go:58-79
has every other protocol commented out). ``-platform`` picks the JAX
backend and is the ONLY selector: an absent platform fails the boot,
loudly, before the replica registers. One process owns a chip, so N
server processes on one host run ``-platform cpu`` (the default);
replicas whose steps run on the chip live in ONE process
(chip_smoke.py phase B composes them from these same flags), as do pod
mode (models/cluster.py) and the sharded mesh (parallel/).
"""

from __future__ import annotations

import argparse
import cProfile
import signal
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("minpaxos-server")
    p.add_argument("-port", type=int, default=7070, help="data port")
    p.add_argument("-addr", default="127.0.0.1", help="listen address")
    p.add_argument("-maddr", default="127.0.0.1", help="master address")
    p.add_argument("-mport", type=int, default=7087, help="master port")
    p.add_argument("-min", action="store_true", default=True,
                   help="use MinPaxos (global-ballot Multi-Paxos)")
    p.add_argument("-classic", action="store_true",
                   help="use classic per-instance Multi-Paxos (explicit "
                        "Commit/CommitShort, per-instance ballots — "
                        "models/paxos.py; overrides -min)")
    p.add_argument("-m", dest="mencius", action="store_true",
                   help="use Mencius rotating-ownership consensus "
                        "(models/mencius.py; the reference's -m flag, "
                        "commented out in its server.go:58-79, runs "
                        "here; overrides -min/-classic)")
    p.add_argument("-exec", dest="exec_", action="store_true", default=True,
                   help="execute committed commands (accepted for "
                        "reference flag compatibility; always on — "
                        "execution drives window reclamation)")
    p.add_argument("-dreply", action="store_true", default=True,
                   help="reply after execution with the value")
    p.add_argument("-durable", action="store_true",
                   help="fsync accepted slots to the stable store")
    p.add_argument("-thrifty", action="store_true",
                   help="send accepts to a bare quorum only")
    p.add_argument("-beacon", action="store_true",
                   help="RTT beacons; thrifty prefers fastest peers")
    p.add_argument("-kvpow2", type=int, default=16,
                   help="KV table capacity = 2^kvpow2 slots; size above "
                        "the workload's distinct-key count (saturation "
                        "fail-stops the replica), but not higher than "
                        "needed — per-tick KV cost scales with capacity")
    p.add_argument("-window", type=int, default=1 << 14,
                   help="resident log window slots")
    p.add_argument("-inbox", type=int, default=4096,
                   help="message rows per protocol tick")
    p.add_argument("-execbatch", type=int, default=0,
                   help="max slots executed per tick (0 = inbox size);"
                        " smaller cuts fixed per-tick exec-pipeline"
                        " cost, at the price of draining large commit"
                        " backlogs over more ticks")
    p.add_argument("-noopdelay", type=int, default=50,
                   help="stalled protocol ticks before recovery kicks "
                        "in (Mencius takeover sweep, MinPaxos frontier "
                        "rescan / gap no-op fill). A busy TCP replica "
                        "ticks every ~2ms, so the pod-mode default (8) "
                        "means ~16ms of peer silence triggers takeover "
                        "churn — on a loaded host peers are routinely "
                        "descheduled longer than that, and the resulting "
                        "ballot-bump/re-drive storms collapsed the rr "
                        "Mencius bench. 50 ticks is ~0.1s busy / ~2.5s "
                        "idle (the reference waits ~5s before "
                        "forceCommit, mencius.go:244-257); the routine "
                        "loss rescuer is the in-ballot accept retry "
                        "(models/mencius.py 9c), not takeover")
    p.add_argument("-gossipticks", type=int, default=4,
                   help="frontier-gossip cadence in ticks (1 ="
                        " immediate); >1 suppresses the per-commit"
                        " wakeup cascade on small hosts at the cost of"
                        " idle followers executing a few ticks late")
    p.add_argument("-fuseticks", type=int, default=3,
                   help="fused protocol substeps per device dispatch"
                        " when the batch will need follow-up ticks"
                        " (exec backlog / lagging catch-up cursors);"
                        " 1 disables fusion")
    p.add_argument("-noidlefast", action="store_true",
                   help="disable the idle fast path (a quiet replica"
                        " then pays a full device dispatch per idle"
                        " poll, the pre-round-6 behavior)")
    p.add_argument("-idlemaxskip", type=float, default=0.25,
                   help="idle fast path safety net: force one real"
                        " device tick at least this often (seconds)")
    p.add_argument("-nopipeline", action="store_true",
                   help="disable the depth-2 pipelined tick loop"
                        " (host persist/dispatch/reply then run"
                        " strictly after each readback instead of"
                        " overlapping the next dispatch's device"
                        " compute) — for A/Bs")
    p.add_argument("-nocoalesce", action="store_true",
                   help="disable the event-driven ingress coalescer"
                        " (client rows then land on a plain polled"
                        " queue and a lone command pays the poll"
                        " interval in <commit>) — for A/Bs")
    p.add_argument("-coalesce-wait-us", type=int, default=200,
                   help="coalescer max-wait: how long the tick loop"
                        " lingers for more client rows once the first"
                        " row of a batch arrives (microseconds; 0 ="
                        " dispatch immediately)")
    p.add_argument("-coalesce-rows", type=int, default=0,
                   help="coalescer max-rows: dispatch as soon as this"
                        " many client rows are pending (0 = half the"
                        " device inbox)")
    p.add_argument("-nooverlapexec", action="store_true",
                   help="disable overlapped exec (committed slots then"
                        " wait a full extra tick before executing —"
                        " the entire <exec_wait> stage) — for A/Bs")
    p.add_argument("-narrow", type=int, default=0,
                   help="small-window specialized step: run"
                        " low-occupancy ticks through a compiled-once"
                        " resident view of this many slots (0 = off;"
                        " try 512 on servers sized -window >= 4096)")
    p.add_argument("-keyhint", type=int, default=0,
                   help="expected distinct keys in the workload; the"
                        " server logs projected KV load vs -kvpow2"
                        " capacity at startup (saturation fail-stops)")
    p.add_argument("-norecorder", action="store_true",
                   help="disable the paxmon flight recorder (the"
                        " per-tick ring served by the control socket's"
                        " TRACE verb; see OBSERVABILITY.md) — for"
                        " overhead A/Bs; the metrics registry stays on")
    p.add_argument("-notrace", action="store_true",
                   help="disable paxtrace sampled per-command tracing"
                        " (the span rings served by the control"
                        " socket's TRACESPANS verb; OBSERVABILITY.md)"
                        " — for overhead A/Bs; disabled tracing is"
                        " byte-transparent on the wire")
    p.add_argument("-tracepow2", type=int, default=4,
                   help="paxtrace sampling exponent: 1 command in"
                        " 2^k is traced (0 = every command — the"
                        " serial-latency bench setting)")
    p.add_argument("-tracering", type=int, default=4096,
                   help="paxtrace span-ring capacity per writer"
                        " thread (5 int64 fields per span)")
    p.add_argument("-recring", type=int, default=4096,
                   help="flight-recorder ring capacity in ticks"
                        " (12 int64 fields per row: 4096 ≈ 384 KiB)")
    p.add_argument("-nowatch", action="store_true",
                   help="disable the paxwatch event journal (the"
                        " cluster-event rings served by the control"
                        " socket's EVENTS verb; OBSERVABILITY.md) —"
                        " elections, failovers, chaos installs and"
                        " alarms then stay stdout-only")
    p.add_argument("-watchring", type=int, default=1024,
                   help="paxwatch event-ring capacity per writer"
                        " thread (8 int64 fields per event)")
    p.add_argument("-q1", type=int, default=0,
                   help="flexible phase-1 (prepare/election) quorum"
                        " size; 0 = simple majority. Safety needs"
                        " q1 + q2 > N — the server refuses a"
                        " non-intersecting pair at boot with the"
                        " refutation witness (verify/quorum.py)")
    p.add_argument("-q2", type=int, default=0,
                   help="flexible phase-2 (accept/commit) quorum size;"
                        " 0 = simple majority. Smaller q2 = fewer acks"
                        " per commit (Flexible Paxos), paid for at"
                        " leader change by a larger -q1")
    p.add_argument("-snap-every", dest="snap_every", type=int,
                   default=8 << 20,
                   help="snapshot + truncate once the on-disk stable"
                        " store grows this many bytes past the last"
                        " snapshot (0 disables the size trigger); two"
                        " snapshots are retained so a corrupt newest"
                        " one falls back to the older + longer replay")
    p.add_argument("-snap-interval", dest="snap_interval", type=float,
                   default=0.0,
                   help="also snapshot every this many seconds while"
                        " new commands executed (0 = size trigger"
                        " only)")
    p.add_argument("-nosnap", action="store_true",
                   help="disable snapshots + log truncation entirely"
                        " (the stable store then grows unboundedly —"
                        " the pre-snapshot behavior) — for A/Bs")
    p.add_argument("-storedir", default=".",
                   help="stable store directory")
    p.add_argument("-platform", default="cpu",
                   help="jax platform for the replica step (cpu/tpu)")
    p.add_argument("-cpuprofile", default="",
                   help="write a profile dump on SIGINT (pprof-style)")
    return p


def config_from_args(args, n_replicas: int):
    """The MinPaxosConfig these flags compile for an N-replica
    deployment (quorum pair certified before anything serves)."""
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig

    # kv_pow2 default 16 (65536 slots) comfortably dominates the
    # client's default -sr key range (30000) — the runtime FAIL-STOPS
    # on table saturation rather than silently dropping acknowledged
    # writes (the reference's Go map just grows, state.go:33-36), so
    # capacity and key space must be sized together: the bucketized
    # two-choice table (ops/kvstore.py) keeps per-tick cost O(batch),
    # but the table's residual per-step traffic still grows with
    # capacity — raise -kvpow2 deliberately, with the workload in
    # mind (keep load under ~0.5 for comfortable two-choice placement)
    cfg = MinPaxosConfig(
        n_replicas=n_replicas, window=args.window, inbox=args.inbox,
        exec_batch=args.execbatch or args.inbox, kv_pow2=args.kvpow2,
        catchup_rows=256, recovery_rows=256,
        gossip_ticks=args.gossipticks, noop_delay=args.noopdelay,
        explicit_commit=args.classic and not args.mencius,
        q1=args.q1, q2=args.q2)
    # refuse a split-brain-capable (q1, q2) BEFORE serving traffic;
    # the raised witness is the pair of disjoint quorums
    from minpaxos_tpu.verify.quorum import validate_config_quorums

    validate_config_quorums(cfg)
    return cfg


def protocol_from_args(args) -> str:
    """The protocol these flags name (``ReplicaServer``'s ``protocol``):
    ``-m`` wins over ``-classic``, ``-min`` is the default."""
    return ("mencius" if args.mencius
            else "classic" if args.classic else "minpaxos")


def flags_from_args(args, profile=None):
    """The RuntimeFlags these flags select — long-lived deployments
    precompile their step variants (warm_variants)."""
    from minpaxos_tpu.runtime.replica import RuntimeFlags

    return RuntimeFlags(dreply=args.dreply,
                        durable=args.durable, thrifty=args.thrifty,
                        beacon=args.beacon, store_dir=args.storedir,
                        fuse_ticks=args.fuseticks,
                        idle_fastpath=not args.noidlefast,
                        idle_skip_max_s=args.idlemaxskip,
                        narrow_window=args.narrow,
                        pipeline=not args.nopipeline,
                        coalesce=not args.nocoalesce,
                        coalesce_wait_us=args.coalesce_wait_us,
                        coalesce_rows=args.coalesce_rows,
                        overlap_exec=not args.nooverlapexec,
                        key_hint=args.keyhint,
                        warm_variants=True,
                        recorder=not args.norecorder,
                        recorder_ring=args.recring,
                        trace=not args.notrace,
                        trace_pow2=args.tracepow2,
                        trace_ring=args.tracering,
                        watch=not args.nowatch,
                        watch_ring=args.watchring,
                        snapshots=not args.nosnap,
                        snap_every_bytes=args.snap_every,
                        snap_interval_s=args.snap_interval,
                        profile=profile)


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)

    # opportunistic native-layer build (C++ frame scan + cycle clock);
    # everything falls back to pure Python when g++ is absent
    from minpaxos_tpu.native.build import try_build

    try_build()

    import jax

    jax.config.update("jax_platforms", args.platform)
    # touch the backend NOW: an absent platform must fail the boot
    # here, before this process registers as a replica
    dev = jax.devices()[0]
    print(f"server: jax platform {dev.platform} ({dev.device_kind})",
          flush=True)
    # shared persistent compile cache: without it every server process
    # re-jits identical kernels at boot (~10-40 s each, and concurrent
    # first boots starve each other on small hosts — utils/backend.py)
    from minpaxos_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()

    from minpaxos_tpu.runtime.master import get_replica_list, register_with_master
    from minpaxos_tpu.runtime.replica import ReplicaServer

    maddr = (args.maddr, args.mport)
    my_id = register_with_master(maddr, args.addr, args.port)
    nodes = get_replica_list(maddr)
    # every dlog line from this process now carries its replica id —
    # N servers interleaving one terminal's stderr stay attributable
    from minpaxos_tpu.utils.dlog import set_dlog_id

    set_dlog_id(f"r{my_id}")
    print(f"server: registered as replica {my_id} of {len(nodes)}",
          flush=True)

    cfg = config_from_args(args, len(nodes))
    prof = cProfile.Profile() if args.cpuprofile else None
    server = ReplicaServer(my_id, [tuple(n) for n in nodes], cfg,
                           flags_from_args(args, profile=prof),
                           protocol=protocol_from_args(args))

    server.start()
    print(f"server: replica {my_id} serving on {args.addr}:{args.port}",
          flush=True)

    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    while not stop:
        time.sleep(0.2)
    joined = server.stop()  # joins the protocol thread
    if prof is not None:
        if joined:  # else the profiler is still live on that thread
            prof.dump_stats(args.cpuprofile)
            print(f"server: profile written to {args.cpuprofile}",
                  flush=True)
        else:
            print("server: protocol thread did not join; profile NOT "
                  "written", flush=True)
    sys.exit(0)


if __name__ == "__main__":
    main()
