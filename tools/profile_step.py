"""Per-kernel step profiler: where does a protocol tick's time go?

Times the jitted protocol steps (MinPaxos / Mencius) and the KV
sub-kernels standalone at deployment shapes, on whatever backend JAX
resolves (pin with JAX_PLATFORMS). This is the measurement tool behind
the round-5 step optimization work (VERDICT round 4 items 6-7): it
separates device compute from dispatch overhead and isolates the KV
claim loop's capacity scaling.

Run: JAX_PLATFORMS=cpu python tools/profile_step.py [--window 4096]
Prints one labeled ms/op line per case.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from minpaxos_tpu.models.mencius import init_mencius, mencius_step
from minpaxos_tpu.models.minpaxos import (
    MinPaxosConfig,
    MsgBatch,
    init_replica,
    replica_step,
)
from minpaxos_tpu.ops import kvstore
from minpaxos_tpu.wire.messages import MsgKind, Op


def _time(fn, iters: int = 20) -> float:
    """Median ms over ``iters`` calls (after one warmup)."""
    fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e3)
    ts.sort()
    return ts[len(ts) // 2]


def propose_inbox(cfg: MinPaxosConfig, n_prop: int, to_leader: bool) -> MsgBatch:
    m = cfg.inbox
    cols = {c: np.zeros(m, np.int32) for c in MsgBatch._fields}
    cols["kind"][:n_prop] = int(MsgKind.PROPOSE)
    cols["src"][:n_prop] = -1
    cols["op"][:n_prop] = int(Op.PUT)
    cols["key_lo"][:n_prop] = np.arange(n_prop, dtype=np.int32)
    cols["val_lo"][:n_prop] = np.arange(n_prop, dtype=np.int32) + 7
    cols["cmd_id"][:n_prop] = np.arange(n_prop, dtype=np.int32)
    cols["client_id"][:n_prop] = 5
    return MsgBatch(**{k: jnp.asarray(v) for k, v in cols.items()})


def bench_step(name, step, cfg, state, inbox, iters=20) -> None:
    # thread the state through (the steps donate their state argument,
    # so the input buffers are consumed by each call); copy first so
    # init-time aliased zero buffers aren't donated twice
    holder = [jax.tree.map(jnp.copy, state)]

    def once():
        st2, out, ex = step(cfg, holder[0], inbox)
        jax.block_until_ready(st2)
        holder[0] = st2

    ms = _time(once, iters)
    print(f"{name:44s} {ms:8.2f} ms/step")


def bench_kv(cfg_label: str, cap_pow2: int, b: int, iters=20) -> None:
    kv = kvstore.kv_init(cap_pow2)
    rng = np.random.default_rng(0)
    op = jnp.asarray(np.full(b, int(Op.PUT), np.int32))
    k_hi = jnp.asarray(np.zeros(b, np.int32))
    k_lo = jnp.asarray(rng.integers(0, 100000, b).astype(np.int32))
    v = jnp.asarray(np.ones((b, kvstore.VAL_LANES), np.int32))
    valid = jnp.asarray(np.ones(b, bool))

    apply_j = jax.jit(kvstore.kv_apply_batch_lanes)

    def once():
        kv2, out, found = apply_j(kv, op, k_hi, k_lo, v, valid)
        jax.block_until_ready(kv2)

    ms = _time(once, iters)
    print(f"kv_apply_batch  C=2^{cap_pow2:<2d} B={b:<5d} {cfg_label:12s}"
          f" {ms:8.2f} ms/call")


def decompose(window: int = 512, iters: int = 40) -> None:
    """Split the per-tick cost into dispatch floor vs marginal compute
    at the serial-latency shape (bench_tcp.py SERIAL_SHAPE) — the
    round-6 question behind VERDICT item 5: how much of the 0.3-0.9 ms
    tick is the host->device round trip that fused substeps amortize?

    Method: time the packed k-substep step for k=1..4; the slope
    (t_k - t_1)/(k-1) is one substep's pure compute (substeps share
    one dispatch), so t_1 minus the slope is the dispatch floor. Also
    A/Bs the narrow resident view: a server-default 16384-slot window
    stepped full-width vs through a 512-slot view.
    """
    from minpaxos_tpu.models.minpaxos import replica_step_impl
    from minpaxos_tpu.runtime.replica import _packed_step

    cfg = MinPaxosConfig(n_replicas=3, window=window, inbox=256,
                         exec_batch=64, kv_pow2=12, catchup_rows=256,
                         recovery_rows=256, gossip_ticks=4)
    prop = propose_inbox(cfg, 1, to_leader=True)  # a serial op's tick

    def timed(k: int) -> float:
        holder = [jax.tree.map(jnp.copy, init_replica(cfg, 0))]

        def once():
            st, om, em, sc = _packed_step(cfg, holder[0], prop,
                                          replica_step_impl, k)
            jax.block_until_ready(sc)
            holder[0] = st

        return _time(once, iters)

    ts = {k: timed(k) for k in (1, 2, 3, 4)}
    slope = (ts[4] - ts[1]) / 3
    floor = max(ts[1] - slope, 0.0)
    print(f"\n-- dispatch-vs-compute decomposition, W={window} "
          f"(1-prop tick, serial shape) --")
    for k, t in ts.items():
        print(f"  k={k} substeps/dispatch {t:8.3f} ms "
              f"({t / k:.3f} ms/substep amortized)")
    print(f"  marginal substep compute {slope:8.3f} ms")
    print(f"  dispatch floor (t1 - marginal) {floor:8.3f} ms "
          f"({100 * floor / ts[1]:.0f}% of a k=1 tick)")

    # narrow view A/B: server-default window, low occupancy
    big = MinPaxosConfig(n_replicas=3, window=1 << 14, inbox=256,
                         exec_batch=64, kv_pow2=12, catchup_rows=256,
                         recovery_rows=256, gossip_ticks=4)
    bprop = propose_inbox(big, 1, to_leader=True)
    for narrow in (0, 512):
        holder = [jax.tree.map(jnp.copy, init_replica(big, 0))]

        def once():
            st, om, em, sc = _packed_step(big, holder[0], bprop,
                                          replica_step_impl, 1, narrow,
                                          jnp.int32(0))
            jax.block_until_ready(sc)
            holder[0] = st

        label = f"narrow view W=16384->{narrow}" if narrow else \
            "full step  W=16384"
        print(f"  {label:28s} {_time(once, iters):8.3f} ms/tick")


def pipeline_decompose(window: int = 512, iters: int = 40) -> None:
    """The pipelined tick loop's decomposition (ISSUE: runtime/
    replica.py now ENQUEUES the step, runs the previous tick's host
    phases while the device computes, then reads back): measure, at
    the serial shape, the walls the pipeline is made of —

    * ``enqueue``: host wall to launch the async dispatch,
    * ``compute``: device wall (enqueue + block, no host work between),
    * ``readback``: host blocked on the transfers after hiding host
      work under the compute,
    * ``host``: a calibrated stand-in for persist+dispatch+reply
      (numpy masking/grouping over outbox-shaped arrays, measured
      standalone),

    and report overlap efficiency: of the host wall, how much
    disappeared when run between enqueue and readback —
    (serial_total - pipelined_total) / host_wall. 1.0 = fully hidden;
    0 = the backend dispatches synchronously and the pipeline only
    reorders."""
    from minpaxos_tpu.models.minpaxos import replica_step_impl
    from minpaxos_tpu.runtime.replica import _packed_step

    cfg = MinPaxosConfig(n_replicas=3, window=window, inbox=256,
                         exec_batch=64, kv_pow2=12, catchup_rows=256,
                         recovery_rows=256, gossip_ticks=4)
    prop = propose_inbox(cfg, 1, to_leader=True)

    # calibrated host-phase stand-in: outbox-shaped numpy work (mask,
    # unique, group), repeated to land near a loaded tick's real
    # persist+dispatch+reply wall (~0.3-0.5 ms on this class of host —
    # the paxmon flight recorder's measured phase sum at bench load)
    out_kind = np.zeros(cfg.inbox, np.int32)
    out_kind[:128] = 3
    out_inst = np.arange(cfg.inbox, dtype=np.int32)

    def host_phases():
        for _ in range(8):
            live = out_kind != 0
            for q in range(cfg.n_replicas):
                m = live & (out_inst % cfg.n_replicas == q)
                if m.any():
                    ks = np.unique(out_kind[m])
                    for k_ in ks:
                        _ = out_inst[m][out_kind[m] == k_].copy()

    holder = [jax.tree.map(jnp.copy, init_replica(cfg, 0))]

    def enqueue():
        st, om, em, sc = _packed_step(cfg, holder[0], prop,
                                      replica_step_impl, 1)
        holder[0] = st
        return sc

    sc = enqueue()
    jax.block_until_ready(sc)  # warm compile

    def timed_leg(with_host: bool):
        """(enqueue_ms, mid_ms, readback_ms) — mid is the host work
        (or nothing) run between enqueue and the blocking readback."""
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            sc = enqueue()
            t1 = time.perf_counter()
            if with_host:
                host_phases()
            t2 = time.perf_counter()
            np.asarray(sc)
            t3 = time.perf_counter()
            ts.append(((t1 - t0) * 1e3, (t2 - t1) * 1e3,
                       (t3 - t2) * 1e3))
        ts.sort(key=lambda t: sum(t))
        return ts[len(ts) // 2]

    host_ms = _time(host_phases, iters)
    enq0, _, rb0 = timed_leg(False)  # device wall, no host work
    enq1, mid1, rb1 = timed_leg(True)
    compute_ms = enq0 + rb0
    serial_ms = compute_ms + host_ms
    pipelined_ms = enq1 + mid1 + rb1
    # of the host wall, how much did NOT extend the tick: host work
    # that fits the (compute - enqueue) overlap window is free
    hidden_ms = host_ms - max(0.0, pipelined_ms - compute_ms)
    eff = (hidden_ms / host_ms) if host_ms > 0 else 0.0
    print(f"\n-- pipeline decomposition, W={window} "
          f"(1-prop tick, serial shape) --")
    print(f"  enqueue (async dispatch launch) {enq1:8.3f} ms")
    print(f"  device compute (enqueue+block)  {compute_ms:8.3f} ms")
    print(f"  overlap window (compute-enqueue){compute_ms - enq0:8.3f} ms")
    print(f"  host phases (standalone)        {host_ms:8.3f} ms")
    print(f"  readback after hidden host work {rb1:8.3f} ms")
    print(f"  serial total (compute + host)   {serial_ms:8.3f} ms")
    print(f"  pipelined total                 {pipelined_ms:8.3f} ms")
    print(f"  overlap efficiency              {eff:8.2f} "
          f"(1.0 = host wall fully hidden under device compute)")


def resident_decompose(g: int = 2, w: int = 1024, p: int = 256,
                       k: int = 8, iters: int = 10) -> None:
    """Per-dispatch decomposition of the device-resident measured loop
    (ISSUE 8 satellite — mirrors ``--pipeline`` for the pipelined tick
    loop): split one resident dispatch into

    * ``enqueue``: host wall to launch the k-round fused dispatch
      (jit call overhead + async submit; nothing transferred in),
    * ``device compute``: enqueue + block, no readback,
    * ``scalar readback``: the two-scalar cursor read after compute
      (the ONLY sanctioned host sync in the steady state),

    and A/B it against the legacy host-in-the-loop dispatch
    (``run_fused``: same k rounds, then the [k, G] cursor-history
    transfer + blocking conversion). A regression in the resident path
    shows up as the readback line growing past scalar size, or the
    enqueue line growing a recompile."""
    import jax.numpy as jnp

    from minpaxos_tpu.parallel.sharded import (
        ShardedCluster,
        sharded_run_resident,
    )

    cu = max(32, p // 4)
    cfg = MinPaxosConfig(n_replicas=5, window=w, inbox=p + 2 * cu + 128,
                         exec_batch=p, kv_pow2=10, catchup_rows=cu,
                         recovery_rows=64)
    sc = ShardedCluster(cfg, g, ext_rows=p, key_space=1 << 8)
    sc.elect(0)
    sc.begin_resident()
    sc.run_resident(k, p)  # warm/compile the resident dispatch

    def dispatch_async():
        out = sharded_run_resident(
            sc.cfg, sc.n_shards, sc.ext_rows, k, sc.ss, sc._inject_round,
            sc._lat_hist, sc._telemetry, sc._tiers, jnp.int32(p),
            jnp.int32(sc.leader), jnp.int32(sc._seed), jnp.int32(sc.seed),
            sc._step_impl, sc.key_space, 1, jnp.int32(sc._tel_base))
        (sc.ss, sc._inject_round, sc._lat_hist, sc._telemetry,
         sc._tiers) = out[:5]
        sc._seed += k
        return out[5], out[6]

    legs = []
    for _ in range(iters):
        t0 = time.perf_counter()
        committed, in_flight = dispatch_async()
        t1 = time.perf_counter()
        jax.block_until_ready(committed)
        t2 = time.perf_counter()
        c, f = int(committed), int(in_flight)  # the scalar readback
        t3 = time.perf_counter()
        legs.append(((t1 - t0) * 1e3, (t2 - t1) * 1e3, (t3 - t2) * 1e3))
    legs.sort(key=lambda t: sum(t))
    enq, comp, rb = legs[len(legs) // 2]

    # legacy comparison: same rounds, host-in-the-loop history readback
    sc2 = ShardedCluster(cfg, g, ext_rows=p, key_space=1 << 8)
    sc2.elect(0)
    sc2.run_fused(k, p)  # warm

    def legacy():
        u, c = sc2.run_fused(k, p)  # np.asarray blocks inside

    legacy_ms = _time(legacy, iters)
    total = enq + comp + rb
    print(f"\n-- resident-loop decomposition, g={g} W={w} p={p} k={k} --")
    print(f"  enqueue (jit call + async submit) {enq:8.3f} ms/dispatch")
    print(f"  device compute ({k} fused rounds)  {comp:8.3f} ms/dispatch")
    print(f"  scalar readback (2 cursors)       {rb:8.3f} ms/dispatch")
    print(f"  resident dispatch total           {total:8.3f} ms "
          f"({total / k:.3f} ms/round)")
    print(f"  legacy run_fused ([k,G] readback) {legacy_ms:8.3f} ms "
          f"({legacy_ms / k:.3f} ms/round)")
    print(f"  host-loop tax amortized away      {legacy_ms - total:8.3f} ms/dispatch")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--inbox", type=int, default=2048)
    ap.add_argument("--props", type=int, default=512)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--no-decompose", action="store_true",
                    help="skip the dispatch-vs-compute / narrow-view "
                         "section (it compiles extra W=16384 and fused "
                         "variants — minutes on slow hosts)")
    ap.add_argument("--pipeline", action="store_true",
                    help="run ONLY the pipeline decomposition "
                         "(enqueue/compute/readback/host walls + "
                         "overlap efficiency) and exit — the per-tick "
                         "evidence behind the pipelined tick loop")
    ap.add_argument("--resident", action="store_true",
                    help="run ONLY the resident-loop decomposition "
                         "(enqueue/device-compute/scalar-readback per "
                         "dispatch + legacy host-loop A/B) and exit — "
                         "the per-dispatch evidence behind the "
                         "device-resident measured loop")
    args = ap.parse_args()

    if args.pipeline:
        print(f"backend: {jax.devices()[0].platform}", file=sys.stderr)
        pipeline_decompose(iters=args.iters)
        return
    if args.resident:
        print(f"backend: {jax.devices()[0].platform}", file=sys.stderr)
        resident_decompose(iters=args.iters)
        return

    print(f"backend: {jax.devices()[0].platform}", file=sys.stderr)

    for kvp in (16, 20):
        cfg = MinPaxosConfig(n_replicas=3, window=args.window,
                             inbox=args.inbox, exec_batch=args.window,
                             kv_pow2=kvp)
        st_m = init_mencius(cfg, 0)
        st_p = init_replica(cfg, 0)
        empty = MsgBatch.empty(cfg.inbox)
        prop = propose_inbox(cfg, args.props, to_leader=True)
        bench_step(f"mencius idle   W={args.window} kv=2^{kvp}",
                   mencius_step, cfg, st_m, empty, args.iters)
        bench_step(f"mencius {args.props}prop W={args.window} kv=2^{kvp}",
                   mencius_step, cfg, st_m, prop, args.iters)
        bench_step(f"minpaxos idle  W={args.window} kv=2^{kvp}",
                   replica_step, cfg, st_p, empty, args.iters)
        bench_step(f"minpaxos {args.props}prop W={args.window} kv=2^{kvp}",
                   replica_step, cfg, st_p, prop, args.iters)

    for cap in (16, 20):
        for b in (512, 2048):
            bench_kv("", cap, b, args.iters)

    if not args.no_decompose:
        decompose(iters=args.iters)
        pipeline_decompose(iters=args.iters)


if __name__ == "__main__":
    main()
