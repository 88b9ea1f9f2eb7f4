#!/usr/bin/env python
"""profile_substeps — per-substep cost attribution for the consensus
kernels (paxray, ISSUE 9 piece 2).

The CPU ablation behind ROADMAP item 1 says per-round cost is ~50 µs
per INBOX ROW (accept/ack/route handling), and that now bounds
throughput everywhere — but that number was one aggregate. This tool
compiles and times the protocol's substep kernels IN ISOLATION at real
bench shapes, sweeps the inbox capacity (the kernels are branch-free
and masked, so cost scales with CAPACITY rows, not live rows — exactly
the ~50 µs/row the ablation measured), fits the per-row cost of each
substep by least squares, and emits a JSON cost table — the direct
input to the kernel work ROADMAP item 1 calls for, and the measured
table PERF.md records.

Substeps isolated (one inbox kind each, through the real jitted
kernels — NOT re-implementations):

* ``propose`` — leader slot assignment + ACCEPT emission
  (replica_step with a PROPOSE-only inbox);
* ``accept``  — follower ballot-compare/scatter + run-length ack
  compression (ACCEPT-only inbox);
* ``ack``     — leader vote counting + range coverage + commit scan
  (ACCEPT_REPLY-only inbox against an in-flight log);
* ``empty``   — the same kernel on an all-padding inbox: the fixed
  per-round floor (commit scan, exec gate, window slide) every round
  pays regardless of traffic;
* ``route``   — the ORIGINAL dense routing fabric (models/cluster.
  _route, kept behind ``route_fabric="dense"``): pool all outboxes,
  cumsum-scatter each replica's next inbox — measured so the PR-9 fit
  stays comparable across the PR-11 rewrite;
* ``route_v2`` — the one-pass segmented fabric (_route_segmented /
  ops/segscatter.py) the cluster actually runs: one segment-prefix-sum
  + rank-select winner (ops/rankselect.py), no per-destination scatter;
* ``apply``   — the KV claim/apply path (ops/kvstore.kv_apply_batch:
  lexsort, segmented scans, two-choice claim rounds) per exec row.

Isolation discipline: every case is jitted WITHOUT donation and
re-invoked on the SAME input state, so each call does identical work
and the protocol cannot drift mid-measurement (a donated propose loop
would fill the window and silently switch to timing the rejection
path). One compile covers propose/accept/ack/empty at each capacity —
they share the replica_step jaxpr.

    JAX_PLATFORMS=cpu python tools/profile_substeps.py
    python tools/profile_substeps.py --rows 128 256 512 --json COSTS.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from minpaxos_tpu.models.cluster import _route, _route_segmented  # noqa: E402
from minpaxos_tpu.models.minpaxos import (  # noqa: E402
    MinPaxosConfig,
    MsgBatch,
    become_leader,
    init_replica,
    replica_step_impl,
)
from minpaxos_tpu.ops import kvstore  # noqa: E402
from minpaxos_tpu.wire.messages import MsgKind, Op  # noqa: E402


def _time_ms(fn, iters: int) -> float:
    """MIN wall ms over ``iters`` calls (one warmup/compile call).
    The min, not the median: these are fixed-shape deterministic
    kernels, so the minimum is the interference-free cost — on a
    shared host the median carries scheduler noise that wrecks the
    linear fit the per-row numbers come from."""
    fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e3)
    return min(ts)


def _mk_inbox(m: int, n: int, **cols) -> MsgBatch:
    """[m]-capacity inbox with the first ``n`` rows live, remaining
    rows padding (kind 0). ``cols`` give per-field fill (scalar or
    [n] array)."""
    out = {f: np.zeros(m, np.int32) for f in MsgBatch._fields}
    for f, v in cols.items():
        out[f][:n] = v
    return MsgBatch(**{f: jnp.asarray(v) for f, v in out.items()})


def _prepared_leader(cfg: MinPaxosConfig, step):
    """A replica-0 state holding a prepare majority at a fresh ballot
    (the steady-state serving leader every hot-path substep runs
    under), built through the real kernels."""
    st, _ = become_leader(cfg, init_replica(cfg, 0))
    b = int(st.default_ballot)
    m = cfg.inbox
    replies = _mk_inbox(
        m, 2, kind=int(MsgKind.PREPARE_REPLY),
        src=np.array([1, 2], np.int32), ballot=b, op=1,
        last_committed=-1)
    st, _, _ = step(cfg, st, replies)
    assert bool(st.prepared), "leader failed to prepare"
    return st, b


def _adopted_follower(cfg: MinPaxosConfig, step, ballot: int):
    """A replica-1 state that has adopted the leader's ballot (the
    state every follower substep runs against)."""
    st = init_replica(cfg, 1)
    prep = _mk_inbox(cfg.inbox, 1, kind=int(MsgKind.PREPARE), src=0,
                     ballot=ballot, last_committed=-1)
    st, _, _ = step(cfg, st, prep)
    assert int(st.default_ballot) == ballot
    return st


def profile_capacity(cfg: MinPaxosConfig, live: int, iters: int) -> dict:
    """ms/step of each replica_step substep at this inbox capacity
    (``cfg.inbox``), with ``live`` live rows each."""
    # no donation: the same input state is re-stepped every iteration
    step = jax.jit(replica_step_impl, static_argnums=0)
    leader, b = _prepared_leader(cfg, step)
    follower = _adopted_follower(cfg, step, b)
    n, m = live, cfg.inbox
    rows = np.arange(n, dtype=np.int32)

    propose = _mk_inbox(m, n, kind=int(MsgKind.PROPOSE), src=-1,
                        op=int(Op.PUT), key_lo=rows, val_lo=rows + 7,
                        cmd_id=rows, client_id=5)
    # leader with n slots in flight (so acks have something to cover);
    # votes stay below majority (self + one peer of five), so the
    # re-stepped state would not commit even if it were kept
    leader_inflight, _, _ = step(cfg, leader, propose)
    accept = _mk_inbox(m, n, kind=int(MsgKind.ACCEPT), src=0, ballot=b,
                       inst=rows, op=int(Op.PUT), key_lo=rows,
                       val_lo=rows + 7, cmd_id=rows, last_committed=-1)
    ack = _mk_inbox(m, n, kind=int(MsgKind.ACCEPT_REPLY), src=1, ballot=b,
                    inst=rows, op=1, cmd_id=1, last_committed=-1)
    empty = _mk_inbox(m, 0)

    def run(state, inbox):
        return lambda: jax.block_until_ready(step(cfg, state, inbox))

    out = {
        "propose": _time_ms(run(leader, propose), iters),
        "accept": _time_ms(run(follower, accept), iters),
        "ack": _time_ms(run(leader_inflight, ack), iters),
        "empty": _time_ms(run(leader_inflight, empty), iters),
    }

    # routing fabric: [R, M] outboxes, n live broadcast rows each
    r = cfg.n_replicas
    omsgs = MsgBatch(**{f: jnp.asarray(np.tile(getattr(accept, f), (r, 1)))
                        for f in MsgBatch._fields})
    dst = jnp.full((r, m), -1, jnp.int32)
    alive = jnp.ones(r, dtype=bool)

    # both fabrics at the same inputs: "route" (dense, the PR-9 fit's
    # subject) stays comparable across the rewrite, "route_v2" is the
    # segmented fabric the cluster actually runs (PR 11)
    route = jax.jit(lambda msgs, d, a: _route(cfg, msgs, d, a, m))
    out["route"] = _time_ms(
        lambda: jax.block_until_ready(route(omsgs, dst, alive)), iters)
    route2 = jax.jit(
        lambda msgs, d, a: _route_segmented(cfg, msgs, d, a, m))
    out["route_v2"] = _time_ms(
        lambda: jax.block_until_ready(route2(omsgs, dst, alive)), iters)

    # KV claim/apply path at batch size m — the batch axis IS the
    # swept dimension for this kernel, so it must equal the fit's x
    # (timing m//2 rows against an x of m would halve the reported
    # per-row cost). Distinct keys — the duplicate-free workload
    # contract, ops/workload.py.
    kv = kvstore.kv_init(cfg.kv_pow2)
    rows_m = np.arange(m, dtype=np.int32)
    op = jnp.asarray(np.full(m, int(Op.PUT), np.int32))
    k_lo = jnp.asarray(rows_m)
    z = jnp.zeros(m, jnp.int32)
    valid = jnp.ones(m, dtype=bool)
    apply_fn = jax.jit(kvstore.kv_apply_batch)
    out["apply"] = _time_ms(
        lambda: jax.block_until_ready(
            apply_fn(kv, op, z, k_lo, z, k_lo + 7, valid)), iters)
    return out


def fit_per_row(caps: list[int], ms: list[float]) -> dict:
    """Least-squares wall(M) = fixed + per_row * M over the capacity
    sweep; per-row cost in µs, plus r² so a bad fit is visible."""
    x, y = np.asarray(caps, float), np.asarray(ms, float)
    b, a = np.polyfit(x, y, 1)
    pred = a + b * x
    ss_res = float(((y - pred) ** 2).sum())
    ss_tot = float(((y - y.mean()) ** 2).sum())
    return {
        "per_row_us": round(b * 1e3, 3),
        "fixed_ms": round(a, 4),
        "r2": round(1.0 - ss_res / ss_tot if ss_tot > 0 else 1.0, 4),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        "profile_substeps", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[128, 256, 512, 1024],
                    help="inbox capacities to sweep (per-row cost is "
                         "fitted across these)")
    ap.add_argument("--window", type=int, default=512,
                    help="log window (the bench's CPU shape)")
    ap.add_argument("--iters", type=int, default=40,
                    help="timing iterations per point (min is kept — "
                         "see _time_ms). Raised 15 -> 40 in PR 11: the "
                         "PR-9 table's accept/empty fits bottomed out "
                         "at r2 0.71/0.77, too noisy for before/after "
                         "claims on a shared host")
    ap.add_argument("--json", default="",
                    help="write the cost table as JSON here")
    args = ap.parse_args(argv)

    platform = jax.devices()[0].platform
    # exec_batch HELD CONSTANT across the sweep: it sizes the step's
    # exec/KV block, so letting it ride m would fold per-exec-row cost
    # into every substep's "per inbox row" slope and kink the fit at
    # m == window — the isolation premise of the sweep
    exec_batch = min(min(args.rows), args.window)
    sweep: dict[str, dict[int, float]] = {}
    for m in args.rows:
        cfg = MinPaxosConfig(
            n_replicas=5, window=args.window, inbox=m,
            exec_batch=exec_batch, kv_pow2=12,
            catchup_rows=64, recovery_rows=64)
        t0 = time.perf_counter()
        point = profile_capacity(cfg, live=m // 2, iters=args.iters)
        print(f"-- capacity {m} rows ({time.perf_counter() - t0:.0f}s "
              f"incl. compile) --")
        for name, ms in point.items():
            sweep.setdefault(name, {})[m] = ms
            print(f"  {name:10s} {ms:8.3f} ms/step")

    table = {}
    bad_fits = []
    print(f"\n== per-row cost (fit over capacities {args.rows}, "
          f"window {args.window}, platform {platform}) ==")
    for name, pts in sweep.items():
        caps = sorted(pts)
        fit = fit_per_row(caps, [pts[c] for c in caps])
        table[name] = {"ms_by_capacity": {str(c): round(pts[c], 3)
                                          for c in caps}, **fit}
        flag = ""
        if fit["r2"] < 0.9:
            flag = "  <-- NOISY FIT (r2 < 0.9)"
            bad_fits.append(name)
        print(f"  {name:10s} {fit['per_row_us']:8.2f} us/row "
              f"(+{fit['fixed_ms']:.3f} ms fixed, r2={fit['r2']}){flag}")
    if bad_fits:
        print(f"\nWARNING: fits below r2=0.9: {', '.join(bad_fits)} — "
              f"their per_row_us/fixed_ms are NOT trustworthy for "
              f"before/after claims. Re-run with a higher --iters on a "
              f"quiet host (min-of-N only rejects noise it gets enough "
              f"samples to see).", flush=True)

    result = {
        "platform": platform,
        "window": args.window,
        "n_replicas": 5,
        "capacities": args.rows,
        "iters": args.iters,
        "substeps": table,
        "fits_below_r2_0_9": bad_fits,
        "note": "branch-free masked kernels: cost scales with inbox "
                "CAPACITY rows; live-row count only changes data. "
                "'empty' is the fixed per-round floor (commit scan, "
                "exec gate, slide) and also scales with capacity "
                "through the outbox/concat shapes. 'route' is the "
                "retired dense fabric (route_fabric='dense', kept for "
                "comparability); 'route_v2' is the segmented fabric "
                "the cluster runs (PR 11).",
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote cost table to {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
