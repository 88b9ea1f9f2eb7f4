"""Scatter-pattern microbenchmark: what does a batched multi-column
scatter cost on this backend, vs the gather-based rewrites?

The protocol step writes inbox-rows into window arrays as ~10 separate
per-column scatters per section (models/minpaxos.py sections 2/3/5),
and the routing fabric compacts outboxes the same way (~12 columns,
models/cluster.py _route). Under vmap over [G, R] those become batched
scatters; if XLA:TPU serializes per update row, the step cost is
O(sections * columns * batch * rows) — the hypothesis for the observed
674 ms/round at g=64 (BENCH round 5, ~40M scattered rows/round).

Candidates measured here at bench-rung-0-like shape (B=320 batch,
M=1408 updates, S=2048 targets):

  a. baseline   — 10 independent per-column scatters (today's code)
  b. argmax+gather — 1 scatter-max of row index, then 10 gathers
  c. onehot-matmul — one-hot [S, M] f32 matmul against [M, 10] payload

  g. rank-select (PR 29): the route's and Mencius's propose's "which
     row is the k-th" at the pod cells' shapes, device-timed:
     ``python tools/scatter_micro.py rankselect [part of a label]``
  h. slot writes (PR 34): every column of a slot's winning inbox row
     into the window, at the five call shapes of the cells, the
     parent's element gathers beside each candidate formulation:
     ``python tools/scatter_micro.py slotwrite [part of a label]
     [leg=part of a formulation's name]``
  i. state reads (PR 36): several window columns, in their own
     dtypes, by one index vector (an inbox row's slot, a run of
     slots, the execution order), at the call shapes of the cells:
     ``python tools/scatter_micro.py stateread [part of a label]
     [leg=part of a formulation's name]``
  j. route (PR 38): every column of each inbox slot's winning pooled
     outbox row, per destination, at the route's call shapes:
     ``python tools/scatter_micro.py route [part of a label]
     [leg=part of a formulation's name]``
  k. KV probe: the slot, key_hi and key_lo of each key's 2W
     candidate ways in the KV table (ops/kvstore.py ``_probe``), the
     parent's three element gathers beside one gather of the stacked
     ``[3, C]`` columns with and without an optimization barrier on
     the stack (the stack's copy included), alone and inside the
     insert, at the call shapes of the cells:
     ``python tools/scatter_micro.py kvprobe [part of a label]
     [leg=part of a formulation's name]``

Run: python tools/scatter_micro.py (on the machine with the chip; one
process owns it)
"""

from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

B, M, S, NCOL = 320, 1408, 2048, 10


def _time(fn, *args, iters=10):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) * 1e3)
    ts.sort()
    return ts[len(ts) // 2]


def main() -> None:
    print(f"backend: {jax.devices()[0].platform}", file=sys.stderr)
    rng = np.random.default_rng(0)
    tgt = jnp.asarray(rng.integers(0, S + 1, (B, M)).astype(np.int32))
    cols = jnp.asarray(rng.integers(0, 1 << 20, (B, NCOL, M)).astype(np.int32))
    old = jnp.zeros((B, NCOL, S), jnp.int32)

    @jax.jit
    def scatter_percol(old, tgt, cols):
        def one(o, t, c):
            return jnp.stack([o[i].at[t].set(c[i], mode="drop")
                              for i in range(NCOL)])
        return jax.vmap(one)(old, tgt, cols)

    @jax.jit
    def argmax_gather(old, tgt, cols):
        def one(o, t, c):
            rows = jnp.arange(M, dtype=jnp.int32)
            win = jnp.full(S + 1, -1, jnp.int32).at[t].max(rows,
                                                           mode="drop")[:S]
            hit = win >= 0
            g = c[:, jnp.clip(win, 0)]          # [NCOL, S] gather
            return jnp.where(hit[None, :], g, o)
        return jax.vmap(one)(old, tgt, cols)

    @jax.jit
    def onehot_matmul(old, tgt, cols):
        def one(o, t, c):
            oh = (t[None, :] == jnp.arange(S)[:, None]).astype(jnp.float32)
            # last-writer-wins not preserved (sums dups) — timing probe only
            out = jnp.einsum("sm,cm->cs", oh, c.astype(jnp.float32))
            hit = oh.sum(1) > 0
            return jnp.where(hit[None, :], out.astype(jnp.int32), o)
        return jax.vmap(one)(old, tgt, cols)

    for name, fn in [("a. per-column scatter x10", scatter_percol),
                     ("b. argmax + gather", argmax_gather),
                     ("c. one-hot matmul", onehot_matmul)]:
        ms = _time(fn, old, tgt, cols)
        print(f"{name:28s} {ms:9.2f} ms  "
              f"({B}x{M} rows -> {S} slots, {NCOL} cols)")

    # single-column scatter scaling: is cost per-column or fixed?
    @jax.jit
    def scatter_onecol(old, tgt, cols):
        return jax.vmap(lambda o, t, c: o.at[t].set(c, mode="drop"))(
            old[:, 0], tgt, cols[:, 0])

    ms1 = _time(scatter_onecol, old, tgt, cols)
    print(f"d. single-column scatter     {ms1:9.2f} ms")

    # -- routing fabric: dense pool-per-destination vs one-pass
    # segmented (PR 11). The dense fabric is a masked cumsum + scatter
    # per destination over the [R·M] pool; the segmented one is one
    # segment-prefix-sum + a rank-select winner + 12 dense gathers
    # (ops/segscatter.py). Same inputs, byte-identical outputs
    # (tests/test_route_fabric.py) — this leg isolates the (a)
    # rewrite's win from the rest of the round.
    from minpaxos_tpu.models.cluster import _route, _route_segmented
    from minpaxos_tpu.models.minpaxos import MinPaxosConfig, MsgBatch

    r_f = 5
    for m_f in (256, 1024):
        cfg = MinPaxosConfig(n_replicas=r_f, window=512, inbox=m_f)
        n_live = m_f // 2
        cols_f = {f: np.zeros((r_f, m_f), np.int32)
                  for f in MsgBatch._fields}
        dst_f = np.full((r_f, m_f), -1, np.int32)
        for rr in range(r_f):
            cols_f["kind"][rr, :n_live] = 1 + rng.integers(0, 8, n_live)
            u = rng.random(n_live)
            dst_f[rr, :n_live] = np.where(
                u < 0.6, -1, np.where(u < 0.85,
                                      rng.integers(0, r_f, n_live), -2))
        msgs = MsgBatch(**{f: jnp.asarray(v) for f, v in cols_f.items()})
        dstj = jnp.asarray(dst_f)
        alive = jnp.ones(r_f, bool)
        dense = jax.jit(lambda a, b, c, _cfg=cfg, _m=m_f:
                        _route(_cfg, a, b, c, _m))
        seg = jax.jit(lambda a, b, c, _cfg=cfg, _m=m_f:
                      _route_segmented(_cfg, a, b, c, _m))
        ms_d = _time(dense, msgs, dstj, alive)
        ms_s = _time(seg, msgs, dstj, alive)
        print(f"e. route dense  (R=5,M={m_f:5d}) {ms_d:9.2f} ms")
        print(f"f. route segmented   (same)  {ms_s:9.2f} ms "
              f"({ms_d / ms_s:.1f}x)")


# -- rank-select (PR 29): "which row is the k-th destined one", the
# search ops/segscatter.py plan_slots and models/mencius.py section 1
# make every round. Formulations that return jnp.searchsorted's result
# element for element, at the two pod cells' shapes ([G, R, rows] x
# slots; kernel rows and outbox rows as eval_shape gives them for
# benchmarks/configs/*.json) and at their full-tier shapes.
RANK_SHAPES = [
    ("pod128 route small", 128, 5, 8645, 512),
    ("pod128 route full", 128, 5, 12485, 1280),
    ("mencius64k propose small", 16, 5, 1216, 4096),
    ("mencius64k propose full", 16, 5, 2112, 4096),
    ("mencius64k route small", 16, 5, 8965, 1152),
    ("mencius64k route full", 16, 5, 13445, 2048),
    # either side of ops/rankselect.py SHORT_ROWS
    ("between, 128 groups", 128, 5, 4096, 512),
    ("between, 128 groups", 128, 5, 6144, 512),
    ("between, 16 groups", 16, 5, 4096, 4096),
]


def _device_ms(fn, *args, iters=5):
    """Device-busy ms a call, from a profiler trace of ``iters`` calls
    (the union of the device plane's op intervals: benchmarks/lib/
    xplane.py); None where the trace holds no device plane (CPU)."""
    import shutil
    import tempfile

    from benchmarks.lib import xplane

    jax.block_until_ready(fn(*args))
    d = tempfile.mkdtemp(prefix="rank_micro_")
    try:
        jax.profiler.start_trace(d)
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        devices, _ = xplane.read_planes(xplane.find_xplane(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    if not devices:
        return None
    busy = max(sum(e - s for s, e in xplane.busy_intervals(ev))
               for ev in devices.values())
    return busy / 1e6 / iters


def rank_select_leg(shapes=RANK_SHAPES) -> None:
    """Leg g: every formulation at every shape, device-timed, with the
    compiled program's temporary bytes and whether it equals the
    default search; the last line of a shape is what
    ``ops/rankselect.py`` chooses there."""
    from minpaxos_tpu.ops import rankselect as rs

    rng = np.random.default_rng(0)
    legs = {
        "scan (jnp default)": jnp.searchsorted,
        "sort": functools.partial(jnp.searchsorted, method="sort"),
        "compare_all (jnp)": functools.partial(jnp.searchsorted,
                                               method="compare_all"),
        "compare_all (ours)": rs._below,
        "blocked": rs._blocked,
        "rank_select": rs.rank_select,
    }
    for label, g, r, n, q in shapes:
        # a healthy round's density: about 0.6 of the slots filled
        mask = rng.random((g, r, n)) < 0.6 * q / n
        cnt = jnp.asarray(np.cumsum(mask, -1).astype(np.int32))
        want = jnp.broadcast_to(jnp.arange(1, q + 1, dtype=jnp.int32),
                                (g, r, q))
        ref = None
        print(f"g. rank-select, {label}: [{g}, {r}, {n}] x {q}")
        for name, f in legs.items():
            fn = jax.jit(jax.vmap(jax.vmap(f)))
            temp = fn.lower(cnt, want).compile() \
                .memory_analysis().temp_size_in_bytes
            got = np.asarray(fn(cnt, want))
            ref = got if ref is None else ref
            ms_dev = _device_ms(fn, cnt, want)
            dev = "not measured" if ms_dev is None else f"{ms_dev:9.3f} ms"
            print(f"   {name:20s} device {dev}  host "
                  f"{_time(fn, cnt, want, iters=5):9.3f} ms  temp "
                  f"{temp / 1e6:7.1f} MB  equal {bool((got == ref).all())}")


# -- slot writes (PR 34): "write the columns of each slot's winning
# inbox row into the window", the pattern of MinPaxos's fused slot
# writes A and B and of Mencius's propose / accept / commit rows
# (ops/winner.py gather_cols). [G, R] x S slots from M kernel rows
# (the tier's inbox rows + the round's proposal rows, as eval_shape
# gives them for benchmarks/configs/*.json) x columns.
SLOTWRITE_SHAPES = [
    ("pod128 write A small", 128, 5, 640, 1024, 9),
    ("pod128 write B small", 128, 5, 640, 1024, 8),
    ("pod128 write A full", 128, 5, 1408, 1024, 9),
    ("pod128 write B full", 128, 5, 1408, 1024, 8),
    ("mencius64k propose small", 16, 5, 1216, 4096, 7),
    ("mencius64k accept small", 16, 5, 1216, 4096, 8),
    ("mencius64k propose full", 16, 5, 2112, 4096, 7),
    ("mencius64k accept full", 16, 5, 2112, 4096, 8),
    ("served3 write A", 3, 1, 1024, 2048, 9),
    # longer inboxes, up to and beyond ops/winner.py ONEHOT_PAIRS
    # (2**24 pairs of slot and row: the last of these is outside)
    ("longer, 128 groups", 128, 5, 4096, 1024, 9),
    ("longer, 128 groups", 128, 5, 6144, 1024, 9),
    ("longer, 16 groups", 16, 5, 4096, 4096, 8),
    ("longer, 16 groups", 16, 5, 8192, 4096, 8),
]


def slot_write_leg(shapes=SLOTWRITE_SHAPES) -> None:
    """Leg h: one pass (fetch + the casts and selects under ``hit``)
    in every formulation at every shape, device-timed; the last two
    lines of a shape are what ``ops/winner.py`` does there, one pass
    and two passes on one inbox."""
    from minpaxos_tpu.ops import winner as w

    def select(hit, got, olds):
        return tuple(jnp.where(hit, g.astype(o.dtype), o)
                     for g, o in zip(got, olds))

    def elements(win, hit, cols, olds):
        return select(hit, [c[win] for c in cols], olds)

    def rows(lanes, barrier=False):
        # without the barrier XLA folds pad, fetch and lane slices
        # into ONE gather of [slots, columns]; with it the fetched
        # [slots, lanes] plane is real
        def f(win, hit, cols, olds):
            got = jnp.stack(cols, -1)
            got = jnp.pad(got, ((0, 0), (0, -len(cols) % lanes)))[win]
            if barrier:
                got = jax.lax.optimization_barrier(got)
            return select(hit, [got[:, i] for i in range(len(cols))], olds)
        return f

    def stacked(win, hit, cols, olds):  # [cols, M], one gather along M
        return select(hit, jnp.stack(cols)[:, win], olds)

    def compare(win, hit, cols, olds):
        eq = win[:, None] == jnp.arange(cols[0].shape[0])[None, :]
        return select(hit, [jnp.where(eq, c[None, :], 0).sum(-1)
                            for c in cols], olds)

    def onehot_rows(win, hit, cols, olds):  # [slots, M] @ [M, bytes]
        m = cols[0].shape[0]
        oh = (win[:, None] == jnp.arange(m, dtype=jnp.int32)[None, :]
              ).astype(jnp.bfloat16)
        by = jnp.stack([(c >> sh) & 0xFF for c in cols
                        for sh in (0, 8, 16, 24)], -1).astype(jnp.bfloat16)
        got = jnp.dot(oh, by, preferred_element_type=jnp.float32
                      ).astype(jnp.int32)
        return select(hit, [
            got[:, 4 * i] | (got[:, 4 * i + 1] << 8)
            | (got[:, 4 * i + 2] << 16) | (got[:, 4 * i + 3] << 24)
            for i in range(len(cols))], olds)

    def twice(win, hit, cols, olds):  # two passes on one inbox
        a = w.gather_cols(win, hit, cols, olds)
        return w.gather_cols(jnp.flip(win), hit, cols, a)

    legs = {"element gathers (parent)": elements,
            "stacked [cols, M]": stacked,
            "rows (folded by XLA)": rows(128),
            "rows, 16 lanes, barrier": rows(16, True),
            "rows, 128 lanes, barrier": rows(128, True),
            "compare": compare,
            "one-hot matmul [S, bytes]": onehot_rows,
            "gather_cols": w.gather_cols,
            "gather_cols x2, one inbox": twice}
    only = [a[4:] for a in sys.argv[2:] if a.startswith("leg=")]
    rng = np.random.default_rng(0)
    for label, g, r, m, s, ncol in shapes:
        # a healthy round: an eighth of the window hit, by distinct
        # rows; every other slot's index is the last row (MinPaxos's
        # mod of a -1 key) and discarded under hit
        n_hit = min(m, s // 8)
        win = np.full((g, r, s), m - 1, np.int32)
        hit = np.zeros((g, r, s), bool)
        at = rng.integers(0, s - n_hit + 1)
        win[..., at:at + n_hit] = rng.permuted(
            np.broadcast_to(np.arange(m, dtype=np.int32), (g, r, m)),
            axis=-1)[..., :n_hit]
        hit[..., at:at + n_hit] = True
        cols = tuple(jnp.asarray(rng.integers(
            -2 ** 31, 2 ** 31, (g, r, m), dtype=np.int64).astype(np.int32))
            for _ in range(ncol))
        # the state's dtypes: op is uint8, MinPaxos's ninth (the
        # sender's bit) uint16, every other column int32
        dts = [jnp.int32, jnp.uint8] + [jnp.int32] * (ncol - 2)
        if ncol == 9:
            dts[-1] = jnp.uint16
        olds = tuple(jnp.zeros((g, r, s), d) for d in dts)
        win, hit = jnp.asarray(win), jnp.asarray(hit)
        ref = None
        print(f"h. slot write, {label}: [{g}, {r}] x {s} slots from "
              f"{m} rows x {ncol} columns")
        for name, f in legs.items():
            if only and not any(o in name for o in only):
                continue
            fn = jax.jit(jax.vmap(jax.vmap(f)))
            try:
                temp = fn.lower(win, hit, cols, olds).compile() \
                    .memory_analysis().temp_size_in_bytes
                got = [np.asarray(x) for x in fn(win, hit, cols, olds)]
            except Exception as e:  # a plane that does not fit
                print(f"   {name:26s} failed: {type(e).__name__}: "
                      f"{str(e)[:120]}")
                continue
            ref = got if ref is None else ref
            same = name.startswith("gather_cols x2") or all(
                (a == b).all() for a, b in zip(got, ref))
            ms_dev = _device_ms(fn, win, hit, cols, olds)
            dev = "not measured" if ms_dev is None else f"{ms_dev:9.3f} ms"
            print(f"   {name:26s} device {dev}  host "
                  f"{_time(fn, win, hit, cols, olds, iters=5):9.3f} ms  "
                  f"temp {temp / 1e6:7.1f} MB  equal {same}", flush=True)


# -- state reads (PR 36): "read these window columns at these slots",
# the pattern of MinPaxos's 1c / 2 / 2b / 7c / 7e / 8 and of Mencius's
# 2 and 11 (ops/winner.py read_cols). [G, R] x N index rows into S
# slots; a column's kind is its dtype (i int32, h uint16, b uint8,
# ? bool); the index is an inbox row's slot (repeats, clipped), a
# clipped run of slots, or a permutation of the window.
STATEREAD_SHAPES = [
    ("pod128 status+ballot small", 128, 5, 640, 1024, "bi", "rows"),
    ("pod128 vb_max small", 128, 5, 640, 1024, "i", "rows"),
    ("pod128 ack+prepare_inst small", 128, 5, 640, 1024, "bibiiiiii", "rows"),
    ("pod128 ack+prepare_inst full", 128, 5, 1408, 1024, "bibiiiiii",
     "rows"),
    ("pod128 catchup", 128, 5, 512, 1024, "biiiiii", "run"),
    ("pod128 exec", 128, 5, 128, 1024, "biiiiii", "run"),
    ("pod128 sweep", 128, 5, 1024, 64, "?", "run"),
    ("pod128 run_len", 128, 5, 640, 641, "i", "rows"),
    ("mencius64k ballot+status small", 16, 5, 1216, 4096, "ib", "rows"),
    ("mencius64k dup small", 16, 5, 1216, 4096, "bbiiiiii", "rows"),
    ("mencius64k dup full", 16, 5, 2112, 4096, "bbiiiiii", "rows"),
    ("mencius64k order", 16, 5, 4096, 4096, "bbii??", "order"),
    ("mencius64k exec", 16, 5, 320, 4096, "biiiiii", "rows"),
    ("mencius64k retry rows", 16, 5, 128, 4096, "bibiiiiii?i", "run"),
    ("served3 ack+prepare_inst", 3, 1, 1024, 2048, "bibiiiiii", "rows"),
    ("mencius3 order", 3, 1, 4096, 4096, "bbii??", "order"),
]
_KIND = {"i": np.int32, "h": np.uint16, "b": np.uint8, "?": np.bool_}


def state_read_leg(shapes=STATEREAD_SHAPES) -> None:
    """Leg i: one read of every column in every formulation at every
    shape, device-timed; a run is also read as a slice of the
    edge-padded column (what a clipped run IS, and no gather)."""
    from minpaxos_tpu.ops import winner as w

    def elements(idx, start, cols):
        return tuple(c[idx] for c in cols)

    def stacked(idx, start, cols):  # [cols, S] as int32, one gather
        got = jnp.stack([c.astype(jnp.int32) for c in cols])[:, idx]
        return tuple(g.astype(c.dtype) for g, c in zip(got, cols))

    def read_cols(idx, start, cols):
        return w.read_cols(idx, cols)

    def run_slice(idx, start, cols):
        n, s = idx.shape[0], cols[0].shape[0]
        at = jnp.clip(start, -n, s) + n
        return tuple(jax.lax.dynamic_slice(
            jnp.pad(c, (n, n), mode="edge"), (at,), (n,)) for c in cols)

    legs = {"element gathers (parent)": elements,
            "stacked [cols, S]": stacked,
            "read_cols": read_cols,
            "edge-padded dynamic_slice": run_slice}
    only = [a[4:] for a in sys.argv[2:] if a.startswith("leg=")]
    rng = np.random.default_rng(0)
    for label, g, r, n, s, kinds, index in shapes:
        # a run begins inside the source or a little before it; the
        # sweep's chunk (shorter than its index) lies inside the window
        lo, hi = (-n // 4, s - n // 2) if s > n else (s - n, 1)
        start = rng.integers(lo, hi, (g, r))
        if index == "rows":  # an eighth beyond the window, clipped
            idx = np.clip(rng.integers(0, s + s // 8, (g, r, n)), 0, s - 1)
        elif index == "run":
            idx = np.clip(start[..., None] + np.arange(n), 0, s - 1)
        else:
            idx = rng.permuted(np.broadcast_to(np.arange(s), (g, r, s)),
                               axis=-1)
        cols = tuple(jnp.asarray(
            rng.random((g, r, s)) < 0.5 if k == "?" else rng.integers(
                np.iinfo(_KIND[k]).min, np.iinfo(_KIND[k]).max, (g, r, s),
                dtype=np.int64, endpoint=True).astype(_KIND[k]))
            for k in kinds)
        idx = jnp.asarray(idx.astype(np.int32))
        start = jnp.asarray(start.astype(np.int32))
        ref = None
        print(f"i. state read, {label}: [{g}, {r}] x {n} rows into {s} "
              f"slots x {len(kinds)} columns ({kinds}), by {index}")
        for name, f in legs.items():
            if (only and not any(o in name for o in only)) or (
                    f is run_slice and index != "run"):
                continue
            fn = jax.jit(jax.vmap(jax.vmap(f)))
            temp = fn.lower(idx, start, cols).compile() \
                .memory_analysis().temp_size_in_bytes
            got = [np.asarray(x) for x in fn(idx, start, cols)]
            ref = got if ref is None else ref
            same = all(a.dtype == b.dtype and (a == b).all()
                       for a, b in zip(got, ref))
            ms_dev = _device_ms(fn, idx, start, cols)
            dev = "not measured" if ms_dev is None else f"{ms_dev:9.3f} ms"
            print(f"   {name:26s} device {dev}  host "
                  f"{_time(fn, idx, start, cols, iters=5):9.3f} ms  "
                  f"temp {temp / 1e6:7.1f} MB  equal {same}", flush=True)


# -- route (PR 38): "fill each destination's inbox slots with the
# columns of its winning pooled outbox row", ops/segscatter.py
# gather_rows, at the route's call shapes (RANK_SHAPES: [G] groups, R
# destinations x slots from R·M pooled rows x the 12 int32 columns of
# a MsgBatch).
ROUTE_SHAPES = [sh for sh in RANK_SHAPES if " route " in sh[0]]


def route_leg(shapes=ROUTE_SHAPES) -> None:
    """Leg j: one route fill in every formulation at every shape,
    device-timed, with the compiled program's temporaries; the last
    line of a shape is what ``ops/segscatter.py`` does there."""
    from minpaxos_tpu.ops import segscatter
    from minpaxos_tpu.ops import winner as w

    def picked(hit, got):
        return tuple(jnp.where(hit, g, 0) for g in got)

    def elements(win, hit, cols):  # the parent: a gather a column
        winc = jnp.where(hit, win, 0)
        return picked(hit, [c[winc] for c in cols])

    def stacked_rows(win, hit, cols):  # int32[R·M, 12], one row gather
        got = jnp.stack(cols, -1)[jnp.where(hit, win, 0)]
        return picked(hit, [got[..., i] for i in range(len(cols))])

    def per_destination(win, hit, cols):  # the pool is the summed side
        got = jax.vmap(lambda i: w._fetch(i, cols))(jnp.where(hit, win, 0))
        return picked(hit, got)

    def per_destination_map(win, hit, cols):  # one destination at a time
        got = jax.lax.map(lambda i: w._fetch(i, cols),
                          jnp.where(hit, win, 0))
        return picked(hit, got)

    def per_block(win, hit, cols):
        # a slot's winner lies in exactly one sender's block of M rows:
        # the other blocks' selects match nothing and add zero
        r = win.shape[0]
        m = cols[0].shape[0] // r
        got = None
        for b in range(r):
            part = jax.vmap(lambda i, b=b: w._fetch(
                i - b * m, [c[b * m:(b + 1) * m] for c in cols]))(win)
            got = part if got is None else [a + p for a, p in zip(got, part)]
        return picked(hit, got)

    def gather_rows(win, hit, cols):
        return tuple(segscatter.gather_rows(cols, win, hit))

    legs = {"element gathers (parent)": elements,
            "stacked rows [R*M, 12]": stacked_rows,
            "select per destination": per_destination,
            "select per destination, map": per_destination_map,
            "select per sender block": per_block,
            "gather_rows": gather_rows}
    only = [a[4:] for a in sys.argv[2:] if a.startswith("leg=")]
    rng = np.random.default_rng(0)
    for label, g, r, n, slots in shapes:
        # a healthy round's density, as leg g: about 0.6 of the slots
        # filled, the rest unfilled (win == n)
        mask = rng.random((g, r, n)) < 0.6 * slots / n
        cnt = jnp.asarray(np.cumsum(mask, -1).astype(np.int32))
        win, hit = jax.jit(jax.vmap(
            lambda c: segscatter.plan_slots(c, slots)))(cnt)
        cols = tuple(jnp.asarray(rng.integers(
            -2 ** 31, 2 ** 31, (g, n), dtype=np.int64).astype(np.int32))
            for _ in range(12))
        ref = None
        print(f"j. route, {label}: [{g}, {r}] x {slots} slots from {n} "
              f"pooled rows x 12 columns ({slots * n / 1e6:.2f} M pairs "
              f"a destination)")
        for name, f in legs.items():
            if only and not any(o in name for o in only):
                continue
            fn = jax.jit(jax.vmap(f))
            try:
                temp = fn.lower(win, hit, cols).compile() \
                    .memory_analysis().temp_size_in_bytes
                got = [np.asarray(x) for x in fn(win, hit, cols)]
            except Exception as e:  # a plane that does not fit
                print(f"   {name:28s} failed: {type(e).__name__}: "
                      f"{str(e)[:120]}")
                continue
            ref = got if ref is None else ref
            same = all((a == b).all() for a, b in zip(got, ref))
            ms_dev = _device_ms(fn, win, hit, cols)
            dev = "not measured" if ms_dev is None else f"{ms_dev:9.3f} ms"
            print(f"   {name:28s} device {dev}  host "
                  f"{_time(fn, win, hit, cols, iters=5):9.3f} ms  "
                  f"temp {temp / 1e6:7.1f} MB  equal {same}", flush=True)


# -- KV probe: "read the slot and the key of every candidate
# way", ops/kvstore.py ``_probe`` inside ``kv_insert_unique``: [G, R]
# tables of 2^kv_pow2 entries, B exec-batch rows x 2W ways each
# (benchmarks/configs/*.json; the served shape is
# minpaxos_tpu/deployments.py SERVER_SHAPE's).
KVPROBE_SHAPES = [
    ("pod128", 128, 5, 128, 16),
    ("mencius64k", 16, 5, 320, 14),
    ("served3", 3, 1, 128, 18),
]


def kv_probe_leg(shapes=KVPROBE_SHAPES) -> None:
    """Leg k: the probe in every formulation at every shape, alone and
    inside the insert (``_insert_probed``: claims and the four write
    scatters too), device-timed, with the compiled program's
    temporaries; ``_probe`` is what ``ops/kvstore.py`` ships."""
    from minpaxos_tpu.ops import kvstore as kvs

    def elements(kv, pos):  # the parent: a gather a column
        return kv.slot[pos], kv.key_hi[pos], kv.key_lo[pos]

    def stacked(barrier):
        def f(kv, pos):
            cols = jnp.stack([kv.slot, kv.key_hi, kv.key_lo])
            if barrier:
                cols = jax.lax.optimization_barrier(cols)
            got = cols[:, pos]
            return got[0], got[1], got[2]
        return f

    def insert(probe):
        def f(kv, pos, k_hi, k_lo, v, delete, valid):
            return kvs._insert_probed(kv, pos, probe(kv, pos), k_hi, k_lo,
                                      v, delete, valid)
        return f

    probes = {"element gathers (parent)": elements,
              "stacked [3, C], barrier": stacked(True),
              "stacked [3, C]": stacked(False),
              "_probe": kvs._probe}
    legs = {**{name: (p, False) for name, p in probes.items()},
            **{f"insert, {name}": (insert(p), True)
               for name, p in probes.items()}}
    only = [a[4:] for a in sys.argv[2:] if a.startswith("leg=")]
    rng = np.random.default_rng(0)
    for label, g, r, b, kv_pow2 in shapes:
        c = 1 << kv_pow2

        def ints(*shape):
            return jnp.asarray(rng.integers(-2 ** 31, 2 ** 31, (g, r) + shape,
                                            dtype=np.int64).astype(np.int32))

        # a table a quarter live, and a batch of distinct random keys
        kv = kvs.KVState(ints(c), ints(c), ints(c, kvs.VAL_LANES),
                         jnp.asarray((rng.random((g, r, c)) < 0.25)
                                     .astype(np.int32)),
                         jnp.zeros((g, r), jnp.int32))
        k_hi, k_lo, v = ints(b), ints(b), ints(b, kvs.VAL_LANES)
        delete = jnp.asarray(rng.random((g, r, b)) < 0.1)
        valid = jnp.ones((g, r, b), bool)
        pos = jax.jit(jax.vmap(jax.vmap(
            lambda h, lo: kvs._cand_pos(c, h, lo))))(k_hi, k_lo)
        print(f"k. KV probe, {label}: [{g}, {r}] x {b} rows x "
              f"{2 * kvs.WAYS} ways into 2^{kv_pow2} entries")
        refs = {}
        for name, (f, whole) in legs.items():
            if only and not any(o in name for o in only):
                continue
            args = ((kv, pos, k_hi, k_lo, v, delete, valid) if whole
                    else (kv, pos))
            fn = jax.jit(jax.vmap(jax.vmap(f)))
            temp = fn.lower(*args).compile() \
                .memory_analysis().temp_size_in_bytes
            got = [np.asarray(x) for x in jax.tree_util.tree_leaves(
                fn(*args))]
            ref = refs.setdefault(whole, got)
            same = all((a == b).all() for a, b in zip(got, ref))
            ms_dev = _device_ms(fn, *args)
            dev = "not measured" if ms_dev is None else f"{ms_dev:9.3f} ms"
            print(f"   {name:34s} device {dev}  host "
                  f"{_time(fn, *args, iters=5):9.3f} ms  "
                  f"temp {temp / 1e6:7.1f} MB  equal {same}", flush=True)


def _labelled(shapes):
    return [sh for sh in shapes
            if all(a in sh[0] for a in sys.argv[2:] if "=" not in a)]


if __name__ == "__main__":
    if sys.argv[1:2] == ["rankselect"]:  # leg g alone: [label part]
        rank_select_leg(_labelled(RANK_SHAPES))
    elif sys.argv[1:2] == ["slotwrite"]:  # leg h: [label part] [leg=part]
        slot_write_leg(_labelled(SLOTWRITE_SHAPES))
    elif sys.argv[1:2] == ["stateread"]:  # leg i: [label part] [leg=part]
        state_read_leg(_labelled(STATEREAD_SHAPES))
    elif sys.argv[1:2] == ["route"]:  # leg j: [label part] [leg=part]
        route_leg(_labelled(ROUTE_SHAPES))
    elif sys.argv[1:2] == ["kvprobe"]:  # leg k: [label part] [leg=part]
        kv_probe_leg(_labelled(KVPROBE_SHAPES))
    else:
        main()
        rank_select_leg()
        slot_write_leg()
        state_read_leg()
        route_leg()
        kv_probe_leg()
