"""KV engine vs a sequential dict oracle (reference state.Execute
semantics, state/state.go:86-103)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minpaxos_tpu.ops.kvstore import KVState, kv_apply_batch, kv_init, kv_lookup
from minpaxos_tpu.ops.packed import join_i64, split_i64
from minpaxos_tpu.wire.messages import Op


def _apply_np(kv, ops, keys, vals, valid=None):
    ops = np.asarray(ops, dtype=np.int32)
    k_hi, k_lo = split_i64(np.asarray(keys))
    v_hi, v_lo = split_i64(np.asarray(vals))
    if valid is None:
        valid = np.ones(len(ops), dtype=bool)
    kv, o_hi, o_lo, found = jax.jit(kv_apply_batch)(
        kv, jnp.asarray(ops), jnp.asarray(k_hi), jnp.asarray(k_lo),
        jnp.asarray(v_hi), jnp.asarray(v_lo), jnp.asarray(valid))
    return kv, join_i64(np.asarray(o_hi), np.asarray(o_lo)), np.asarray(found)


class DictOracle:
    def __init__(self):
        self.d = {}

    def apply(self, ops, keys, vals, valid=None):
        outs, founds = [], []
        if valid is None:
            valid = [True] * len(ops)
        for op, k, v, ok in zip(ops, keys, vals, valid):
            if not ok:
                outs.append(0); founds.append(False); continue
            if op == Op.PUT:
                self.d[k] = v; outs.append(v); founds.append(True)
            elif op == Op.GET:
                outs.append(self.d.get(k, 0)); founds.append(k in self.d)
            elif op == Op.DELETE:
                self.d.pop(k, None); outs.append(0); founds.append(False)
            else:
                outs.append(0); founds.append(False)
        return np.array(outs, dtype=np.int64), np.array(founds)


def test_put_then_get_same_batch():
    kv = kv_init(8)
    ops = [Op.PUT, Op.GET, Op.PUT, Op.GET, Op.GET]
    keys = [7, 7, 7, 7, 99]
    vals = [10, 0, 20, 0, 0]
    kv, out, found = _apply_np(kv, ops, keys, vals)
    assert out.tolist() == [10, 10, 20, 20, 0]
    assert found.tolist() == [True, True, True, True, False]


def test_cross_batch_persistence():
    kv = kv_init(8)
    kv, _, _ = _apply_np(kv, [Op.PUT], [5], [55])
    kv, out, found = _apply_np(kv, [Op.GET], [5], [0])
    assert out[0] == 55 and found[0]


def test_delete_semantics():
    kv = kv_init(8)
    kv, _, _ = _apply_np(kv, [Op.PUT, Op.DELETE, Op.GET], [1, 1, 1], [9, 0, 0])
    kv, out, found = _apply_np(kv, [Op.GET], [1], [0])
    assert not found[0] and out[0] == 0


def test_64bit_keys_and_values():
    kv = kv_init(8)
    k = 0x1234_5678_9ABC_DEF0 - 2**63  # negative i64
    v = 2**62 + 12345
    kv, out, found = _apply_np(kv, [Op.PUT, Op.GET], [k, k], [v, 0])
    assert out[1] == v and found[1]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_random_vs_oracle(seed):
    rng = np.random.default_rng(seed)
    kv = kv_init(12)  # 4096 slots
    oracle = DictOracle()
    for _ in range(5):
        b = int(rng.integers(1, 300))
        ops = rng.choice([Op.PUT, Op.GET, Op.DELETE], size=b, p=[0.5, 0.4, 0.1])
        keys = rng.integers(-50, 50, size=b).astype(np.int64)
        vals = rng.integers(-(2**60), 2**60, size=b).astype(np.int64)
        valid = rng.random(b) < 0.9
        kv, out, found = _apply_np(kv, ops, keys, vals, valid)
        want_out, want_found = oracle.apply(ops, keys, vals, valid)
        np.testing.assert_array_equal(out, want_out)
        np.testing.assert_array_equal(found, want_found)
        assert int(np.asarray(kv.dropped)) == 0
    # final table state agrees with the oracle
    ks = np.array(sorted(oracle.d), dtype=np.int64)
    if len(ks):
        k_hi, k_lo = split_i64(ks)
        f, v_hi, v_lo = jax.jit(kv_lookup)(kv, jnp.asarray(k_hi), jnp.asarray(k_lo))
        assert np.asarray(f).all()
        np.testing.assert_array_equal(
            join_i64(np.asarray(v_hi), np.asarray(v_lo)),
            np.array([oracle.d[k] for k in ks]))


@pytest.mark.parametrize("lanes", [4, 256])
def test_wide_value_lanes_vs_oracle(lanes):
    """The engine is generic over the value-lane axis: lanes=256 is the
    reference's 1KB build variant (state.go.1k:15, Value [128]int64 =
    256 i32 lanes). Same sequential semantics, oracle-checked on whole
    lane vectors including in-batch PUT->GET forwarding."""
    from minpaxos_tpu.ops.kvstore import kv_apply_batch_lanes, kv_lookup_lanes

    rng = np.random.default_rng(99)
    kv = kv_init(6, val_lanes=lanes)  # 64 slots
    oracle = {}
    for _ in range(3):
        b = 40
        ops = rng.choice([Op.PUT, Op.GET, Op.DELETE], size=b,
                         p=[0.5, 0.4, 0.1]).astype(np.int32)
        keys = rng.integers(0, 20, size=b).astype(np.int64)
        k_hi, k_lo = split_i64(keys)
        vals = rng.integers(-(2**31), 2**31, size=(b, lanes)).astype(np.int32)
        want_out = np.zeros((b, lanes), np.int32)
        want_found = np.zeros(b, bool)
        for i, (op, k) in enumerate(zip(ops, keys)):
            if op == Op.PUT:
                oracle[k] = vals[i].copy()
                want_out[i], want_found[i] = vals[i], True
            elif op == Op.GET:
                if k in oracle:
                    want_out[i], want_found[i] = oracle[k], True
            elif op == Op.DELETE:
                oracle.pop(k, None)
        kv, out, found = jax.jit(kv_apply_batch_lanes)(
            kv, jnp.asarray(ops), jnp.asarray(k_hi), jnp.asarray(k_lo),
            jnp.asarray(vals), jnp.ones(b, bool))
        np.testing.assert_array_equal(np.asarray(out), want_out)
        np.testing.assert_array_equal(np.asarray(found), want_found)
        assert int(np.asarray(kv.dropped)) == 0
    # final table state: every surviving key holds its full lane vector
    ks = np.array(sorted(oracle), dtype=np.int64)
    if len(ks):
        k_hi, k_lo = split_i64(ks)
        f, v = jax.jit(kv_lookup_lanes)(kv, jnp.asarray(k_hi),
                                        jnp.asarray(k_lo))
        assert np.asarray(f).all()
        np.testing.assert_array_equal(
            np.asarray(v), np.stack([oracle[k] for k in ks]))


def test_put_delete_churn_reuses_capacity():
    # delete-in-place: churn on one key must not consume table slots
    kv = kv_init(4)  # 16 slots
    for i in range(40):
        kv, _, _ = _apply_np(kv, [Op.PUT, Op.DELETE], [7, 7], [i, 0])
    kv, out, found = _apply_np(kv, [Op.PUT, Op.GET], [7, 7], [99, 0])
    assert found[1] and out[1] == 99
    assert int(np.asarray(kv.dropped)) == 0


def test_probe_chain_with_collisions():
    # tiny table (16 slots) + more distinct keys than half capacity
    kv = kv_init(4)
    keys = np.arange(12, dtype=np.int64) * 1000
    kv, out, found = _apply_np(kv, [Op.PUT] * 12, keys, keys + 1)
    kv, out, found = _apply_np(kv, [Op.GET] * 12, keys, np.zeros(12))
    assert found.all()
    assert (out == keys + 1).all()


# -- the probe pinned to the three element gathers it replaced: the
# table's slot, key_hi and key_lo read one column at a time at the
# candidate ways. Swapping ``kvstore._probe`` for this gives that
# formulation of the engine, which every leaf and output must equal,
# bit for bit.
def _three_gathers(kv, pos):
    return kv.slot[pos], kv.key_hi[pos], kv.key_lo[pos]


def _both(monkeypatch, fn, *args):
    """``fn(*args)`` jitted through the engine's probe and through the
    three gathers (a fresh jit each, so neither reuses a trace)."""
    from minpaxos_tpu.ops import kvstore

    got = jax.jit(lambda *a: fn(*a))(*args)
    with monkeypatch.context() as m:
        m.setattr(kvstore, "_probe", _three_gathers)
        want = jax.jit(lambda *a: fn(*a))(*args)
    return got, want


def _assert_bitwise(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.atleast_1d(g), np.atleast_1d(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))


def _spread(k):
    """Small ints to 64-bit keys all over the hash's input."""
    return (np.asarray(k, np.int64).astype(np.uint64)
            * np.uint64(0x9E3779B97F4A7C15)).view(np.int64)


def _batch(rng, b, keys, lanes):
    """A PUT / GET / DELETE mix over ``keys`` distinct keys (so
    duplicates within a batch, deletes of absent keys and keys already
    live all occur), about a tenth of the rows invalid."""
    ops = rng.choice([Op.PUT, Op.GET, Op.DELETE], size=b,
                     p=[0.5, 0.3, 0.2]).astype(np.int32)
    k_hi, k_lo = split_i64(_spread(rng.integers(0, keys, b)))
    vals = rng.integers(-(2**31), 2**31, (b, lanes)).astype(np.int32)
    valid = rng.random(b) < 0.9
    return tuple(jnp.asarray(x) for x in (ops, k_hi, k_lo, vals, valid))


@pytest.mark.parametrize("lanes,kv_pow2,keys,full", [
    (2, 10, 300, False),  # roomy: nothing dropped
    (2, 4, 64, True),  # 16 ways, 64 keys: both buckets full, dropped moves
    (256, 8, 120, False),
    (256, 4, 48, True),
])
def test_probe_equals_three_gathers(monkeypatch, lanes, kv_pow2, keys,
                                    full):
    from minpaxos_tpu.ops.kvstore import (
        kv_apply_batch_lanes, kv_insert_unique, kv_lookup_lanes)

    rng = np.random.default_rng(4200 + kv_pow2 + lanes)
    kv = kv_init(kv_pow2, val_lanes=lanes)
    for _ in range(4):
        args = _batch(rng, 96, keys, lanes)
        got, want = _both(monkeypatch, kv_apply_batch_lanes, kv, *args)
        _assert_bitwise(got, want)  # every KVState leaf, out, found
        kv = got[0]
    assert (int(kv.dropped) > 0) == full
    assert int((np.asarray(kv.slot) == 1).sum()) > 0
    # each public entry point alone makes its own probe: a lookup of
    # live and absent keys, an insert of distinct keys over live ones
    _, k_hi, k_lo, vals, valid = _batch(rng, 64, keys, lanes)
    _assert_bitwise(*_both(monkeypatch, kv_lookup_lanes, kv, k_hi, k_lo,
                           valid))
    k_hi, k_lo = split_i64(_spread(rng.permutation(keys)[:32]))
    d = jnp.asarray(rng.random(32) < 0.3)
    _assert_bitwise(*_both(monkeypatch, kv_insert_unique, kv,
                           jnp.asarray(k_hi), jnp.asarray(k_lo),
                           vals[:32], d, valid[:32]))


def test_probe_equals_three_gathers_under_pod_vmap(monkeypatch):
    """The pod applies its exec batches under a [G, R] vmap: the
    stacked probe is then a batched gather, pinned the same way."""
    g, r, b, kv_pow2 = 3, 2, 48, 4
    rng = np.random.default_rng(4242)
    kv = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (g, r) + x.shape), kv_init(kv_pow2))

    def apply(kv, op, k_hi, k_lo, v_hi, v_lo, valid):
        return jax.vmap(jax.vmap(kv_apply_batch))(
            kv, op, k_hi, k_lo, v_hi, v_lo, valid)

    for _ in range(3):
        cols = [_batch(rng, b, 40, 2) for _ in range(g * r)]
        op, k_hi, k_lo, v, valid = (
            jnp.stack([c[i] for c in cols]).reshape((g, r) + cols[0][i].shape)
            for i in range(5))
        got, want = _both(monkeypatch, apply, kv, op, k_hi, k_lo,
                          v[..., 0], v[..., 1], valid)
        _assert_bitwise(got, want)
        kv = got[0]
    assert (np.asarray(kv.dropped) > 0).any()  # 40 keys into 16 ways
