"""``ops/winner.py gather_cols``: what per-column ``gather_row`` gives,
from one fetch of the winning row (PR 34); and ``read_cols``, the same
fetch with slots and rows exchanged (PR 36): what ``col[idx]`` gives a
column, for the window's columns in their own dtypes.

``gather_cols`` took the place of seven to nine ``gather_row``s (or
their open-coded twins) in MinPaxos's two fused slot writes and in
Mencius's propose / accept / commit rows / takeover adoption, so it
must return, column for column and bit for bit, what ``gather_row``
returns for each: every log and table downstream is pinned byte for
byte (tests/test_kernel_golden.py, tests/test_route_fabric.py,
tests/test_mencius*.py, tests/test_sharded.py, which run through it
with their fixture files unchanged). Here the primitive alone, on
either side of its bound on the pairs of slot and row, over the edges
a step can hand it: the state's mixed dtypes, no slot hit and every
slot hit, -1 for a slot no row won, one inbox row, windows shorter and
longer than the inbox, payloads with their sign bits set, the pod's
[G, R] vmap, and two passes on one inbox. The cells' own call shapes
run once each, under the vmap: XLA:CPU pays for their [slots, rows]
plane in full.

``read_cols`` took the place of the element gathers of STATE by an
inbox row's slot (MinPaxos 1c / 2 / 2b, Mencius 2), by a run of slots
(catch-up, the exec batch), by the sweep's chunk and by Mencius's
execution order, so it is held to ``col[idx]`` over what those hand
it: every dtype the window has, indices that repeat, indices the clip
has piled on both edges, a run, a permutation, the [G, R] vmap, the
bound itself (Mencius's order x window is exactly ``ONEHOT_PAIRS``)
and each cell's call shape once.
"""

from __future__ import annotations

import zlib

import jax
import numpy as np
import pytest

from minpaxos_tpu.ops.winner import (
    ONEHOT_PAIRS,
    gather_cols,
    gather_row,
    read_cols,
)

I32 = np.iinfo(np.int32)

# (M rows, S slots, columns): toys, and the last shape that selects by
# the one-hot matmul beside the first that reads a column at a time
SHAPES = {
    "toy_window_over_inbox": (40, 64, 9),
    "toy_inbox_over_window": (96, 32, 8),
    "toy_one_row": (1, 16, 8),
    "toy_one_slot": (33, 1, 7),
    "toy_two_columns": (50, 70, 2),
    "pairs_at_the_bound": (ONEHOT_PAIRS // 2048, 2048, 8),
    "pairs_past_the_bound": (ONEHOT_PAIRS // 2048 + 1, 2048, 9),
}
# the call shapes of the cells (kernel rows = the tier's inbox rows +
# the round's proposal rows), and of the distributed mode's default
CELL_SHAPES = {
    "pod128_write_a_small": (640, 1024, 9),
    "pod128_write_b_full": (1408, 1024, 8),
    "mencius64k_propose_small": (1216, 4096, 7),
    "mencius64k_accept_full": (2112, 4096, 8),
    "served3_write_a": (1024, 2048, 9),
}
DEFAULT_SERVER = (4096, 16384, 9)  # cli/server.py -inbox, -window

# the state's dtypes, by column: ballot int32, op uint8, the payload
# int32; MinPaxos's ninth column is the sender's bit, a uint16 of votes
_STATE_DTYPES = (np.int32, np.uint8) + (np.int32,) * 6 + (np.uint16,)

WINS = ("healthy", "no_hit", "every_hit", "minus_one", "last_row_mod")


def _rng(*key) -> np.random.Generator:
    return np.random.default_rng(zlib.crc32(repr(key).encode()))


def _inputs(rng, batch, m, s, ncol, wins):
    """cols int32[*batch, m] x ncol over the whole int32 range (sign
    bits set in half of them), win / hit [*batch, s], olds in the
    state's dtypes."""
    cols = [rng.integers(I32.min, I32.max, batch + (m,), dtype=np.int64,
                         endpoint=True).astype(np.int32)
            for _ in range(ncol)]
    cols[0][..., 0] = I32.min  # both ends present, in row 0 and the last
    cols[0][..., -1] = I32.max
    olds = [rng.integers(0, 200, batch + (s,)).astype(dt)
            for dt in _STATE_DTYPES[:ncol]]
    win = rng.integers(0, m, batch + (s,)).astype(np.int32)
    if wins == "healthy":  # an eighth of the window hit
        hit = rng.random(batch + (s,)) < 0.125
    elif wins == "no_hit":
        hit = np.zeros(batch + (s,), bool)
    elif wins == "every_hit":
        hit = np.ones(batch + (s,), bool)
    elif wins == "minus_one":  # slot_winner's: -1 where no row won
        hit = rng.random(batch + (s,)) < 0.5
        win = np.where(hit, win, -1).astype(np.int32)
    else:  # MinPaxos's: mod(key, M) of a -1 key is the LAST row
        hit = rng.random(batch + (s,)) < 0.5
        win = np.where(hit, win, m - 1).astype(np.int32)
    return cols, olds, win, hit


def _per_column(win, hit, cols, olds):
    return tuple(gather_row(win, hit, c, o) for c, o in zip(cols, olds))


def _assert_same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("wins", WINS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_equals_gather_row_column_for_column(shape, wins):
    m, s, ncol = SHAPES[shape]
    cols, olds, win, hit = _inputs(_rng(shape, wins), (), m, s, ncol, wins)
    _assert_same(jax.jit(gather_cols)(win, hit, cols, olds),
                 jax.jit(_per_column)(win, hit, cols, olds))


@pytest.mark.parametrize("shape", list(SHAPES) + list(CELL_SHAPES))
def test_under_the_pods_vmap(shape):
    """[G, R] groups x replicas, each with rows and winners of its
    own, as ``sharded_round`` steps them."""
    m, s, ncol = {**SHAPES, **CELL_SHAPES}[shape]
    batch = (2, 2) if s * m > ONEHOT_PAIRS // 16 else (4, 3)
    cols, olds, win, hit = _inputs(_rng(shape, "vmap"), batch, m, s, ncol,
                                   "minus_one")
    both = lambda f: jax.jit(jax.vmap(jax.vmap(f)))  # noqa: E731
    _assert_same(both(gather_cols)(win, hit, cols, olds),
                 both(_per_column)(win, hit, cols, olds))


@pytest.mark.parametrize("shape", list(SHAPES))
def test_two_passes_on_one_inbox(shape):
    """MinPaxos's write A then write B, Mencius's sections: the second
    pass reads the first's results as its olds, and fewer columns of
    the same rows."""
    m, s, ncol = SHAPES[shape]
    rng = _rng(shape, "two")
    cols, olds, win_a, hit_a = _inputs(rng, (), m, s, ncol, "last_row_mod")
    _, _, win_b, hit_b = _inputs(rng, (), m, s, ncol, "healthy")

    def twice(one_pass):
        def f(win_a, hit_a, win_b, hit_b, cols, olds):
            mid = one_pass(win_a, hit_a, cols, olds)
            return one_pass(win_b, hit_b, cols[:-1], mid[:-1]) + mid[-1:]
        return jax.jit(f)

    args = (win_a, hit_a, win_b, hit_b, cols, olds)
    _assert_same(twice(gather_cols)(*args), twice(_per_column)(*args))


def _selects_by_matmul(m, s, ncol):
    cols, olds, win, hit = _inputs(_rng("traced"), (), m, s, ncol, "no_hit")
    return "dot_general" in str(jax.make_jaxpr(gather_cols)(
        win, hit, cols, olds))


@pytest.mark.parametrize("shape", list(SHAPES) + list(CELL_SHAPES))
def test_the_matmul_up_to_the_pair_bound(shape):
    """Every shape a cell times selects by the one-hot matmul; the
    bound is on slots x rows, what the plane's size goes by."""
    m, s, ncol = {**SHAPES, **CELL_SHAPES}[shape]
    assert _selects_by_matmul(m, s, ncol) == (shape != "pairs_past_the_bound")
    assert (s * m <= ONEHOT_PAIRS) == (shape != "pairs_past_the_bound")


def test_the_default_server_reads_a_column_at_a_time():
    """README's distributed mode runs the step on XLA:CPU at 16,384
    slots x 4,096 rows: a one-hot plane there is 134 MB a pass and
    2.4 GMAC (41.7 ms a step against the gathers' 2.15; PERF.md
    section 6, PR 34)."""
    assert not _selects_by_matmul(*DEFAULT_SERVER)


# ---- read_cols: STATE's columns by an index vector (PR 36) ----

# a column's kind: ballot / payload int32, votes / pvotes uint16,
# status / op uint8, executed / in_prefix bool
_KINDS = {"i": np.int32, "h": np.uint16, "b": np.uint8, "?": np.bool_}
# three values a kind's byte split could get wrong, put in the first,
# the last and the middle slot (the clipped indices land on the edges)
_EDGES = {"i": (I32.min, -1, 0x00FF00FF), "h": (0xFFFF, 0xFF00, 0x00FF),
          "b": (255, 128, 1), "?": (True, False, True)}

# (N index rows, S source slots, column kinds)
READ_SHAPES = {
    "toy_every_dtype": (40, 64, "ihb?"),
    "toy_rows_over_window": (96, 32, "bibiiiiii"),
    "toy_one_row": (1, 16, "bi"),
    "toy_one_slot": (33, 1, "ih?"),
    "toy_bytes_only": (50, 70, "bb??"),
    # Mencius's window in execution order: S x S, exactly the bound
    "pairs_at_the_bound": (4096, 4096, "bbii??"),
    "pairs_past_the_bound": (4097, 4096, "bbii??"),
}
READ_CELL_SHAPES = {
    # MinPaxos pod: before write A, after it (small and full tier),
    # catch-up's run, the sweep's chunk by window slot, the exec batch
    "pod128_status_ballot_small": (640, 1024, "bi"),
    "pod128_ack_small": (640, 1024, "bibiiiiii"),
    "pod128_ack_full": (1408, 1024, "bibiiiiii"),
    "pod128_catchup": (512, 1024, "biiiiii"),
    "pod128_sweep": (1024, 64, "?"),
    "pod128_exec": (128, 1024, "biiiiii"),
    # a row's run length by its run id (ops/ackruns.py): M + 1 sources
    "pod128_run_len": (640, 641, "i"),
    # Mencius pod: section 2 (small and full tier), the exec batch,
    # section 9's retry rows (status, the slot, driven_by_me, n_votes)
    "mencius64k_dup_small": (1216, 4096, "bbiiiiii"),
    "mencius64k_dup_full": (2112, 4096, "bbiiiiii"),
    "mencius64k_exec": (320, 4096, "biiiiii"),
    "mencius64k_retry_rows": (128, 4096, "bibiiiiii?i"),
    # the served cells' steps (MinPaxos, Mencius)
    "served3_ack": (1024, 2048, "bibiiiiii"),
    "mencius3_dup": (2048, 4096, "bbiiiiii"),
}
READ_DEFAULT_SERVER = (4096, 16384, "bibiiiiii")  # -inbox, -window

IDXS = ("repeats", "edge_clipped", "run", "permutation")


def _read_inputs(rng, batch, n, s, kinds, idxs):
    """cols [*batch, s] in their kinds' dtypes over each dtype's whole
    range, idx [*batch, n] in [0, s)."""
    cols = []
    for kd in kinds:
        dt = _KINDS[kd]
        if kd == "?":
            col = rng.random(batch + (s,)) < 0.5
        else:
            info = np.iinfo(dt)
            col = rng.integers(info.min, info.max, batch + (s,),
                               dtype=np.int64, endpoint=True).astype(dt)
        first, last, middle = np.asarray(_EDGES[kd]).astype(dt)
        col[..., 0], col[..., -1], col[..., s // 2] = first, last, middle
        cols.append(col)
    if idxs == "repeats":
        idx = rng.integers(0, s, batch + (n,))
    elif idxs == "edge_clipped":  # _rel's sentinel and the clips
        idx = np.clip(rng.integers(-s, 2 * s, batch + (n,)), 0, s - 1)
    elif idxs == "run":  # catch-up's: start + arange(K), clipped
        start = rng.integers(-n // 2, s, batch + (1,))
        idx = np.clip(start + np.arange(n), 0, s - 1)
    else:  # the execution order: each slot once (again where n > s)
        idx = rng.permuted(np.broadcast_to(
            np.resize(np.arange(s), max(n, s)), batch + (max(n, s),)),
            axis=-1)[..., :n]
    return tuple(cols), idx.astype(np.int32)


def _index_per_column(idx, cols):
    return tuple(c[idx] for c in cols)


@pytest.mark.parametrize("idxs", IDXS)
@pytest.mark.parametrize("shape", list(READ_SHAPES))
def test_read_cols_equals_indexing_column_for_column(shape, idxs):
    n, s, kinds = READ_SHAPES[shape]
    cols, idx = _read_inputs(_rng(shape, idxs), (), n, s, kinds, idxs)
    _assert_same(jax.jit(read_cols)(idx, cols),
                 jax.jit(_index_per_column)(idx, cols))


@pytest.mark.parametrize("shape", list(READ_SHAPES) + list(READ_CELL_SHAPES))
def test_read_cols_under_the_pods_vmap(shape):
    n, s, kinds = {**READ_SHAPES, **READ_CELL_SHAPES}[shape]
    batch = (2, 2) if n * s > ONEHOT_PAIRS // 16 else (4, 3)
    cols, idx = _read_inputs(_rng(shape, "vmap"), batch, n, s, kinds,
                             "edge_clipped")
    both = lambda f: jax.jit(jax.vmap(jax.vmap(f)))  # noqa: E731
    _assert_same(both(read_cols)(idx, cols),
                 both(_index_per_column)(idx, cols))


def _reads_by_matmul(n, s, kinds):
    cols, idx = _read_inputs(_rng("traced"), (), n, s, kinds, "repeats")
    return "dot_general" in str(jax.make_jaxpr(read_cols)(idx, cols))


@pytest.mark.parametrize("shape", list(READ_SHAPES) + list(READ_CELL_SHAPES))
def test_read_cols_takes_the_matmul_up_to_the_pair_bound(shape):
    """Every read a cell times is one product, Mencius's execution
    order AT the bound among them; one pair more reads a column a
    gather."""
    n, s, kinds = {**READ_SHAPES, **READ_CELL_SHAPES}[shape]
    assert _reads_by_matmul(n, s, kinds) == (shape != "pairs_past_the_bound")
    assert (n * s <= ONEHOT_PAIRS) == (shape != "pairs_past_the_bound")


def test_the_default_server_reads_state_a_column_at_a_time():
    """16,384 slots x 4,096 inbox rows on XLA:CPU: every read by an
    inbox row's slot is the parent's gathers (PERF.md section 6, PR
    36)."""
    assert not _reads_by_matmul(*READ_DEFAULT_SERVER)
