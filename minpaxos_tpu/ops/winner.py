"""Scatter-free slot updates: pick one winning inbox row per window
slot, then FETCH that row once.

The protocol step's hot sections (models/minpaxos.py 1c/2/3/5,
models/mencius.py 1/2/6/7b, models/cluster.py _route) each write ~10
message columns into per-slot arrays. Written as ten independent
``at[tgt].set`` scatters, XLA:TPU lowers each to a serialized
per-update loop — and under the [G, R] vmap of the sharded bench that
serialization multiplies out to tens of millions of scattered rows per
round (measured: ~674 ms/round at the 131k-instance rung, BENCH round
5). The rewrite here pays ONE small scatter (max of row index per
slot) and turns every column write into a dense read by that index.

Semantics preserved: sections already dedupe multi-row slot conflicts
by max ballot before writing (minpaxos.py ``ab_max``/``vb_max``); among
equal-priority rows the highest row index wins deterministically (the
old per-column scatters picked an unspecified duplicate — this is
strictly tighter).

``gather_row`` reads ONE column by the winners, an element gather of a
window-sized result. Where a pass writes several columns from the one
winning row, ``gather_cols`` reads them all at once (PR 34): the TPU
gathers elements at some 10 ns apiece, so seven to nine ``gather_row``s
a pass were the whole of ``px.slot_write_a`` / ``_b`` (114.9 ms of
``pod128_steady``'s 375.8 ms round) and of Mencius's ``px.propose``,
``px.commit_rows`` and most of ``px.accept`` (74 ms of 165.7; ledger
and PERF.md section 5, PR 33). It returns what ``gather_row`` returns
for each column, bit for bit (tests/test_winner.py).

How: SELECT BY A ONE-HOT MATMUL. The columns are split into their four
bytes, a ``bfloat16`` plane each (0..255 is exact there); a pass
compares every slot's winner with every row index, and the MXU
multiplies that 0/1 plane into the byte planes with a ``float32``
accumulator. One row matches a slot, so every sum is one byte times
one: exact, sign bits included, and the bytes are shifted back
together. A pass names the columns it needs and pays for no other.

Device time of one pass on a v5e, isolated, ms (my chip runs, PR 34;
``tools/scatter_micro.py slotwrite``; [G, R] x slots from M kernel
rows (the tier's inbox rows + the round's proposal rows) x columns;
the byte split and the selects under ``hit`` included; A and B are
MinPaxos's two slot writes, propose and accept Mencius's sections):

====================================  ========  ======  =====  =======  ======
shape                                 elements  gather  plane  compare  matmul
====================================  ========  ======  =====  =======  ======
pod128 A small [128,5]x1024<-640x9       72.73    4.77   5.63     3.15    0.97
pod128 B small [128,5]x1024<-640x8       64.65    4.49   5.01     2.90    0.92
pod128 A full [128,5]x1024<-1408x9       80.86    4.86   5.72     5.83    1.27
pod128 B full [128,5]x1024<-1408x8       72.64    4.57   5.09     5.08    1.29
propose small [16,5]x4096<-1216x7        28.28    2.66   2.92     2.38    0.37
accept small [16,5]x4096<-1216x8         32.32    3.64   3.90     2.62    0.51
propose full [16,5]x4096<-2112x7         28.28    2.69   2.95     3.73    0.61
accept full [16,5]x4096<-2112x8          32.33    7.60   4.74     4.15    0.75
served3 A [3]x2048<-1024x9                0.50    0.05   0.05     0.04    0.03
====================================  ========  ======  =====  =======  ======

``elements`` is the parent's gather a column (in the round each reads
6.67 ms, its output in the fast memory space; isolated 8.1); ``gather``
one gather of the stacked columns (169-694 MB of temporaries at
Mencius's shapes, where XLA pads the fetched plane to 128 lanes);
``plane`` a row fetch from ``[M, 128]`` behind an
``optimization_barrier`` (without one XLA folds pad, fetch and lane
slices into the same single gather); ``compare`` a
``where(win == m, col, 0).sum()`` a column; ``matmul`` what
``gather_cols`` does: all five call shapes take it, and a pass is
50-75 times faster than the parent's.

What the matmul costs in memory, as read (the same runs): XLA:TPU
fuses the compare into the matmul's operand at Mencius's shapes and at
the served one (0 B of temporaries), and materialises part of the
[slots, rows] plane at [128, 5] (87-112 MB at the pod128 shapes: 98 MB
at 640 rows x 9, growing with the rows, 297 MB at 4,096, 499 MB at
6,144); the peak of the whole pod128 round rose by 7.1 MB. Its time
grows with the rows too (0.04 ms a hundred at [128, 5] x 1,024 slots),
an element gather's does not: [128,5]x1024<-4096x9 reads 3.70 ms and
<-6144x9 5.95 against 17.6-17.8 for one stacked gather;
[16,5]x4096<-4096x8 1.76 against 2.26, and <-8192x8 2.96 against 2.32.

On XLA:CPU the plane is real memory, 2 B a pair of slot and row, and
the product runs on no MXU: one served step (``_packed_step``, k = 1,
three replicas' configuration, an empty inbox; this sandbox's CPU,
PR 34) takes 0.93 ms at the parent and 1.75 ms through the matmul at
the served cells' shape (2,048 slots x 1,024 rows), and at the
distributed mode's default (``cli/server.py``: 16,384 x 4,096, which
README runs on the CPU) 2.15 ms at the parent and 41.7 ms through the
matmul: two 134 MB planes and 2.4 GMAC a pass. Hence ``ONEHOT_PAIRS``:
a pass of more than 2**24 pairs (a 32 MB plane) reads its columns one
``gather_row`` each, as the parent did. Every cell's call shape is
inside it (the largest, Mencius's full tier, has 8.7 M pairs), so is
every shape the chip measured the matmul as the faster at, and the one
it measured as the slower (33.6 M pairs; 35.4 ms there now, the
parent's eight gathers) is outside; the default server's 67 M pairs
are outside too, and its step is the parent's. The bound is the CPU's:
on the chip the matmul beats a gather a column on either side of it.
"""

from __future__ import annotations

import jax.numpy as jnp

# a pass of more slot x row pairs than this reads its columns one
# gather_row each: on XLA:CPU the one-hot plane is real memory
ONEHOT_PAIRS = 1 << 24


def slot_winner(size: int, rel, ok):
    """Per-slot winning row: ``win[s]`` = max row index among rows with
    ``ok`` whose target is slot ``rel`` (-1 if none), plus ``hit`` mask.

    One [M]-row scatter-max into a [size+1] i32 array (row ``size``
    absorbs masked-off rows).
    """
    m = ok.shape[0]
    rows = jnp.arange(m, dtype=jnp.int32)
    win = jnp.full(size + 1, -1, jnp.int32).at[
        jnp.where(ok, rel, size)].max(rows, mode="drop")[:size]
    return win, win >= 0


def _select(hit, picked, old):
    if picked.dtype != old.dtype:
        picked = picked.astype(old.dtype)
    return jnp.where(hit, picked, old)


def gather_row(win, hit, col, old):
    """new[s] = col[win[s]] where hit else old[s] — a dense gather."""
    return _select(hit, col[jnp.clip(win, 0)], old)


def _fetch(win, cols):
    """``int32[columns, S]``: row ``win[s]`` of every ``int32[M]``
    column, by a one-hot matmul over the columns' bytes."""
    m = cols[0].shape[0]
    # the four bytes of every column as planes of their own: 0..255 is
    # exact in bfloat16, sign bits ride in the top byte
    cols = jnp.stack(cols)  # [columns, M]
    planes = jnp.stack([(cols >> sh) & 0xFF for sh in (0, 8, 16, 24)],
                       axis=1).reshape(-1, m).astype(jnp.bfloat16)
    # exactly one row matches a slot's index, so each sum is one byte
    # times one: exact in the MXU's float32 accumulator
    onehot = (win[:, None] == jnp.arange(m, dtype=jnp.int32)[None, :]
              ).astype(jnp.bfloat16)
    got = jnp.einsum("pm,sm->ps", planes, onehot,
                     preferred_element_type=jnp.float32
                     ).astype(jnp.int32).reshape(-1, 4, win.shape[0])
    return got[:, 0] | (got[:, 1] << 8) | (got[:, 2] << 16) | (got[:, 3] << 24)


def gather_cols(win, hit, cols, olds):
    """``gather_row(win, hit, col, old)`` for each of the ``int32[M]``
    columns ``cols``, from ONE fetch of the winning row."""
    s, m = win.shape[0], cols[0].shape[0]
    if s * m > ONEHOT_PAIRS:
        return tuple(gather_row(win, hit, c, o) for c, o in zip(cols, olds))
    got = _fetch(jnp.clip(win, 0, m - 1), cols)
    return tuple(_select(hit, picked, old) for picked, old in zip(got, olds))


def gather_const(hit, value, old):
    """new[s] = value where hit else old[s] (constant-fill variant)."""
    return jnp.where(hit, jnp.asarray(value, old.dtype), old)
