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

``read_cols`` (PR 36) is the same select with slots and rows
exchanged: where a section reads several WINDOW columns of the state
at one index vector (``state.status[rel]``, ``state.key_hi[rel]``,
...), the index is the one-hot's row side and the window the side
summed over, and ``_fetch`` is already generic in which is which. The
steps call it once per index and version of the state:
models/minpaxos.py 1c and 2 (status and ballot before write A, then
``vb_max`` and ``ab_max`` as each is made; the post-PIR ballot follows
from the first two without a read), 2 and 2b (after write A, nine
columns for ``px.accept_ack`` and ``px.prepare_inst`` together), 7c
(catch-up's run of slots, seven columns), 7e (the sweep's chunk by
window slot) and 8 (the exec batch, seven); models/mencius.py 2
(ballot and status, ``ab_max``, then status and the seven payload
columns), 9 / 9b / 9c / 9d (the slots each announces: status and the
eight slot fields, 9c's two predicates with them) and 11 (six columns
by the execution order, a permutation of the window, and the exec
batch's seven); ops/ackruns.py (a row's run length by its run id, in
both protocols' ack sections). The window's columns come in
four widths, so a column is split into as many byte planes as it is
wide: ``bool`` and ``uint8`` one, ``uint16`` two, ``int32`` four; an
index may repeat (two ACCEPT rows of one slot, a clip's pile-up on an
edge), because every OUTPUT row still matches exactly one slot. The
same bound on the same observable, ``len(idx) * S`` pairs, and for the
same reason: Mencius's order x window at 4,096 slots is exactly 2**24
and inside it; the default server's reads by inbox row (4,096 x
16,384) are outside and stay gathers.

Device time of one read on a v5e, isolated, ms (my chip runs, PR 36;
``tools/scatter_micro.py stateread``; [G, R] x N index rows into S
slots x columns, a column's kind its dtype: i int32, b uint8, ? bool):

========================================  ========  =======  =========
shape                                     elements  stacked  read_cols
========================================  ========  =======  =========
pod128 status+ballot [128,5]x640<-1024 bi    10.74     3.40       0.50
pod128 vb_max [128,5]x640<-1024 i             5.03     5.03       0.37
pod128 ack+2b small x640<-1024 bibiiiiii     46.70     3.01       1.62
pod128 ack+2b full x1408<-1024 bibiiiiii    102.73     6.49       1.61
pod128 catchup x512<-1024 biiiiii (run)      31.23     2.32       1.29
pod128 exec x128<-1024 biiiiii (run)          7.04     0.66       0.68
pod128 sweep x1024<-64 ? (run)                7.57     6.69       0.04
pod128 run_len x640<-641 i                    4.18     4.18       0.23
mencius64k ballot+status x1216<-4096 ib       2.40     0.81       0.35
mencius64k dup small x1216<-4096 bbiiiiii     9.56     0.71       0.63
mencius64k dup full x2112<-4096 bbiiiiii     17.11     1.20       0.95
mencius64k order x4096<-4096 bbii?? (perm)   25.08     2.25       1.49
mencius64k exec x320<-4096 biiiiii            2.20     0.22       0.25
mencius64k 9c x128<-4096 bibiiiiii?i (run)    1.37     0.14       0.26
served3 ack+2b [3]x1024<-2048 bibiiiiii       0.29     0.05       0.02
mencius3 order [3]x4096<-4096 bbii??          0.76     0.13       0.06
========================================  ========  =======  =========

``elements`` is the parent's gather a column (in the round they read
4.05-5.05 ms apiece at pod128's 409,600 elements); ``stacked`` one
gather of the columns stacked as ``int32[columns, S]``. Temporaries:
0.0 MB everywhere but pod128's nine-column read (7.0 MB at 640 rows,
117.0 MB at 1,408); the round's peak rose by 29.7 MB in the MinPaxos
pod and 2.0 MB in the Mencius one. The table was read with a byte
split of one shift a column; ``_fetch`` now splits columns of one
width together, which XLA:CPU compiles in two thirds of the time and
the chip runs a little faster still (``px.accept_ack`` 1.70 -> 0.92
ms and ``px.catchup`` 1.13 -> 0.65 in ``pod128_steady``'s round). A
clipped run (catch-up's, the exec batch's) is also a slice of the
edge-padded column: under the [G, R] vmap that ``dynamic_slice`` has a
start per replica and lowers to a gather, 5.92 ms for catch-up, 5.74
for the exec batch, 0.85 for the sweep's chunk, 1.17 for Mencius's
9c: slower than ``read_cols`` at every one of them.
"""

from __future__ import annotations

import functools
import itertools

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


_BYTE_SHIFTS = (0, 8, 16, 24)


def _byte_planes(cols, width):
    """``int32[n * width, M]``: the bytes of ``n`` columns ``width``
    bytes wide, a column's low byte first."""
    stacked = jnp.stack([c.astype(jnp.int32) for c in cols])
    return jnp.stack([(stacked >> sh) & 0xFF
                      for sh in _BYTE_SHIFTS[:width]],
                     axis=1).reshape(-1, stacked.shape[-1])


def _from_byte_planes(planes, width):
    """``_byte_planes`` undone: ``int32[n * width, N]`` -> ``[n, N]``."""
    planes = planes.reshape(-1, width, planes.shape[-1])
    return functools.reduce(jnp.bitwise_or, [
        planes[:, j] << _BYTE_SHIFTS[j] for j in range(width)])


def _fetch(idx, cols):
    """``col[idx]`` for every ``[M]`` column of ``cols``, each in its
    own dtype, by ONE one-hot matmul over the columns' bytes; ``idx``
    lies in ``[0, M)``."""
    m = cols[0].shape[0]
    # a column's bytes as planes of their own, as many as it is wide
    # (bool and uint8 one, uint16 two, int32 four): 0..255 is exact in
    # bfloat16, sign bits ride in the top byte. Columns of one width
    # are split together (one shift a byte, not one a column: the
    # lowered step and its compile stay near the parent's)
    groups = [(w, [i for i, c in enumerate(cols) if c.dtype.itemsize == w])
              for w in (1, 2, 4)]
    groups = [(w, which) for w, which in groups if which]
    planes = jnp.concatenate([
        _byte_planes([cols[i] for i in which], w) for w, which in groups
    ]).astype(jnp.bfloat16)  # [planes, M]
    # exactly one source row matches an index, so each sum is one byte
    # times one: exact in the MXU's float32 accumulator
    onehot = (idx[:, None] == jnp.arange(m, dtype=jnp.int32)[None, :]
              ).astype(jnp.bfloat16)
    got = jnp.einsum("pm,sm->ps", planes, onehot,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    # each group's planes shifted back together, then every column in
    # its own place and dtype
    ends = itertools.accumulate(w * len(which) for w, which in groups)
    picked = {
        i: col
        for (w, which), e in zip(groups, ends)
        for i, col in zip(which, _from_byte_planes(
            got[e - w * len(which):e], w))}
    return tuple(picked[i].astype(c.dtype) for i, c in enumerate(cols))


def gather_cols(win, hit, cols, olds):
    """``gather_row(win, hit, col, old)`` for each of the ``int32[M]``
    columns ``cols``, from ONE fetch of the winning row."""
    s, m = win.shape[0], cols[0].shape[0]
    if s * m > ONEHOT_PAIRS:
        return tuple(gather_row(win, hit, c, o) for c, o in zip(cols, olds))
    got = _fetch(jnp.clip(win, 0, m - 1), cols)
    return tuple(_select(hit, picked, old) for picked, old in zip(got, olds))


def read_cols(idx, cols):
    """``col[idx]`` for each of the ``[S]`` columns ``cols`` (``int32``,
    ``uint16``, ``uint8`` or ``bool``; each result in its column's
    dtype), from ONE fetch by the index vector: the reads of STATE by
    an inbox row's slot, by a run of slots or by the execution order.
    ``idx`` is already clipped into ``[0, S)`` and may repeat: every
    OUTPUT row matches one source slot."""
    if idx.shape[0] * cols[0].shape[0] > ONEHOT_PAIRS:
        return tuple(c[idx] for c in cols)
    return _fetch(idx, cols)


def gather_const(hit, value, old):
    """new[s] = value where hit else old[s] (constant-fill variant)."""
    return jnp.where(hit, jnp.asarray(value, old.dtype), old)
