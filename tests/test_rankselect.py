"""``ops/rankselect.py``: the integers ``jnp.searchsorted`` gives, from
vector compares (PR 29).

The rank-select took the place of ``jnp.searchsorted(count, want)`` in
the routing plan (ops/segscatter.py ``plan_slots``) and in Mencius's
propose (models/mencius.py section 1), so it must return that call's
result ELEMENT FOR ELEMENT: every inbox, log and table downstream is
pinned byte for byte (tests/test_route_fabric.py,
tests/test_kernel_golden.py, tests/test_mencius*.py, which run through
it unchanged). Here the primitive alone, over both of its formulations
and the edges a route can hand it: plateaus, an empty count, ranks
beyond the total and beyond the rows, lengths that are no multiple of
a block or of a chunk.

And its working set: compiled for the chip (the TPU's compiler is
installed where the tests run; nothing executes), one group of
``pod128_steady``'s route holds no [slots, rows] plane.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minpaxos_tpu.ops import rankselect
from minpaxos_tpu.ops.rankselect import BLOCK, CHUNK, SHORT_ROWS, rank_select

I32 = np.iinfo(np.int32)

# [R, rows] x ranks. One group of each cell's shapes: the issue's
# reckoning of the pooled rows (5 x 1,856 and the full tier's 5 x
# 2,624) and what eval_shape gives for benchmarks/configs/*.json
# (5 x 1,729 / 2,497 MinPaxos, 5 x 1,793 / 2,689 Mencius; the propose
# count is the kernel's rows, 1,216 / 2,112).
CELL_SHAPES = {
    "pod128_route_small_issue": (5, 9280, 512),
    "pod128_route_full_issue": (5, 13120, 1280),
    "pod128_route_small": (5, 8645, 512),
    "pod128_route_full": (5, 12485, 1280),
    "mencius64k_propose_small": (5, 1216, 4096),
    "mencius64k_propose_full": (5, 2112, 4096),
    "mencius64k_route_small": (5, 8965, 1152),
    "mencius64k_route_full": (5, 13445, 2048),
}
# toy shapes of the tier-1 suites, and every edge of the two static
# choices (SHORT_ROWS picks the formulation, BLOCK and CHUNK shape the
# blocked one): slots can exceed the pooled rows there
TOY_SHAPES = {
    "toy_slots_over_rows": (5, 40, 96),
    "toy_one_row": (3, 1, 7),
    "toy_no_rank": (2, 33, 0),
    "short_exactly": (2, SHORT_ROWS, 300),
    "blocked_first": (2, SHORT_ROWS + 1, 300),
    "blocked_whole_blocks": (2, SHORT_ROWS + BLOCK, CHUNK),
    "blocked_ragged_chunk": (2, SHORT_ROWS + 77, CHUNK + 1),
    "blocked_many_chunks": (1, SHORT_ROWS + 5, 3 * CHUNK + 19),
}
SHAPES = {**TOY_SHAPES, **CELL_SHAPES}

COUNTS = ("mask", "plateaus", "all_zero", "every_row", "any_int32")


def _count(kind: str, rng, g: int, r: int, n: int, q: int) -> np.ndarray:
    """Nondecreasing int32 [g, r, n]."""
    if kind == "mask":  # a route's: the running count of a 0/1 mask,
        # its total about 0.6 of the slots (ranks beyond it unfilled)
        c = np.cumsum(rng.random((g, r, n)) < min(1.0, 0.6 * q / n), -1)
    elif kind == "plateaus":  # long flat runs, then jumps
        c = np.cumsum((rng.random((g, r, n)) < 0.01)
                      * rng.integers(1, 40, (g, r, n)), -1)
    elif kind == "all_zero":
        c = np.zeros((g, r, n))
    elif kind == "every_row":  # rises every row: total == rows
        c = np.broadcast_to(np.arange(1, n + 1), (g, r, n))
    else:  # the whole int32 range, both ends present
        c = np.sort(rng.integers(I32.min, I32.max, (g, r, n),
                                 endpoint=True), -1)
        c[..., :1], c[..., -1:] = I32.min, I32.max
    return c.astype(np.int32)


def _want(kind: str, rng, g: int, r: int, q: int, top: int) -> np.ndarray:
    if kind == "any_int32":
        w = rng.integers(I32.min, I32.max, (g, r, q), endpoint=True)
        w[..., :2] = np.array([I32.max, I32.min])[:q]
        return w.astype(np.int32)
    # half the lanes as plan_slots asks (1..slots, beyond the total and
    # beyond the rows where slots exceed them), half at random around
    # the count's range, below 1 included (unsorted: the primitive does
    # not lean on the order of its ranks)
    w = np.broadcast_to(np.arange(1, q + 1), (g, r, q)).copy()
    w[:, 1::2] = rng.integers(-3, top + 5, w[:, 1::2].shape)
    return w.astype(np.int32)


@pytest.mark.parametrize("kind", COUNTS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_equals_searchsorted_under_vmap(shape, kind):
    """Element for element what ``jnp.searchsorted(c, want)`` returns,
    as int32, under ``vmap`` over [G, R]."""
    r, n, q = SHAPES[shape]
    g = 2
    rng = np.random.default_rng(zlib.crc32(f"{shape}/{kind}".encode()))
    count = _count(kind, rng, g, r, n, q)
    want = _want(kind, rng, g, r, q, int(count.max()))
    assert (np.diff(count.astype(np.int64), axis=-1) >= 0).all()
    got = jax.jit(jax.vmap(jax.vmap(rank_select)))(count, want)
    ref = jax.jit(jax.vmap(jax.vmap(jnp.searchsorted)))(count, want)
    assert got.dtype == jnp.int32 and got.shape == (g, r, q)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    if kind in ("mask", "all_zero") and q:
        # what plan_slots reads from it: rank 1 + total is unreached
        total = count[..., -1]
        beyond = np.asarray(jax.vmap(jax.vmap(rank_select))(
            count, (total + 1)[..., None]))
        assert (beyond == n).all()


@pytest.mark.parametrize("rows", [SHORT_ROWS, SHORT_ROWS + 1])
def test_both_formulations_are_reached(rows, monkeypatch):
    """The static choice: the blocked search runs beyond SHORT_ROWS
    rows and only there (so the cases above cover both sides)."""
    calls = []
    real = rankselect._blocked
    monkeypatch.setattr(rankselect, "_blocked",
                        lambda c, w: calls.append(c.shape) or real(c, w))
    rank_select(jnp.zeros(rows, jnp.int32), jnp.ones(4, jnp.int32))
    assert calls == ([(rows,)] if rows > SHORT_ROWS else [])


# ------------------------------------------------ bounded working set

@pytest.fixture(scope="module")
def one_chip():
    """A described v5e (nothing attached, nothing runs): what the
    chip's compiler does with the program. Only inside a fixture: a
    module that loads the TPU's library at import breaks collection
    under several workers."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache and cannot be read back without a chip (it warns, then
    compiles again): keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


#: One group's [slots, rows] plane of ``pod128_steady``'s small route
#: as bytes of int32 is 5 x 512 x 8,645 x 4 = 88.5 MB (a ``pred``
#: plane a quarter of it), the full route's 319.6 MB, and one group's
#: unchunked [slots, 128] block fetch 1.3 / 3.3 MB. The chip's
#: compiler reports 0 B of temporaries for both (PR 29); 1 MiB holds
#: every one of those planes out, with room for a scratch buffer.
TEMP_LIMIT = 1 << 20


@pytest.mark.parametrize("cell", ["pod128_route_small", "pod128_route_full"])
def test_working_set_is_bounded_on_the_chip(cell, one_chip, no_compile_cache):
    r, n, q = CELL_SHAPES[cell]
    count = jax.ShapeDtypeStruct((r, n), jnp.int32, sharding=one_chip)
    want = jax.ShapeDtypeStruct((q,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.vmap(rank_select, in_axes=(0, None))
                       ).lower(count, want).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert r * q * BLOCK * 4 > TEMP_LIMIT  # the limit can tell
    assert temp <= TEMP_LIMIT, (cell, temp)
