"""On-device benchmark workload: counter-based PRNG proposal batches.

The device-resident consensus loop (parallel/sharded.py
``sharded_run_resident``) needs its client workload synthesized
*inside* the fused scan — zero host->device transfers in the steady
state — while staying bit-reproducible from a seed so bench runs stay
comparable across machines and sessions (ISSUE 8; the injection-policy
argument is "Paxos in the Cloud", arXiv 1404.6719: delivered consensus
performance is dominated by batching/injection, so the injector must
be cheap, deterministic, and out of the measured loop's way).

Design: Threefry-2x32 (Salmon et al., SC'11 — the same construction
behind ``jax.random.fold_in``), implemented here directly in 32-bit
lane ops rather than through ``jax.random`` so the *host mirror below
is byte-identical by construction* and the stream can never drift
under a jax upgrade. The PRNG is keyed on (seed, round) and countered
on (shard, row): any (round, shard, row) cell of the workload can be
regenerated independently — the property that lets the host injector
(``propose_batch_host``) reproduce the device stream exactly for the
host-in-the-loop runner (``sharded_run``) and the equivalence tests
(tests/test_workload.py).

Row format is the MsgBatch PROPOSE layout the host injector produces
(models/cluster.py ``Cluster.propose``): op=PUT, bounded keys
(uniform-key mode, reference client.go:68-103 karray), value from the
second Threefry lane, cmd_id = round*rows+row for exactly-once
auditing, client_id = shard.

Key schedule: a per-(shard, round) Threefry-random base plus an
odd-stride walk, masked into ``key_space`` — uniform across rounds but
DUPLICATE-FREE within a round (for rows <= key_space), like the mix
hash it replaces. This is deliberate: duplicate keys inside one exec
batch serialize the KV claim loop (measured 199 vs 122 ms/round at
the bench shape when ~9% of a round's keys collided — PERF.md), and a
workload generator must not smuggle a kernel pathology into the
headline number; key-conflict behavior is a knob for the TCP client's
``gen_workload(conflict_pct=...)``, not an accident of the PRNG.

Multi-owner stream (Mencius, ``owners`` below): every replica owns
slots and serves its OWN clients (the upstream client's ``-e`` mode,
round-robin over all replicas), so owner o of a shard is proposed rows
that are a pure function of (seed, round, shard, owner, row). The
Threefry counter gains the owner (``shard * R + owner``, row); keys
walk the owner's own range ``owner * keys_per_owner + walk`` with
``keys_per_owner = owner_key_range(key_space, R)``, a power of two —
owner-disjoint, the upstream client's default of 0 % conflicts, so a
key's final value is the last PUT of its one owner's stream whatever
interleaving the protocol picks; ``cmd_id = (round * rows + row) * R +
owner`` is unique across owners and ``client_id = shard * R + owner``.
The single-leader stream above is unchanged.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from minpaxos_tpu.models.minpaxos import MsgBatch
from minpaxos_tpu.wire.messages import MsgKind, Op

# Threefry-2x32 rotation schedule (two alternating groups of four).
_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
_PARITY = 0x1BD11BDA  # key-schedule parity constant

# odd multiplier (Knuth) for the within-round key walk: odd => the
# masked walk is a bijection on the power-of-two key space, so a
# round's keys are distinct whenever ext_rows <= key_space
_KEY_STRIDE = 2654435761


def owner_key_range(key_space: int, n_owners: int) -> int:
    """``keys_per_owner`` of the multi-owner stream: the largest power
    of two that gives each of ``n_owners`` owners a range of its own
    inside the power-of-two ``key_space`` (8,192 over 5 owners: 1,024
    each, keys 0..5,119)."""
    return key_space >> (n_owners - 1).bit_length()


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32: (key k0,k1) x (counter c0,c1) -> two uint32
    lanes, elementwise over broadcastable arrays. 20 rounds, the full
    recommended strength — the generator runs once per workload row
    per protocol round, nowhere near the step kernels' cost."""
    k0 = jnp.asarray(k0).astype(jnp.uint32)
    k1 = jnp.asarray(k1).astype(jnp.uint32)
    x0 = jnp.asarray(c0).astype(jnp.uint32)
    x1 = jnp.asarray(c1).astype(jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_PARITY))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in (_ROT_A if i % 2 == 0 else _ROT_B):
            x0 = x0 + x1
            x1 = (x1 << r) | (x1 >> (32 - r))
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def threefry2x32_host(k0, k1, c0, c1):
    """NumPy mirror of ``threefry2x32`` — the independent host
    reference the equivalence tests hold the device stream to, and the
    host injector's generator. Kept
    textually parallel to the jnp version on purpose; uint32 wraparound
    is the defined behavior, so the overflow warnings are silenced."""
    with np.errstate(over="ignore"):
        k0 = np.uint32(k0) * np.ones(1, np.uint32)
        k1 = np.uint32(k1) * np.ones(1, np.uint32)
        x0 = np.broadcast_to(c0, np.broadcast_shapes(
            np.shape(c0), np.shape(c1))).astype(np.uint32)
        x1 = np.broadcast_to(c1, x0.shape).astype(np.uint32)
        ks = (k0, k1, k0 ^ k1 ^ np.uint32(_PARITY))
        x0 = x0 + ks[0]
        x1 = x1 + ks[1]
        for i in range(5):
            for r in (_ROT_A if i % 2 == 0 else _ROT_B):
                x0 = x0 + x1
                x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
                x1 = x1 ^ x0
            x0 = x0 + ks[(i + 1) % 3]
            x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def workload_lanes(n_shards: int, ext_rows: int, round_idx, seed,
                   key_space: int = 1 << 20, hot_pct: int = 0,
                   hot_keys: int = 8, owners: int = 0):
    """(key, val) int32 lanes for ``round_idx`` — a scalar (one round,
    [G, M]) or a [k] vector (all of a fused dispatch's rounds at once,
    [k, G, M]). The fused runners pass the VECTOR form and hoist this
    out of the ``lax.scan`` body: Threefry is ~100 elementwise uint32
    ops, and traced per round on tiny [G, M] arrays the XLA-CPU
    per-op overhead alone cost ~40 ms per 8-round dispatch (measured,
    PERF.md) — batched over [k, G, M] the same ops amortize to noise.
    Both forms draw the identical stream (the round index participates
    elementwise), so hoisting cannot change a single byte.

    Values are raw Threefry lane 1; keys walk the bounded power-of-two
    ``key_space`` from a per-(shard, round) lane-0 base with an odd
    stride — distinct within a round (see module docstring).

    ``hot_pct`` (paxsoak's hot-key-skew knob): that percentage of
    rows redirect their key into the ``hot_keys`` lowest slots, drawn
    from an INDEPENDENT Threefry counter block (shard + n_shards) so
    the redirect decision never correlates with the value lane. The
    knob is Python-gated: at the default 0 the traced graph and the
    emitted stream are byte-identical to the pinned golden digests.

    ``owners`` (static; the replica count R, or 0): the multi-owner
    stream of the module docstring — lanes gain an owner axis,
    [G, R, M] or [k, G, R, M]."""
    # paxlint: disable=trace-hazard -- `owners` is a static int
    if owners:
        return _owner_lanes(n_shards, ext_rows, round_idx, seed, key_space,
                            hot_pct, owners)
    r = jnp.asarray(round_idx, jnp.int32)[..., None, None]
    b0, b1 = threefry2x32(seed, r,
                          jnp.arange(n_shards, dtype=jnp.int32)[:, None],
                          jnp.arange(ext_rows, dtype=jnp.int32)[None, :])
    colu = jnp.arange(ext_rows, dtype=jnp.uint32)
    key = ((b0[..., :1] + colu * jnp.uint32(_KEY_STRIDE))
           & jnp.uint32(key_space - 1)).astype(jnp.int32)
    if hot_pct:
        h0, h1 = threefry2x32(
            seed, r,
            jnp.arange(n_shards, dtype=jnp.int32)[:, None]
            + jnp.int32(n_shards),
            jnp.arange(ext_rows, dtype=jnp.int32)[None, :])
        redirect = (h0 % jnp.uint32(100)) < jnp.uint32(hot_pct)
        hot = (h1 % jnp.uint32(hot_keys)).astype(jnp.int32)
        key = jnp.where(redirect, hot, key)
    return key, b1.astype(jnp.int32)


def _owner_lanes(n_shards, ext_rows, round_idx, seed, key_space, hot_pct,
                 owners):
    """``workload_lanes`` of the multi-owner stream: [..., G, R, M]."""
    if hot_pct:
        raise ValueError("the multi-owner stream has no hot-key knob")
    r = jnp.asarray(round_idx, jnp.int32)[..., None, None, None]
    own = jnp.arange(owners, dtype=jnp.int32)[:, None]
    b0, b1 = threefry2x32(
        seed, r,
        jnp.arange(n_shards, dtype=jnp.int32)[:, None, None] * owners + own,
        jnp.arange(ext_rows, dtype=jnp.int32))
    per_owner = owner_key_range(key_space, owners)
    walk = ((b0[..., :1]
             + jnp.arange(ext_rows, dtype=jnp.uint32) * jnp.uint32(_KEY_STRIDE))
            & jnp.uint32(per_owner - 1)).astype(jnp.int32)
    return own * per_owner + walk, b1.astype(jnp.int32)


def _propose_rows(active, key, val, cmd_id, client_id) -> MsgBatch:
    """PUT PROPOSE rows where ``active``; every argument [G, R, M] or
    broadcastable to it."""
    z = jnp.zeros(active.shape, jnp.int32)
    return MsgBatch(
        kind=jnp.where(active, int(MsgKind.PROPOSE), 0).astype(jnp.int32),
        src=jnp.full(active.shape, -1, jnp.int32),
        ballot=z,
        inst=z,
        last_committed=z,
        op=jnp.where(active, int(Op.PUT), 0).astype(jnp.int32),
        key_hi=z,
        key_lo=jnp.where(active, key, 0),
        val_hi=z,
        val_lo=jnp.where(active, val, 0),
        cmd_id=jnp.where(active, cmd_id, 0),
        client_id=jnp.where(active, client_id, 0),
    )


def assemble_batch(n_replicas: int, n_shards: int, ext_rows: int,
                   count, leader, round_idx, key, val) -> MsgBatch:
    """One round's [G, R, M] PROPOSE rows from precomputed key/val
    lanes. [G, M] lanes are the single-leader stream: ``count`` rows
    per shard are live, addressed to ``leader``. [G, R, M] lanes are
    the multi-owner stream (Mencius; a trace-time choice by the lanes'
    rank): each owner is proposed its own rows, ``count`` of them
    (a scalar, or [R] with one count per owner — an owner at 0 idles),
    every owner when leader < 0, else owner ``leader`` alone. Cheap by
    construction (~10 broadcast selects), so it is the only workload
    code traced inside the scan body."""
    g, r, m = n_shards, n_replicas, ext_rows
    shard = jnp.arange(g, dtype=jnp.int32)[:, None, None]
    rep = jnp.arange(r, dtype=jnp.int32)[None, :, None]
    col = jnp.arange(m, dtype=jnp.int32)[None, None, :]
    if key.ndim == 3:
        active = jnp.broadcast_to(
            ((rep == leader) | (leader < 0))
            & (col < jnp.reshape(count, (1, -1, 1))), (g, r, m))
        return _propose_rows(active, key, val,
                             (round_idx * m + col) * r + rep,
                             shard * r + rep)
    active = jnp.broadcast_to(
        ((rep == leader) | (leader < 0)) & (col < count), (g, r, m))
    z = jnp.zeros((g, r, m), jnp.int32)
    return MsgBatch(
        kind=jnp.where(active, int(MsgKind.PROPOSE), 0).astype(jnp.int32),
        src=jnp.full((g, r, m), -1, jnp.int32),
        ballot=z,
        inst=z,
        last_committed=z,
        op=jnp.where(active, int(Op.PUT), 0).astype(jnp.int32),
        key_hi=z,
        key_lo=jnp.where(active, key[:, None, :], 0),
        val_hi=z,
        val_lo=jnp.where(active, val[:, None, :], 0),
        cmd_id=jnp.where(active, round_idx * m + col, 0),
        client_id=jnp.where(active, shard, 0),
    )


def propose_batch(n_replicas: int, n_shards: int, ext_rows: int,
                  count, leader, round_idx, seed,
                  key_space: int = 1 << 20, hot_pct: int = 0,
                  hot_keys: int = 8, owners: bool | None = None) -> MsgBatch:
    """[G, R, M] PROPOSE rows for one protocol round, generated on
    device (``workload_lanes`` + ``assemble_batch``). ``key_space``
    must be a power of two and at or below half the KV capacity so
    long runs don't saturate the table. ``hot_pct``/``hot_keys``:
    the Python-gated hot-key-skew knob (see ``workload_lanes``).
    ``owners`` (static): the multi-owner stream; None reads it off a
    concrete ``leader < 0`` (a caller that traces ``leader`` says).

    Pure jnp: callers jit it directly (parallel/sharded.py
    ``make_propose_ext``) or trace it inside a fused scan."""
    if owners is None:
        # paxlint: disable=trace-hazard -- a concrete leader only (a traced one raises: its caller passes `owners`)
        owners = bool(leader < 0)
    key, val = workload_lanes(n_shards, ext_rows, round_idx, seed,
                              key_space, hot_pct=hot_pct,
                              hot_keys=hot_keys,
                              owners=n_replicas if owners else 0)
    return assemble_batch(n_replicas, n_shards, ext_rows, count, leader,
                          round_idx, key, val)


def propose_batch_host(n_replicas: int, n_shards: int, ext_rows: int,
                       count: int, leader: int, round_idx: int, seed: int,
                       key_space: int = 1 << 20, hot_pct: int = 0,
                       hot_keys: int = 8,
                       owners: bool | None = None) -> MsgBatch:
    """The host injector: NumPy twin of ``propose_batch``, row-for-row
    and byte-for-byte identical from the same (seed, round), in both
    streams (``owners`` as there; ``count`` may be [R] in the
    multi-owner one). The reference the on-device generator is proven
    against, and what a host replay of a resident run draws
    (chip_smoke.py, the benchmark's references)."""
    g, r, m = n_shards, n_replicas, ext_rows
    if leader < 0 if owners is None else owners:
        return _propose_batch_owners_host(g, r, m, count, leader, round_idx,
                                          seed, key_space, hot_pct)
    shard = np.arange(g, dtype=np.int32)[:, None, None]
    rep = np.arange(r, dtype=np.int32)[None, :, None]
    col = np.arange(m, dtype=np.int32)[None, None, :]
    active = np.broadcast_to(
        ((rep == leader) | (leader < 0)) & (col < count), (g, r, m))
    b0, b1 = threefry2x32_host(seed, round_idx,
                               np.arange(g, dtype=np.int32)[:, None],
                               np.arange(m, dtype=np.int32)[None, :])
    with np.errstate(over="ignore"):
        colu = np.arange(m, dtype=np.uint32)[None, :]
        key = ((b0[:, :1] + colu * np.uint32(_KEY_STRIDE))
               & np.uint32(key_space - 1)).astype(np.int32)[:, None, :]
        if hot_pct:
            h0, h1 = threefry2x32_host(
                seed, round_idx,
                np.arange(g, dtype=np.int32)[:, None] + np.int32(g),
                np.arange(m, dtype=np.int32)[None, :])
            redirect = (h0 % np.uint32(100)) < np.uint32(hot_pct)
            hot = (h1 % np.uint32(hot_keys)).astype(np.int32)
            key = np.where(redirect, hot, key[:, 0, :])[:, None, :]
    val = b1.astype(np.int32)[:, None, :]
    z = np.zeros((g, r, m), np.int32)
    with np.errstate(over="ignore"):
        cmd = np.int32(round_idx) * np.int32(m) + col
    return MsgBatch(
        kind=np.where(active, np.int32(int(MsgKind.PROPOSE)), z),
        src=np.full((g, r, m), -1, np.int32),
        ballot=z,
        inst=z,
        last_committed=z,
        op=np.where(active, np.int32(int(Op.PUT)), z),
        key_hi=z,
        key_lo=np.where(active, np.broadcast_to(key, (g, r, m)), z),
        val_hi=z,
        val_lo=np.where(active, np.broadcast_to(val, (g, r, m)), z),
        cmd_id=np.where(active, np.broadcast_to(cmd, (g, r, m)), z),
        client_id=np.where(active, np.broadcast_to(shard, (g, r, m)), z),
    )


def _propose_batch_owners_host(g, r, m, count, leader, round_idx, seed,
                               key_space, hot_pct) -> MsgBatch:
    """``propose_batch_host`` of the multi-owner stream (the twin of
    ``_owner_lanes`` + ``assemble_batch``'s [G, R, M] branch)."""
    if hot_pct:
        raise ValueError("the multi-owner stream has no hot-key knob")
    shard = np.arange(g, dtype=np.int32)[:, None, None]
    rep = np.arange(r, dtype=np.int32)[None, :, None]
    col = np.arange(m, dtype=np.int32)[None, None, :]
    active = np.broadcast_to(
        ((rep == leader) | (leader < 0))
        # paxlint: disable=trace-hazard -- host-side twin (NumPy by design)
        & (col < np.reshape(np.asarray(count, np.int32), (1, -1, 1))),
        (g, r, m))
    b0, b1 = threefry2x32_host(seed, round_idx, shard * r + rep, col)
    per_owner = owner_key_range(key_space, r)
    with np.errstate(over="ignore"):
        walk = ((b0[..., :1] + col.astype(np.uint32) * np.uint32(_KEY_STRIDE))
                & np.uint32(per_owner - 1)).astype(np.int32)
        cmd = (np.int32(round_idx) * np.int32(m) + col) * np.int32(r) + rep
    z = np.zeros((g, r, m), np.int32)
    return MsgBatch(
        kind=np.where(active, np.int32(int(MsgKind.PROPOSE)), z),
        src=np.full((g, r, m), -1, np.int32),
        ballot=z,
        inst=z,
        last_committed=z,
        op=np.where(active, np.int32(int(Op.PUT)), z),
        key_hi=z,
        key_lo=np.where(active, rep * per_owner + walk, z),
        val_hi=z,
        val_lo=np.where(active, b1.astype(np.int32), z),
        cmd_id=np.where(active, cmd, z),
        client_id=np.where(active, shard * r + rep, z),
    )
