"""A Mencius owner that returns after a takeover, over real localhost TCP
(ROADMAP M9; the pod's case, after a revocation, is
tests/test_mencius_revoke.py).

Owner 2 of a durable N=3 cluster is killed while the others serve: its
turns block the merged frontier until its successor's takeover sweep
fills them. A served replica never begins a revocation
(``models/mencius.py mencius_served_step_impl``: its stable store keeps
no accepted range), so the sweep is the whole takeover here, and an
acceptor restarted from its store in the middle of it forgets nothing
the takeover needs. Owner 2 is restarted from its durable store and
heals to the frontier; then clients write through all three owners, owner
2 among them. Its commands have to commit, each exactly once, and read
back through owner 0: a command it put into a turn its peers filled
with a no-op would be answered by it and missing everywhere else. And
every replica, once it has executed to the common frontier, holds each
of those writes in its table.
"""

from __future__ import annotations

import time

import numpy as np

from minpaxos_tpu.models.minpaxos import NO_BALLOT
from minpaxos_tpu.runtime.client import MultiClient, gen_workload
from minpaxos_tpu.wire.messages import Op
from test_distributed import Harness


def _wait_frontier(h, i, target, timeout_s=30.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if h.servers[i].snapshot["frontier"] >= target:
            return True
        time.sleep(0.1)
    return False


def test_mencius_tcp_revived_owner_after_a_takeover(tmp_path):
    h = Harness(tmp_path, mencius=True, durable=True)
    every = list(h.servers.values())
    try:
        cli = h.client()
        ops, keys, vals = gen_workload(200, seed=39)
        assert cli.run_workload(ops, keys, vals, timeout_s=60)["acked"] == 200
        h.kill(2)
        cli.replies.clear()
        # a short outage: the log stays within the victim's retention,
        # so it heals from its peers' catch-up rows once it is back
        ops, keys, vals = gen_workload(100, seed=40)
        stats = cli.run_workload(ops, keys, vals, timeout_s=90)
        assert stats["acked"] == 100, stats  # commits with owner 2 dead
        # an acceptor restarted from its store while the owner is dead
        h.kill(1)
        h.start_replica(1)
        every.append(h.servers[1])
        ops, keys, vals = gen_workload(100, seed=41)
        stats = cli.run_workload(ops, keys, vals, timeout_s=90)
        assert stats["acked"] == 100, stats
        cli.close_conn()
        h.start_replica(2)
        every.append(h.servers[2])
        assert _wait_frontier(h, 2, h.servers[0].snapshot["frontier"],
                              timeout_s=90), (
            h.servers[2].snapshot)
        # every owner serves a third of the writes, owner 2 included
        multi = MultiClient(("127.0.0.1", h.mport), check=True, mode="rr")
        n = 600
        ops = np.full(n, int(Op.PUT), np.int64)
        keys = np.arange(n, dtype=np.int64) + 1000  # each written once
        vals = np.arange(n, dtype=np.int64) + 7000
        stats = multi.run_workload(ops, keys, vals, timeout_s=150)
        assert stats["acked"] == n and stats["duplicates"] == 0, stats
        assert h.servers[2].stats["client_proposals"] > 0
        # the revived owner's writes (command i went to owner i % 3)
        # read back through owner 0, under command ids of their own (a
        # reader's ids equal to a writer's would be answered as that
        # writer's command by exactly-once bookkeeping keyed on them)
        mine = np.arange(2, n, 3)
        reader = h.client()
        ids = mine + n
        gets = reader.run_partition(
            ids, np.full(2 * n, int(Op.GET), np.int64), np.tile(keys, 2),
            np.zeros(2 * n, np.int64), timeout_s=60)
        assert gets["acked"] == len(mine), gets
        got = {int(keys[i]): reader.replies[int(i) + n]["val"] for i in mine}
        assert got == {int(keys[i]): int(vals[i]) for i in mine}
        reader.close_conn()
        for c in multi.clients:
            c.close_conn()
        # every replica executes to one frontier ...
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snaps = [h.servers[i].snapshot for i in range(3)]
            if len({(x["frontier"], x["executed"]) for x in snaps}) == 1:
                break
            time.sleep(0.1)
        assert len({(x["frontier"], x["executed"]) for x in snaps}) == 1, snaps
    finally:
        h.stop()
    # ... and its table holds every acknowledged write of the last phase
    for i in range(3):
        kv = h.servers[i].state.kv
        live = np.asarray(kv.slot) == 1
        held = dict(zip(
            (np.asarray(kv.key_lo)[live].astype(np.int64) & 0xFFFFFFFF
             | np.asarray(kv.key_hi)[live].astype(np.int64) << 32).tolist(),
            (np.asarray(kv.val)[live, 1].astype(np.int64) & 0xFFFFFFFF
             | np.asarray(kv.val)[live, 0].astype(np.int64) << 32).tolist()))
        assert {int(k): held.get(int(k)) for k in keys} == {
            int(k): int(v) for k, v in zip(keys, vals)}, i
    # no served replica, in any of its lives, began or took part in a
    # revocation: nothing of one is in a device state the store forgets
    for s in every:
        assert int(s.state.rk_at) == -1
        assert (np.asarray(s.state.rp_bal) == NO_BALLOT).all()
        assert (np.asarray(s.state.rv_bal) == NO_BALLOT).all()
