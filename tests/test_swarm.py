"""Concurrent-client swarm leg (ISSUE 15 CI satellite): a real
in-process TCP cluster driven by ClientSwarm's selector loop — many
concurrent closed-loop sessions multiplexed through the ingress
coalescer, every command acked exactly once.

The ~64-session leg rides tier-1; the 1k-session leg is `slow`. Neither adds a compiled
variant: the servers run the same step shapes every other distributed
test compiles.
"""

from __future__ import annotations

import time

import pytest

from minpaxos_tpu.models.minpaxos import MinPaxosConfig
from minpaxos_tpu.runtime.client import ClientSwarm, gen_workload
from minpaxos_tpu.runtime.master import Master, register_with_master
from minpaxos_tpu.runtime.replica import ReplicaServer, RuntimeFlags
from minpaxos_tpu.utils.netutil import CONTROL_OFFSET, free_ports

SMALL = dict(window=1 << 10, inbox=1024, exec_batch=512, kv_pow2=12,
             catchup_rows=64, recovery_rows=64)


class _Cluster:
    """Master + 3 in-process replicas (test_distributed's harness
    shape, local copy: test modules aren't importable packages)."""

    def __init__(self, tmp_path, n=3):
        self.mport = free_ports(1)[0]
        self.addrs = [("127.0.0.1", p) for p in
                      free_ports(n, sibling_offset=CONTROL_OFFSET)]
        self.master = Master("127.0.0.1", self.mport, n, ping_s=0.3)
        self.master.start()
        for host, port in self.addrs:
            register_with_master(("127.0.0.1", self.mport), host, port,
                                 timeout_s=5.0)
        cfg = MinPaxosConfig(n_replicas=n, **SMALL)
        self.servers = []
        for i in range(n):
            s = ReplicaServer(i, self.addrs, cfg,
                              RuntimeFlags(store_dir=str(tmp_path),
                                           tick_s=0.001))
            s.start()
            self.servers.append(s)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if self.servers[0].snapshot["prepared"]:
                return
            time.sleep(0.05)
        raise AssertionError("leader never prepared")

    def stop(self):
        for s in self.servers:
            s.stop()
        self.master.stop()


def _run_swarm(tmp_path, sessions: int, ops_per_session: int,
               timeout_s: float) -> tuple[dict, _Cluster]:
    c = _Cluster(tmp_path)
    try:
        n = sessions * ops_per_session
        ops, keys, vals = gen_workload(n, key_range=1000, seed=3)
        swarm = ClientSwarm(("127.0.0.1", c.mport), sessions=sessions)
        try:
            res = swarm.run(ops, keys, vals, ops_per_session,
                            timeout_s=timeout_s)
        finally:
            swarm.close()
        # coalescer evidence on the leader: parked-tick-loop wakeups
        # and drained multi-row batches (the counters paxtop's
        # COALESCE column reads)
        stats = c.servers[0].stats
        return {**res, "leader_stats": stats}, c
    except BaseException:
        c.stop()
        raise


def test_swarm_64_sessions_exactly_once(tmp_path):
    res, c = _run_swarm(tmp_path, sessions=64, ops_per_session=4,
                        timeout_s=60.0)
    try:
        assert res["acked"] == res["sent"] == 256, res
        assert res["dead_sessions"] == 0, res
        assert len(res["lat_ms_sorted"]) == 256
        st = res["leader_stats"]
        assert st.get("coalesce_wakeups", 0) > 0, st
        # a 64-way concurrent burst must actually coalesce: some
        # drained batch carried more than one client's rows
        hist = (c.servers[0].metrics.snapshot()
                .get("histograms") or {}).get("coalesce_batch_rows")
        assert hist and hist["count"] > 0, hist
    finally:
        c.stop()


@pytest.mark.slow
def test_swarm_1k_sessions_bounded_queueing(tmp_path):
    """1024 concurrent sessions: overload may engage the admission
    gate (counted rejects + client retransmits), but every command is
    still acked exactly once — bounded queueing, not tail blowup."""
    res, c = _run_swarm(tmp_path, sessions=1024, ops_per_session=2,
                        timeout_s=180.0)
    try:
        assert res["acked"] == res["sent"] == 2048, res
        assert res["dead_sessions"] == 0, res
        st = res["leader_stats"]
        assert st.get("coalesce_wakeups", 0) > 0, st
    finally:
        c.stop()
