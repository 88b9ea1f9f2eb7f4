"""The pod round's state transfer (models/minpaxos.py ``state_transfer``,
parallel/sharded.py ``transfer_round``): a follower that has fallen
below its leader's window is healed ON THE DEVICE, under load, with the
served path's snapshot semantics (runtime/replica.py
``_install_snapshot_pairs``).

The program without the mechanism is the same step under a wrapper that
declares no round section: what the pod ran before it existed, where a
follower dead for longer than ``retention`` slots stayed frozen for
good (``MinPaxosConfig.retention``'s note).
"""

import functools
import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import podstream
from benchmarks.lib.tables import dump_table
from minpaxos_tpu.models.cluster import tree_slice
from minpaxos_tpu.models.minpaxos import (
    MinPaxosConfig,
    replica_step_impl,
    state_transfer,
    transfer_bytes,
    transfer_needs,
)
from minpaxos_tpu.models.paxos import classic_config
from minpaxos_tpu.parallel import sharded

#: a toy of the kill / recover configuration's shape: catch-up 4p (at 2p
#: it only keeps pace with a full-rate stream), inbox p + 2 x catch-up +
#: 128, retention window / 2 = 4 rounds of proposals
KW = dict(n_replicas=5, window=128, inbox=272, exec_batch=16, kv_pow2=8,
          catchup_rows=64, recovery_rows=16)
CFG = MinPaxosConfig(**KW)
G, P, KEYS, SEED, VICTIM = 2, 16, 128, 5, 4


def parent_step(cfg, state, inbox, tick_inc=1, gates=None):
    """``replica_step_impl`` as the pod ran it before the transfer: the
    same step and the same ``px.retry`` gate, no round section."""
    return replica_step_impl(cfg, state, inbox, tick_inc, gates)


parent_step.recovery_gates = replica_step_impl.recovery_gates
parent_step.takes_gates = True


class Pod:
    """A ``ShardedCluster`` whose resident dispatches step ``step``."""

    def __init__(self, step=replica_step_impl, cfg=CFG):
        self.sc = sc = sharded.ShardedCluster(cfg, G, ext_rows=P,
                                              key_space=KEYS, seed=SEED)
        sc._step_impl = step
        sc._round_sections = tuple(sharded.round_sections(step))
        sc.elect(0)
        sc.begin_resident()
        self.loaded = []  # rounds of the stream that carried proposals

    def run(self, rounds, n=P):
        for _ in range(rounds):
            if n:
                self.loaded.append(self.sc._seed)
            out = self.sc.run_resident(1, n)
        return out

    def drain(self, limit=40):
        for i in range(limit):
            _, in_flight = self.run(1, 0)
            if in_flight == 0 and not self.disagreements():
                return i + 1
        return None

    def frontiers(self):
        st = self.sc.ss.states
        return np.asarray(st.committed_upto), np.asarray(st.executed_upto)

    def disagreements(self):
        upto, executed = self.frontiers()
        return int((upto != upto[:, :1]).sum() + (executed != upto).sum())

    def tables(self, g):
        kv = self.sc.ss.states.kv
        arrs = [np.asarray(x[g]) for x in kv[:4]]
        return [dump_table(*(a[r] for a in arrs))
                for r in range(self.sc.cfg.n_replicas)]

    def replay(self):
        want = podstream.replay(SEED, self.loaded, range(G), P, KEYS)
        return {g: {k: v & 0xFFFFFFFF for k, v in t.items()}
                for g, t in want.items()}


def outage(pod, dead_rounds, healthy=8, after=16):
    pod.run(healthy)
    pod.sc.kill(VICTIM)
    pod.run(dead_rounds)
    pod.sc.revive(VICTIM)
    pod.run(after)


def tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# -------------------------------------------------- (a) beyond retention

@pytest.mark.parametrize("protocol", ["minpaxos", "classic"])
def test_a_follower_dead_for_four_windows_rejoins_under_load(protocol):
    """Dead for 32 rounds = 512 slots = 4 windows = 8 x retention,
    revived under the full rate: one install a group, equal frontiers
    and executed prefixes on all five replicas, every replica's table
    equal to a host replay of the stream."""
    cfg = classic_config(**KW) if protocol == "classic" else CFG
    pod = Pod(cfg=cfg)
    outage(pod, dead_rounds=32)
    upto, _ = pod.frontiers()
    assert (upto[:, 0] - upto[:, VICTIM] <= cfg.window // 2).all(), (
        "the victim recovers under load, not in the drain", upto)
    assert pod.drain() is not None
    assert pod.disagreements() == 0
    want = pod.replay()
    for g in range(G):
        for r, table in enumerate(pod.tables(g)):
            assert table == want[g], (g, r)
    tiers = pod.sc.resident_tiers()
    assert tiers["state_transfers"] == G
    assert tiers["state_transfer_bytes"] == G * transfer_bytes(cfg)
    assert tiers["round_gates"] == {"px.state_transfer": 1}
    assert 0 < tiers["lagging_rounds"] < 16
    assert tiers["gates"] == {"px.retry": 0}  # the step's own: as it was
    from minpaxos_tpu import obs
    entry = obs.process_pods()[-1]
    assert {k: entry[k] for k in ("round_gates", "state_transfers",
                                  "state_transfer_bytes", "lagging_rounds")
            } == {k: tiers[k] for k in ("round_gates", "state_transfers",
                                        "state_transfer_bytes",
                                        "lagging_rounds")}
    assert int(np.asarray(pod.sc.ss.states.kv.dropped).sum()) == 0


def test_without_the_transfer_the_victim_stays_frozen_for_good():
    """The same schedule through the step that declares no round
    section, which is what the pod ran before: the victim's frontier
    never leaves the slot it died at (at the benchmark's rehearsal
    shape that end state read ``committed_upto`` [2431 2431 2431 2431
    319]), its table misses every later write, and nothing counts."""
    pod = Pod(parent_step)
    outage(pod, dead_rounds=32)
    assert pod.drain() is None
    upto, _ = pod.frontiers()
    assert (upto[:, VICTIM] < 8 * P).all()  # what 8 loaded rounds gave it
    assert (upto[:, :VICTIM] == upto[:, :1]).all()
    assert pod.disagreements() >= G
    assert pod.tables(0)[VICTIM] != pod.replay()[0]
    tiers = pod.sc.resident_tiers()
    assert "state_transfers" not in tiers and "round_gates" not in tiers


# ------------------------------------- (b) one semantics for both paths

def test_the_install_is_the_served_paths_snapshot_install():
    """What ``state_transfer`` leaves in the victim is what
    ``runtime/replica.py _install_snapshot_pairs`` leaves when handed
    the donor's table and executed frontier: the table as a dict, every
    cursor, the window's columns (all fill: the victim's window lay
    wholly below the frontier), its identity and ballot. One leaf
    differs on purpose: the pod keeps ``gossip_upto`` so that the next
    step reports the new frontier to the leader."""
    from minpaxos_tpu.runtime.replica import ReplicaServer

    pod = Pod(parent_step)  # no transfer while setting the scene up
    pod.run(8)
    pod.sc.kill(VICTIM)
    pod.run(32)
    pod.sc.revive(VICTIM)
    ss = pod.sc.ss
    states = tree_slice(ss.states, 0)  # group 0: leaves [R, ...]
    need, donor = transfer_needs(CFG, states, ss.alive[0])
    assert need.tolist() == [False] * VICTIM + [True] and int(donor) == 0
    got, installs = state_transfer(CFG, states, ss.alive[0])
    assert int(installs) == 1
    # every other replica is untouched, leaf for leaf
    for r in range(VICTIM):
        assert tree_equal(tree_slice(got, r), tree_slice(states, r)), r
    got = tree_slice(got, VICTIM)

    d = tree_slice(states, 0)
    frontier = int(d.executed_upto)
    table = dump_table(*(np.asarray(x) for x in d.kv[:4]))
    pairs = np.zeros(len(table), [("key", "<i8"), ("val", "<i8")])
    pairs["key"], pairs["val"] = list(table), list(table.values())
    host = types.SimpleNamespace(
        cfg=CFG, state=jax.tree_util.tree_map(
            jnp.copy, tree_slice(states, VICTIM)))
    ReplicaServer._install_snapshot_pairs(host, pairs, frontier)
    want = host.state

    assert dump_table(*(np.asarray(x) for x in got.kv[:4])) == table == \
        dump_table(*(np.asarray(x) for x in want.kv[:4]))
    differ = [name for name in got._fields if name != "kv" and not
              np.array_equal(np.asarray(getattr(got, name)),
                             np.asarray(getattr(want, name)))]
    assert differ == ["gossip_upto"], differ
    assert int(got.window_base) == frontier + 1 == int(got.executed_upto) + 1
    assert int(got.me) == VICTIM and int(got.leader_id) == 0
    assert not bool(got.prepared)
    assert int(got.gossip_upto) < frontier  # so the next step reports


def test_a_transfer_keeps_accepted_slots_above_the_frontier_and_ballots():
    """A replica that holds accepted slots ABOVE the donor's executed
    frontier keeps them through an install (its vote may be part of
    their quorum), and never lowers a ballot it has promised. The scene
    is planted: a laggard whose window straddles the frontier, 40 slots
    accepted from 10 below it to 29 above."""
    pod = Pod(parent_step)
    pod.run(8)
    pod.sc.kill(VICTIM)
    pod.run(32)
    pod.sc.revive(VICTIM)
    ss = pod.sc.ss
    states = tree_slice(ss.states, 0)
    v = tree_slice(states, VICTIM)
    f = int(states.executed_upto[0])
    promised = int(v.default_ballot) + 16
    base = f - 10
    planted = jnp.arange(40)

    def plant(col, values):
        return col.at[VICTIM, planted].set(values.astype(col.dtype))

    states = states._replace(
        default_ballot=states.default_ballot.at[VICTIM].set(promised),
        window_base=states.window_base.at[VICTIM].set(base),
        status=plant(states.status.at[VICTIM].set(0),
                     jnp.full(40, 3)),  # ACCEPTED, and nothing else
        ballot=plant(states.ballot, jnp.full(40, int(v.default_ballot))),
        key_lo=plant(states.key_lo, 1000 + planted),
        val_lo=plant(states.val_lo, 2000 + planted),
        votes=plant(states.votes, jnp.full(40, 1 << VICTIM)))
    need, donor = transfer_needs(CFG, states, ss.alive[0])
    assert bool(need[VICTIM]) and int(donor) == 0
    got = tree_slice(state_transfer(CFG, states, ss.alive[0])[0], VICTIM)
    assert int(got.window_base) == f + 1
    live = np.flatnonzero(np.asarray(got.status))
    assert live.tolist() == list(range(29))  # slots f + 1 .. f + 29
    for col, first in (("key_lo", 1011), ("val_lo", 2011)):
        assert np.asarray(getattr(got, col))[:29].tolist() == list(
            range(first, first + 29)), col
    assert (np.asarray(got.ballot)[:29] == int(v.default_ballot)).all()
    assert (np.asarray(got.votes)[:29] == 1 << VICTIM).all()
    # the 11 slots the slide freed at the window's end are fill
    assert (np.asarray(got.ballot)[-11:] == -1).all()  # NO_BALLOT
    assert int(got.default_ballot) == promised
    assert int(got.leader_id) == int(v.leader_id)
    assert int(got.crt_inst) >= f + 1


def test_neither_a_dead_donor_nor_a_dead_laggard_transfers():
    pod = Pod(parent_step)
    pod.run(8)
    pod.sc.kill(VICTIM)
    pod.run(32)
    ss = pod.sc.ss
    states, alive = tree_slice(ss.states, 0), ss.alive[0]
    assert not transfer_needs(CFG, states, alive)[0].any()  # laggard dead
    assert not bool(replica_step_impl.round_sections["px.state_transfer"][0](
        CFG, ss.states, ss.alive))
    revived = alive.at[VICTIM].set(True)
    assert transfer_needs(CFG, states, revived)[0].tolist() == [
        False] * VICTIM + [True]
    assert not transfer_needs(
        CFG, states, revived.at[0].set(False))[0].any()  # donor dead
    # a healthy replica whose report the leader lost is left alone
    stale = states._replace(
        peer_commits=states.peer_commits.at[0, 1].set(-1))
    assert transfer_needs(CFG, stale, revived)[0].tolist() == [
        False] * VICTIM + [True]


# ------------------------------- (c) inside retention: nothing changes

def test_an_outage_inside_retention_opens_no_gate_and_changes_no_byte():
    """Two rounds dead (32 slots, half the retention): catch-up rows
    heal the victim as they always did; the transfer's gate never
    opens and the end state equals, leaf for leaf, the run of the step
    that has no transfer."""
    ends = []
    for step in (replica_step_impl, parent_step):
        pod = Pod(step)
        outage(pod, dead_rounds=2)
        assert pod.drain() is not None
        ends.append((pod.sc.ss, pod.sc.resident_hist(),
                     np.asarray(pod.sc._tiers)))
        if step is replica_step_impl:
            tiers = pod.sc.resident_tiers()
            assert tiers["round_gates"] == {"px.state_transfer": 0}
            assert tiers["state_transfers"] == 0
    assert tree_equal(*ends)


# ------------------------------------------- (d) no fault: the parent's

def test_without_a_fault_the_dispatch_ends_where_the_parents_does():
    ends = []
    for step in (replica_step_impl, parent_step):
        pod = Pod(step)
        pod.run(24)
        assert pod.drain() is not None
        ends.append((pod.sc.ss, pod.sc.resident_hist(),
                     np.asarray(pod.sc._tiers),
                     np.asarray(pod.sc._gate_opens)))
    assert tree_equal(*ends)
    assert pod.sc._recovery is None  # the parent's step carries no count


def test_the_mencius_dispatch_holds_nothing_of_the_transfer(monkeypatch):
    """The Mencius pod's lowered resident dispatch is the same text with
    the round sections taken out of ``parallel/sharded.py`` altogether,
    and names no transfer: its step declares none, so nothing of the
    mechanism is traced into it."""
    from minpaxos_tpu.models.mencius import init_mencius, mencius_step_impl

    cfg = MinPaxosConfig(n_replicas=5, window=256, inbox=512, exec_batch=40,
                         kv_pow2=8, catchup_rows=32, recovery_rows=16)

    def lowered():
        sharded.sharded_run_resident.clear_cache()
        ss = sharded.init_sharded(cfg, 2, None, init_mencius)
        i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
        return sharded.sharded_run_resident.lower(
            cfg, 2, 8, 2, ss, i32((2, cfg.window)), i32(sharded.LATENCY_BINS),
            i32((0, sharded.N_TEL_FIELDS)), i32(3), i32(5), jnp.int32(-1),
            jnp.int32(0), jnp.int32(1), mencius_step_impl, 64, 1,
            jnp.int32(0), i32(sharded.N_COUNTS), i32(2)).as_text()

    assert sharded.round_sections(mencius_step_impl) == {}
    text = lowered()
    assert "state_transfer" not in text
    monkeypatch.setattr(sharded, "round_sections", lambda step: {})
    assert hashlib.sha256(lowered().encode()).digest() == \
        hashlib.sha256(text.encode()).digest()


# -------------------------------- (e) random kill / revive schedules

@pytest.mark.parametrize("seed", [11, 12, 13])
def test_random_kill_revive_schedules_keep_agreement_and_promises(seed):
    """Followers killed and revived at random under load, for outages
    short and long (some beyond retention: installs happen): after
    every round no replica's promised ballot has fallen, no committed
    frontier has moved back, and no two replicas hold different
    commands in a slot both have committed; at the end, every replica
    alive, the pod drains to equal frontiers and equal tables."""
    rng = np.random.default_rng(seed)
    pod = Pod()
    pod.run(4)
    dead: dict[int, int] = {}  # follower -> rounds left dead
    ballots = np.asarray(pod.sc.ss.states.default_ballot)
    upto = pod.frontiers()[0]
    log: dict[tuple[int, int], tuple] = {}  # (group, slot) -> command
    for _ in range(90):
        for r in [r for r, left in dead.items() if left == 0]:
            pod.sc.revive(r)
            del dead[r]
        # at most two followers down: a quorum of three stays
        if len(dead) < 2 and rng.random() < 0.15:
            r = int(rng.choice([q for q in range(1, 5) if q not in dead]))
            pod.sc.kill(r)
            dead[r] = int(rng.choice([1, 3, 6, 12, 24]))
        dead = {r: left - 1 for r, left in dead.items()}
        pod.run(1, int(rng.choice([0, P, P, P])))
        st = pod.sc.ss.states
        now = np.asarray(st.default_ballot)
        assert (now >= ballots).all(), "a promised ballot fell"
        ballots = now
        new = np.asarray(st.committed_upto)
        assert (new >= upto).all(), "a committed frontier moved back"
        upto = new
        base, status = np.asarray(st.window_base), np.asarray(st.status)
        cols = [np.asarray(getattr(st, c)) for c in
                ("op", "key_lo", "val_lo", "cmd_id")]
        for g in range(G):
            for r in range(CFG.n_replicas):
                for i in np.flatnonzero(status[g, r] >= 4):  # COMMITTED
                    cmd = tuple(int(c[g, r, i]) for c in cols)
                    assert log.setdefault((g, int(base[g, r] + i)),
                                          cmd) == cmd, (g, r, i)
    for r in dead:
        pod.sc.revive(r)
    pod.run(8)
    assert pod.drain() is not None
    # (no replay here: while fewer than a quorum is current the window
    # fills and the leader sheds proposals, which the stream's replay
    # would count; agreement is what must hold)
    for g in range(G):
        tables = pod.tables(g)
        assert tables[0] and all(t == tables[0] for t in tables), g
    assert pod.sc.resident_tiers()["state_transfers"] > 0
