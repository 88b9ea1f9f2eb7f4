"""Mencius with an owner down: the revocation of its turns
(``models/mencius.py`` section 7r) in the pod, at the rehearsal shape of
``mencius5_pod_owner_down`` on the CPU.

* The live owners' commands commit at the rate they are offered once
  the first revocation is done, the pod drains to exactness with the
  owner still dead, and every table equals the plain reference
  (``benchmarks/lib/menciusstream.py``), the dead owner's last values,
  which a majority accepted and nobody committed, among them.
* A value of the dead owner that a majority accepted but its successor
  never saw commits with its value.
* An owner revived after a revocation never writes into a revoked turn,
  and its later proposals commit (ROADMAP M9).
"""

from __future__ import annotations

import numpy as np
import pytest

from benchmarks.lib import menciusstream
from benchmarks.lib.tables import dump_table
from minpaxos_tpu.models.minpaxos import MinPaxosConfig
from minpaxos_tpu.wire.messages import MsgKind, Op

R, P, K, GROUPS, KEYS_PER_OWNER, VICTIM = 5, 8, 2, 2, 64, 4
CFG = MinPaxosConfig(n_replicas=R, window=512, inbox=256, exec_batch=40,
                     kv_pow2=10, catchup_rows=16, recovery_rows=16,
                     noop_delay=8)
SEED = 3000039123


class Pod:
    """A resident Mencius pod that keeps, a dispatch at a time, what
    every owner was offered and how many commands were given a slot."""

    def __init__(self):
        from minpaxos_tpu.parallel.sharded import ShardedCluster

        self.sc = ShardedCluster(CFG, GROUPS, ext_rows=P, protocol="mencius",
                                 key_space=512,
                                 seed=SEED % 0x7FFFFFFF)
        self.sc.begin_resident()
        self.log = []  # (round0, offered per owner, committed, assigned)

    def dispatch(self, offered):
        r0 = self.sc._seed
        committed, in_flight = self.sc.run_resident(K, list(offered))
        self.log.append((r0, list(offered), committed, committed + in_flight))
        return committed, in_flight

    def refused(self, i):
        before = self.log[i - 1][3] if i else 0
        return K * sum(self.log[i][1]) * GROUPS - (self.log[i][3] - before)

    def drain(self, live):
        for _ in range(30):
            _, in_flight = self.dispatch([0] * R)
            st = self.sc.ss.states
            up = np.asarray(st.committed_upto)[:, live]
            ex = np.asarray(st.executed_upto)[:, live]
            if in_flight == 0 and (up == up[:, :1]).all() and (ex == up).all():
                return True
        return False

    def tables(self, replicas):
        kv = self.sc.ss.states.kv
        out = {}
        for g in range(GROUPS):
            arrs = [np.asarray(x[g]) for x in kv[:4]]
            out[g] = [dump_table(*(a[r] for a in arrs)) for r in replicas]
        return out

    def want(self):
        """Each key's last offered write and whether its dispatch gave
        every offered command a slot (refused commands never commit,
        which ones is the program's business)."""
        rounds = [[r0 + t for r0, n, *_ in self.log if n[o]
                   for t in range(K)] for o in range(R)]
        sure = {r0 + t for i, (r0, *_) in enumerate(self.log)
                if not self.refused(i) for t in range(K)}
        out = {g: {} for g in range(GROUPS)}
        for r in sorted(set().union(*map(set, rounds))):
            keys, vals = menciusstream.owner_rows(
                SEED % 0x7FFFFFFF, r, list(range(GROUPS)), R, P,
                KEYS_PER_OWNER)
            for o in range(R):
                if r in rounds[o]:
                    for g in range(GROUPS):
                        out[g].update((k, (v & 0xFFFFFFFF, r in sure))
                                      for k, v in zip(keys[g, o].tolist(),
                                                      vals[g, o].tolist()))
        return out


def _mismatch(pod, replicas):
    want, got = pod.want(), pod.tables(replicas)
    bad = undecided = 0
    for g in range(GROUPS):
        decided = {k: v for k, (v, sure) in want[g].items() if sure}
        undecided += len(want[g]) - len(decided)
        for t in got[g]:
            t = {k: v for k, v in t.items() if k in decided or k not in want[g]}
            bad += len(set(decided.items()) ^ set(t.items()))
    return bad, undecided


@pytest.fixture(scope="module")
def dead_owner_pod():
    pod = Pod()
    for _ in range(8):
        pod.dispatch([P] * R)
    kill_round = pod.sc._seed
    pod.sc.kill(VICTIM)
    for _ in range(40):
        pod.dispatch([P] * (R - 1) + [0])
    return pod, kill_round


def test_live_owners_commit_what_they_are_offered_after_the_revocation(
        dead_owner_pod):
    pod, kill_round = dead_owner_pod
    after = [i for i, (r0, *_) in enumerate(pod.log) if r0 >= kill_round + 24]
    assert len(after) >= 10
    per_dispatch = K * P * (R - 1) * GROUPS
    for i in after:
        assert pod.refused(i) == 0, i
        assert pod.log[i][2] - pod.log[i - 1][2] == per_dispatch, i
    counts = pod.sc.command_counts()
    # every no-op slot the frontier passed since the kill is a revoked
    # turn of the victim: a fifth of the slots, less the first episode
    assert counts["revoked_slots"] > 0
    assert counts["takeover_episodes"] >= GROUPS


def test_drains_to_exactness_with_the_owner_dead(dead_owner_pod):
    pod, kill_round = dead_owner_pod
    live = [r for r in range(R) if r != VICTIM]
    assert pod.drain(live)
    counts = pod.sc.command_counts()
    assert counts["commands"] == counts["assigned"]
    up = np.asarray(pod.sc.ss.states.committed_upto)[:, 0]
    assert int((up + 1).sum()) == counts["commands"] + counts["noop_slots"]
    bad, undecided = _mismatch(pod, live)
    assert bad == 0 and undecided == 0
    # the dead owner's last two rounds, accepted by every live replica
    # and committed by none at the kill, are in every live table
    tables, want = pod.tables(live), pod.want()
    for g in range(GROUPS):
        for r in (kill_round - 2, kill_round - 1):
            keys, _ = menciusstream.owner_rows(
                SEED % 0x7FFFFFFF, r, [g], R, P, KEYS_PER_OWNER)
            for t in tables[g]:
                for k in keys[0, VICTIM].tolist():
                    assert t[k] == want[g][k][0]  # its last write
    assert np.asarray(pod.sc.ss.states.kv.dropped).sum() == 0


def test_a_value_a_majority_accepted_unseen_by_the_successor_commits():
    """Owner 4 dies with one more ACCEPT out than its successor (0)
    saw: replicas 1-3 hold it. The revocation's no-ops start above it;
    the next takeover adopts it from them and commits it."""
    import jax

    from minpaxos_tpu.models.mencius import MenciusCluster
    from minpaxos_tpu.models.minpaxos import MsgBatch

    cl = MenciusCluster(CFG, ext_rows=64)
    cid = 0
    for step in range(12):
        for o in range(R):
            keys = o * 100 + np.arange(4) + step % 3
            cl.propose(np.full(4, int(Op.PUT)), keys, keys * 7 + step,
                       np.arange(cid, cid + 4), client_id=o, to=o)
            cid += 4
        cl.step()
    cl.kill(VICTIM)
    slot = int(np.asarray(cl.cs.states.crt_own)[VICTIM])
    row = MsgBatch(**{f: np.zeros(1, np.int32) for f in MsgBatch._fields})
    row = row._replace(kind=np.full(1, int(MsgKind.ACCEPT), np.int32),
                       src=np.full(1, VICTIM, np.int32),
                       inst=np.full(1, slot, np.int32),
                       op=np.full(1, int(Op.PUT), np.int32),
                       key_lo=np.full(1, 9999, np.int32),
                       val_lo=np.full(1, 4242, np.int32),
                       cmd_id=np.full(1, 77, np.int32),
                       client_id=np.full(1, 7, np.int32))
    for r in (1, 2, 3):
        cl._ext_queue.append((r, row))
    for step in range(80):
        for o in range(R - 1):
            keys = o * 100 + np.arange(2)
            cl.propose(np.full(2, int(Op.PUT)), keys, keys + step,
                       np.arange(cid, cid + 2), client_id=o, to=o)
            cid += 2
        cl.step()
    st = jax.tree_util.tree_map(np.asarray, cl.cs.states)
    live = [r for r in range(R) if r != VICTIM]
    assert (st.committed_upto[live] >= slot).all()
    for r in live:
        table = dump_table(*(np.asarray(x)[r] for x in cl.cs.states.kv[:4]))
        assert table.get(9999) == 4242, r


def test_a_revived_owner_never_writes_a_revoked_turn_and_commits_again():
    pod = Pod()
    for _ in range(8):
        pod.dispatch([P] * R)
    pod.sc.kill(VICTIM)
    # a light load while it is down: the peers' window stays within
    # the victim's retention, so catch-up rows heal it once it is back
    for _ in range(10):
        pod.dispatch([1] * (R - 1) + [0])
    st = pod.sc.ss.states
    lo, cm = (int(np.asarray(st.rv_lo)[0, 0, VICTIM]),
              int(np.asarray(st.rv_cm)[0, 0, VICTIM]))
    assert cm > lo >= 0  # the successor's revocation is chosen
    pod.sc.revive(VICTIM)

    def no_value_in_a_revoked_turn():
        st = pod.sc.ss.states
        base = np.asarray(st.window_base)[..., None]
        slot = base + np.arange(CFG.window)
        turn = ((slot % R == VICTIM) & (slot >= lo) & (slot <= cm)
                & (np.asarray(st.status) > 0))
        assert (np.asarray(st.op)[turn] == int(Op.NONE)).all()

    # light load while catch-up rows heal it (its own clients come
    # back once it has: a proposal it took before it heard of the
    # revocation would land in a revoked turn, ROADMAP M9), then the
    # full load
    for _ in range(30):
        pod.dispatch([1] * (R - 1) + [0])
        no_value_in_a_revoked_turn()
    up = np.asarray(pod.sc.ss.states.committed_upto)
    assert (up[:, VICTIM] >= up[:, 0] - R).all()  # back with its peers
    for _ in range(50):
        pod.dispatch([P] * R)
        no_value_in_a_revoked_turn()
    own = np.asarray(pod.sc.ss.states.crt_own)[:, VICTIM]
    hi = np.asarray(pod.sc.ss.states.rp_hi)[:, VICTIM, VICTIM]
    assert (own > hi + R * P).all()  # it proposes past the range
    assert pod.drain(list(range(R)))
    counts = pod.sc.command_counts()
    assert counts["commands"] == counts["assigned"]
    tables = pod.tables(range(R))
    for g in range(GROUPS):
        assert all(t == tables[g][0] for t in tables[g])
    bad, undecided = _mismatch(pod, range(R))
    assert bad == 0 and undecided == 0
