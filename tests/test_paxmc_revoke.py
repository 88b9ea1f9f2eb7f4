"""paxmc over ``mencius_step_impl`` with one replica never scheduled (a
crashed owner): every interleaving the small bounds admit reaches its
successor's takeover and the first phase of the revocation of its turns
(``models/mencius.py`` section 7r) with every invariant of
``verify/invariants.py`` holding. The revocation's second phase and its
commit lie deeper than these bounds reach in the suite's time: the
request queues behind the rows the successor sent before it (one
message a step, links in order)."""

from __future__ import annotations

import numpy as np

from minpaxos_tpu.verify.mc import CLIENT, Bounds, Explorer

DEAD = 2
BOUNDS = Bounds(max_depth=12, drops=0, dups=0, internal=2, elections=0,
                n_cmds=2, propose_to=(0,), silent=(DEAD,))


class _Watching(Explorer):
    """An explorer that notes what the reached states hold."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.seen = {"takeover": 0, "revocation": 0, "dead_stepped": 0}

    def _apply(self, node, action):
        out = super()._apply(node, action)
        for r, st in enumerate(out[0]):
            self.seen["takeover"] += int(st.takeover_ballot) >= 0
            self.seen["revocation"] += int(st.rk_at) >= 0
        self.seen["dead_stepped"] += int(out[0][DEAD].tick) > 0
        return out


def test_a_dead_owner_is_taken_over_and_revoked_safely():
    ex = _Watching("mencius", BOUNDS)
    res = ex.run()
    assert res.drained and res.counterexample is None
    assert ex.seen["takeover"] > 0 and ex.seen["revocation"] > 0
    assert ex.seen["dead_stepped"] == 0


def test_a_silent_replica_is_neither_delivered_to_nor_ticked():
    ex = Explorer("mencius", Bounds(silent=(DEAD,), n_cmds=1,
                                    propose_to=(0, DEAD)))
    root = ex.initial()
    acts = ex._actions(root)
    assert acts and all(a.get("r") != DEAD for a in acts)
    assert all(a["link"][1] != DEAD for a in acts if "link" in a)
    # what a live replica sends is never queued for the dead one
    node = ex._apply(root, {"a": "deliver", "link": [CLIENT, 0]})
    assert not any(dst == DEAD and src != CLIENT for src, dst in node[1])
    assert np.asarray(node[0][DEAD].tick) == 0
