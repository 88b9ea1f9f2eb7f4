"""Rounds of the window at whose end some LIVE replica's committed
frontier trailed its group leader's by more than two rounds' proposals
(``lagging_rounds`` in ``ShardedCluster.resident_tiers()``, counted on
the device; a healthy follower trails by one round's, a dead one is not
counted). In the kill / recover cell these are the rounds from the
revive until the victim has caught up: recovery time, in rounds, until
the benchmark has it end to end. 0 in a window without a fault; a
program without the counter reads nothing."""


def read(obs):
    return obs["counters"].get("lagging_rounds")
