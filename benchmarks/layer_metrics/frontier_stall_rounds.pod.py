"""Rounds of the window at whose end replica 0's merged frontier stood
where it stood a round before, in every group, while commands were in
flight (``stalled_rounds`` in ``obs.process_pods()``, counted on the
device): the time without service, in rounds. It grows with the window
for as long as a fault lasts, so compare it only between windows of one
length. A program without the counter reads nothing."""


def read(obs):
    return obs["counters"].get("stalled_rounds")
