"""Share of the slots replica 0 executed in the window that hold no
client command: slots an owner with nothing to propose at its turn
ceded (SKIP), or that a takeover filled. The program counts both kinds
where it executes them (``runtime/replica.py``: ``noop_slots``,
``command_slots``; every slot executes once, and the check ties the
no-op count to the disk). Near 0 while every owner is loaded at every
turn; the share of turns at which an owner had nothing to propose
otherwise. A program without the counters reads nothing."""


def read(obs):
    c = obs["counters"]
    noops, commands = c.get("noop_slots"), c.get("command_slots")
    if noops is None or commands is None or noops + commands == 0:
        return None
    return 100.0 * noops / (noops + commands)
