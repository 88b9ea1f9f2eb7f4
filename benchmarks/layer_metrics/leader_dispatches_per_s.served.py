"""The leader's device dispatches per second of the window: the tick
cadence the host sustains."""


def read(obs):
    c = obs["counters"]
    if not c.get("leader_window_s"):
        return None
    return c["leader_dispatches"] / c["leader_window_s"]
