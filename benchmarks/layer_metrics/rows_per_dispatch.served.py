"""Client rows the leader proposed per device dispatch, from its
``proposals`` and ``dispatches`` counters over the window: the batching
the ingress coalescer and the tick loop achieve."""


def read(obs):
    c = obs["counters"]
    if not c.get("leader_dispatches"):
        return None
    return c["leader_proposals"] / c["leader_dispatches"]
