"""Device-busy milliseconds per leader dispatch, from the trace: the
union of the chip's operation intervals in the traced part of the
window over the leader's dispatches in it. All three replicas step on
the one chip, so this is what a tick of the cluster costs the device."""


def read(obs):
    trace, n = obs["trace"], obs["counters"].get("traced_leader_dispatches")
    if not trace or not trace["devices"] or not n:
        return None
    return trace["busy_s"] * 1e3 / n
