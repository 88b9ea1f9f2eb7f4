"""The SLOWEST owner's device dispatches per second of the window:
under Mencius a command is answered when the merged frontier passes
it, so the cadence the merge waits on is the slowest replica's, not
one leader's. The three replicas' ``dispatches`` counters over the
window replica 0's snapshots span. A program whose runner reads one
replica alone reads nothing."""


def read(obs):
    c = obs["counters"]
    per_owner = c.get("owner_dispatches")
    if not per_owner or not c.get("leader_window_s"):
        return None
    return min(per_owner) / c["leader_window_s"]
