"""The most loaded owner's share, in percent, of the client commands
the three replicas gave slots of their own in the window (each
replica's ``client_proposals`` counter): 100 / 3 when the load is
spread evenly, 34.4 with 22 of 64 sessions on owner 0, 100 when one
owner takes every client. A program without the counter reads
nothing."""


def read(obs):
    per_owner = obs["counters"].get("owner_client_proposals")
    if not per_owner or sum(per_owner) == 0:
        return None
    return 100.0 * max(per_owner) / sum(per_owner)
