"""Mean milliseconds one ``os.fsync`` of a replica's log took inside the
window, all replicas, timed around the call by the benchmark's ledger
(``lib/fsync_ledger.py``): the disk's share of a tick."""


def read(obs):
    c = obs["counters"]
    if not c.get("fsyncs"):
        return None
    return c["fsync_s"] * 1e3 / c["fsyncs"]
