"""``os.fsync`` calls of all replicas inside the window per request
acknowledged in it, from the benchmark's ledger of the process's fsyncs
(``lib/fsync_ledger.py``): what batching makes of "fsync before reply".
A change that stops syncing reads 0 here and fails ``correct``."""


def read(obs):
    c = obs["counters"]
    if "fsyncs" not in c or not c.get("acked_in_window"):
        return None
    return c["fsyncs"] / c["acked_in_window"]
