"""Device-busy milliseconds per protocol round, from the trace: the
union of the chip's operation intervals in the traced dispatches over
the rounds they ran."""


def read(obs):
    trace, n = obs["trace"], obs["counters"].get("traced_rounds")
    if not trace or not trace["devices"] or not n:
        return None
    return trace["busy_s"] * 1e3 / n
