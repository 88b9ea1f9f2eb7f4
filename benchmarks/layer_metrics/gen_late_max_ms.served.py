"""How late the load generator itself ran, at worst, over the window:
a starved generator must not read as a fast server."""


def read(obs):
    late = obs["counters"].get("gen_behind_max_s")
    return None if late is None else late * 1e3
