"""Requests acknowledged (each once) inside the window, over the
window's whole length."""


def read(obs):
    c = obs["counters"]
    if "acked_in_window" not in c:
        return None
    return c["acked_in_window"] / c["window_s"]
