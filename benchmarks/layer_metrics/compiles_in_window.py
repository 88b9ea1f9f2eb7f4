"""Programs JAX compiled (or loaded from its cache) inside the window,
from its own monitoring events. Expected 0: every variant is warmed in
set-up."""


def read(obs):
    return obs["counters"].get("compiles_in_window")
