"""Where JAX's persistent compilation cache lives.

The program runs two ways: on the CPU for tests (``JAX_PLATFORMS=cpu``,
8 virtual devices) and on a TPU, where ONE process owns the chip for
its whole life. Neither way selects a backend here — JAX picks the
platform (``JAX_PLATFORMS`` or its default) and an entry point that
needs a chip checks ``jax.devices()[0].platform`` itself and fails when
it is not there (chip_smoke.py, benchmarks/run.py). What is shared is
the compile cache: every process that jits the protocol kernels points
at the same directory, so only the first pays the compile.
"""

from __future__ import annotations

import os
import pathlib

#: the fixed fallback location (already in .gitignore). The directory
#: is part of every cache key, so it must never move between runs — no
#: temporary name, pid or timestamp may enter this path.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: the directory is placed from
    outside (JAX reads that variable itself) and this function sets
    none. Unset: ``<checkout>/.jax_cache``. Why it exists: every replica
    server process jit-compiles the same protocol kernels (~10-40 s
    each on a small host), and three servers compiling concurrently at
    boot starved each other; with the cache a repeat boot loads in
    ~1 s. Must run BEFORE the first compile; safe to call twice. An
    unwritable directory raises — a run that silently pays every cold
    compile is not the run that was asked for."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(DEFAULT_CACHE_DIR)
        pathlib.Path(d).mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return d
