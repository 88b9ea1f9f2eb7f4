"""Counts what JAX compiled, from its own monitoring events (a copy of
``chip_smoke.py _CompileMeter``, PR 21)."""

from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self) -> None:
        import jax

        self.compile_s: list[float] = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name: str, secs: float, **_kw) -> None:
        if name == BACKEND_COMPILE:
            self.compile_s.append(secs)

    def _on_event(self, name: str, **_kw) -> None:
        if name == CACHE_HIT:
            self.cache_hits += 1

    def take(self) -> dict:
        """Compilations, their seconds and persistent-cache hits since
        the last ``take``. A persistent-cache hit still fires the
        backend-compile event (it times the load), so ``compilations``
        counts programs built OR loaded: inside a window both are 0."""
        out = {"compilations": len(self.compile_s),
               "compile_s": sum(self.compile_s),
               "persistent_cache_hits": self.cache_hits}
        self.compile_s = []
        self.cache_hits = 0
        return out
