"""Counts JAX's own compile events (``jax.monitoring``): every backend
compile with its seconds, and the persistent cache's hits and misses. Copied
from ``chip_smoke.CompileWatch`` without the program's ledger: a compile
inside the measured window shows here whatever compiled it."""

from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileWatch:
    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.backend_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.backend_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    def mark(self) -> dict:
        return {"compiles": self.compiles, "backend_s": self.backend_s,
                "hits": self.hits, "misses": self.misses}

    def since(self, m: dict) -> dict:
        now = self.mark()
        return {k: now[k] - m[k] for k in now}
