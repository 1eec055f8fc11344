"""Compilations and persistent-cache hits, read from JAX's monitoring
events: the seconds that set-up spends compiling, and how many programs
were traced or compiled inside the measured window (there should be
none)."""
from __future__ import annotations

import jax

TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        self.traces = self.compiles = self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == TRACE:
            self.traces += 1
        elif event == COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"traces": self.traces, "compiles": self.compiles,
                "compile_s": self.compile_s, "cache_hits": self.cache_hits}
