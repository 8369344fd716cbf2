"""Backend compile counts, read from ``jax.monitoring``'s public listeners.

A compile inside a training run is a stall the round timings cannot explain:
the engine (core/engine.run_rounds) counts them per chunk, so an operator sees
which chunk recompiled. A persistent-cache hit still counts as a compile (the
in-memory jit cache missed and XLA loaded the executable from disk); cache
hits are counted besides.
"""
from __future__ import annotations

import threading

import jax

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts backend compiles and persistent-cache hits in this process from
    its creation until ``close()`` (also a context manager)."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def __enter__(self) -> "CompileCounter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


__all__ = ["CompileCounter"]
