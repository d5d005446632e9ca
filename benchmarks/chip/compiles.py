"""Compiles and persistent-cache reads as JAX itself reports them through
``jax.monitoring``, stamped on ``time.monotonic()`` so that the harness can
split them between set-up and the measured window."""
from __future__ import annotations

import threading
import time

import jax.monitoring as monitoring

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


class CompileLog:
    """Records (time, event, seconds) for every compile stage and cache
    read while active."""

    def __init__(self):
        self._lock = threading.Lock()
        self.durations: list[tuple[float, str, float]] = []
        self.counts: list[tuple[float, str]] = []

    def _on_duration(self, event, secs, **_):
        if event in (TRACE, LOWER, BACKEND):
            with self._lock:
                self.durations.append((time.monotonic(), event, float(secs)))

    def _on_event(self, event, **_):
        if event in (HIT, MISS):
            with self._lock:
                self.counts.append((time.monotonic(), event))

    def __enter__(self) -> "CompileLog":
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def summary(self, t0: float, t1: float) -> dict:
        """Compile seconds (trace + lower + backend), backend compiles and
        cache hits and misses stamped in [t0, t1)."""
        with self._lock:
            ds = [(e, s) for t, e, s in self.durations if t0 <= t < t1]
            cs = [e for t, e in self.counts if t0 <= t < t1]
        return {"compile_s": sum(s for _, s in ds),
                "backend_compiles": sum(1 for e, _ in ds if e == BACKEND),
                "cache_hits": cs.count(HIT), "cache_misses": cs.count(MISS)}
