"""Spans of the live runtime on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler trace is
active, the profiler keeps it in memory on the same clock as the device's
own events and writes it at ``stop_trace``; with no trace active it costs
under a microsecond, so spans are always on. Each span also takes one
``time.perf_counter()`` reading at each end, and the duration between them
is what the runtime's counters add up (``seg_done``'s ``busy_s`` and
``wait_s``, ``LiveResult.control_points``): the span and the counter share
those readings.

Names are ``ftp.w{dev}.<what>`` in a worker's thread and
``ftp.coord.<what>`` in the coordinator's (docs/operations.md, "Tracing a
run"). A trace reader that flattens threads sees only the name, so the
worker's device id is part of it; ``seg`` and ``batch`` ride as the
event's stats. Within one thread spans do not nest.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class Span:
    """One span. Use it as a context manager, or ``open()`` and
    ``close()`` it by hand where it starts and ends at two points of a
    loop. ``close()`` returns the seconds between the two readings and,
    where ``into`` is given, adds them to ``into[key]``."""

    __slots__ = ("_ann", "_into", "_key", "t0", "dt")

    def __init__(self, name: str, into: dict | None = None, key: str = "",
                 **stats):
        self._ann = TraceAnnotation(name, **stats)
        self._into = into
        self._key = key
        self.t0 = 0.0
        self.dt = 0.0

    def open(self) -> "Span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def close(self) -> float:
        self.dt = time.perf_counter() - self.t0
        self._ann.__exit__(None, None, None)
        if self._into is not None:
            self._into[self._key] = self._into.get(self._key, 0.0) + self.dt
        return self.dt

    def __enter__(self) -> "Span":
        return self.open()

    def __exit__(self, *exc) -> None:
        self.close()
