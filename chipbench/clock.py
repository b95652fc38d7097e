"""Compilation seen through JAX's own monitoring events.

Copied from ``chip_smoke.py``'s ``CompileClock`` and extended to count
events by kind, so that a run can show that nothing compiled inside its
measured window.
"""
from __future__ import annotations

import threading
import time
from typing import List, Tuple

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Records JAX's trace, lowering and backend-compile durations, from
    any thread, with the time each ended, and the persistent-cache hits."""

    def __init__(self, jax):
        self.ended: List[Tuple[float, str, float]] = []  # (end, event, s)
        self.hits: List[float] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def seconds(self, start: float, end: float) -> float:
        """Compile seconds of the events that ended in [start, end)."""
        with self._lock:
            return sum(d for t, _, d in self.ended if start <= t < end)

    def compiles(self, start: float, end: float) -> int:
        """Programs traced, compiled or loaded from the persistent cache
        in [start, end): any of them inside a measured window means a
        shape was not warmed up."""
        with self._lock:
            n = sum(1 for t, e, _ in self.ended
                    if start <= t < end and e in (TRACE, BACKEND))
            return n + sum(1 for t in self.hits if start <= t < end)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in (TRACE, LOWER, BACKEND):
            with self._lock:
                self.ended.append((time.perf_counter(), event, duration))

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            with self._lock:
                self.hits.append(time.perf_counter())
