"""Seconds JAX spends tracing, lowering and compiling, from jax.monitoring's
duration events (copied from the root chip_smoke.py's CompileClock).

The backend-compile event spans ``compile_or_get_cached``, so a program
served from the persistent cache still reports one, lasting as long as
the retrieval took; its cache hits are counted apart. A program that
missed the cache reports a ``cache_misses`` event (when JAX writes it to
the cache) before its backend-compile event ends: that compile is cold,
and its seconds are kept apart too.
"""
from __future__ import annotations

import time

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileClock:
    """Log of the compile events JAX reports, each with the host time at
    which it was reported, so those inside a window can be told from those
    of set-up. Register with ``install()``."""

    def __init__(self):
        self.events = []  # (perf_counter at report, event, seconds)
        self.cache_hits = []  # perf_counter of each persistent-cache hit
        self.cold = []  # (perf_counter at report, seconds) of each cold compile
        self._missed = False

    def install(self, monitoring) -> None:
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            now = time.perf_counter()
            self.events.append((now, event, float(duration)))
            if event == COMPILE_EVENTS[2] and self._missed:
                self.cold.append((now, float(duration)))
                self._missed = False

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits.append(time.perf_counter())
        elif event == CACHE_MISS_EVENT:
            self._missed = True

    def cold_seconds(self, t0: float, t1: float) -> float:
        """Seconds of the backend compiles, reported inside [t0, t1], that
        missed the persistent cache."""
        return sum(s for t, s in self.cold if t0 <= t <= t1)

    def between(self, t0: float, t1: float) -> dict:
        """Compile seconds, compile events and cache hits reported inside
        [t0, t1]. The seconds are those of the union of the events'
        intervals (each ends when it is reported): a jit traced inside
        another's trace reports an event nested in the outer one."""
        inside = [(e, s) for t, e, s in self.events if t0 <= t <= t1]
        spans = sorted((t - s, t) for t, _, s in self.events if t0 <= t <= t1)
        seconds, end = 0.0, float("-inf")
        for a, b in spans:
            a = max(a, end, t0)
            if b > a:
                seconds += b - a
            end = max(end, b)
        return {
            "seconds": seconds,
            "events": len(inside),
            "backend_compiles": sum(
                1 for e, _ in inside if e == COMPILE_EVENTS[2]
            ),
            "cache_hits": sum(1 for t in self.cache_hits if t0 <= t <= t1),
            "cold_compiles": sum(1 for t, _ in self.cold if t0 <= t <= t1),
        }
