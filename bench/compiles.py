"""Executables JAX built, from JAX's own monitoring events (copied from
``chip_smoke.py``'s ``CompileCounter``).

``backend_compile_duration`` wraps every executable build, whether XLA
compiled it or it came out of the persistent cache; ``cache_hits`` counts
the latter.  Shards compile on several threads, hence the lock."""
from __future__ import annotations

import threading
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.last_ns = time.perf_counter_ns()   # time of the latest build
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.seconds += duration
                self.last_ns = time.perf_counter_ns()

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        with self._lock:
            return self.count, self.seconds, self.cache_hits
