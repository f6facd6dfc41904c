"""Seconds JAX spends compiling or fetching compiled programs from the
persistent cache, read from JAX's own monitoring events.

A copy, kept with the benchmark, of ``chip_smoke.CompileMeter``."""
from __future__ import annotations

import threading

import jax


class CompileMeter:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return {"compile_s": self.seconds, "compiles": self.compiles,
                    "cache_hits": self.cache_hits}
