"""Compile time and compile events, read from JAX's monitoring events.

``/jax/core/compile/backend_compile_duration`` is recorded once for every
XLA program the process builds or loads from the persistent cache, so a
count of those events inside the measured window is the number of programs
that were not warmed up.
"""
from __future__ import annotations

import threading

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def install(self) -> "CompileClock":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration, **kwargs):
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += duration
                if event == BACKEND_COMPILE:
                    self.compiles += 1

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1
