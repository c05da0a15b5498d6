"""Counts the programs JAX builds while the harness runs.

Adapted from the smoke run's probe: JAX's monitoring events for a
backend compile and for a program loaded from the persistent cache.
Either one inside the measured window means a shape was not warmed.
"""

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Probe:
    """Counts backend compiles and persistent-cache loads between
    ``install`` and ``remove``; a context manager does both."""

    def __init__(self):
        self.compiles = 0
        self.cache_loads = 0
        self.compile_s = 0.0

    def _on_duration(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **kwargs):
        if event == HIT_EVENT:
            self.cache_loads += 1

    def built(self) -> int:
        """Programs built so far: compiled or loaded from the cache."""
        return self.compiles + self.cache_loads

    def __enter__(self) -> "Probe":
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False
