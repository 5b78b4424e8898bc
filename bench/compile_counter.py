"""Counts JAX backend compilations (persistent-cache loads included) and
persistent-cache hits from ``jax.monitoring`` events."""
from __future__ import annotations

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Backend compilations and persistent-cache hits since construction.
    ``jax.monitoring`` keeps its listeners for the life of the process, so
    make one counter per process and read differences of
    :meth:`snapshot`."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kwargs):
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event, **kwargs):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits
