"""Executables built or loaded, with their seconds, and persistent-cache
hits and misses, counted through ``jax.monitoring``."""
from __future__ import annotations


class CompileMeter:
    """Counts ``backend_compile`` events (an executable compiled or loaded
    from the persistent cache) and the cache's hits and misses.

    ``jax.monitoring`` keeps its listeners for the life of the process, so
    make one meter per process and take differences with :meth:`since`."""

    def __init__(self):
        from jax import monitoring
        self.executables = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.executables += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple:
        return (self.executables, self.seconds, self.cache_hits,
                self.cache_misses)

    def since(self, snap: tuple) -> dict:
        now = self.snapshot()
        return {"executables": now[0] - snap[0],
                "compile_s": now[1] - snap[1],
                "cache_hits": now[2] - snap[2],
                "cache_misses": now[3] - snap[3]}
