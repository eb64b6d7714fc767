"""XLA compiles and persistent-cache hits and misses, counted from JAX's
monitoring events (process-wide running totals)."""

from __future__ import annotations


class CompileCounter:
    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.compiles, self.hits, self.misses
