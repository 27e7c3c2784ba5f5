"""Counts JAX compiles and persistent-cache hits from JAX's own
monitoring events."""
from __future__ import annotations


class CompileMeter:
    """Seconds spent compiling (a persistent-cache hit counts only its
    read), programs compiled, and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.programs, self.cache_hits

    def since(self, snap) -> dict:
        s, p, h = snap
        return {"compile_s": self.seconds - s, "programs": self.programs - p,
                "cache_hits": self.cache_hits - h}
