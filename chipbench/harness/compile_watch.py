"""Process-wide compile accounting from JAX's own monitoring events
(copied from chip_smoke.py's CompileWatch)."""
from __future__ import annotations


class CompileWatch:
    """Counts every compile request (a persistent-cache hit included),
    the seconds they took, and the persistent-cache hits among them."""

    def __init__(self):
        import jax
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration

    def snapshot(self):
        return {"requests": self.requests,
                "seconds": self.seconds,
                "cache_hits": self.cache_hits}
