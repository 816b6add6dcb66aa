"""Counts the backend compiles of this process (copied from the bring-up
smoke test): a persistent-cache hit is still one request, timed as its
load, and is counted among the cache hits too."""

from __future__ import annotations

from jax import monitoring


class Compiles:
    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
