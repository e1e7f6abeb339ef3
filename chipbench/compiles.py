"""Compile seconds and persistent-cache hits from JAX's monitoring events
(copied from the repository's chip_smoke.py)."""

from __future__ import annotations

import jax


class Compiles:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event == self.EVENT:
            self.seconds += seconds
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
