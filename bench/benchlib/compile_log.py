"""Counts XLA compiles and persistent-cache hits from JAX's monitoring
events (copied from the bring-up check ``chip_smoke.py``)."""
from __future__ import annotations

import jax


class CompileLog:
    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
