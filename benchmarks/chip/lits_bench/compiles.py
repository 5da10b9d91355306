"""Backend compiles seen by this process (a copy of
``chip_smoke.CompileCounter``).  JAX records the compile event for a
persistent-cache hit too, so the count is of programs made ready, from the
compiler or from the cache."""
from __future__ import annotations

import jax.monitoring as mon

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self.count, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
