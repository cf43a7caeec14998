"""XLA compile seconds and persistent-cache hits from JAX's monitoring
events, so that compiles inside a measured window are counted, not hidden."""

from __future__ import annotations

import collections

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self):
        import jax
        self.secs = collections.Counter()
        self.events = collections.Counter()
        self.compiles = 0

        def on_duration(name, secs, **kw):
            self.secs[name] += secs
            if name == BACKEND_COMPILE:
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.events.update([name]))

    def snapshot(self) -> dict:
        return {
            "xla_compiles": self.compiles,
            "xla_compile_s": self.secs[BACKEND_COMPILE],
            "cache_hits": self.events["/jax/compilation_cache/cache_hits"],
            "cache_misses": self.events["/jax/compilation_cache/cache_misses"],
        }

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
