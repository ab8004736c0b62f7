"""Counts XLA compiles through JAX's monitoring events (a copy of the
repository's ``chip_smoke.CompileCounter``): every compile request, a
persistent-cache read included, the cache hits, and their seconds."""
from __future__ import annotations


class CompileCounter:

    def __init__(self):
        import jax
        self.requests = 0
        self.hits = 0
        self.seconds = 0.0

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1
                self.seconds += duration

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self):
        return (self.requests, self.hits, self.seconds)

    def since(self, snap) -> str:
        r, h, s = (a - b for a, b in zip(self.snapshot(), snap))
        return (f"compiles={r} cache_hits={h} fresh={r - h} "
                f"compile_s={s:.3f}")
