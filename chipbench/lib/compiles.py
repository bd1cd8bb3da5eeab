"""Counts of JAX compilation events, to show that nothing compiles inside
the measured window.

JAX reports every executable it builds as a ``backend_compile`` event,
whether XLA compiled it or it was read back from the persistent cache; a
cache read is also reported as a ``cache_hits`` event.  So the backend
compiles are the first count minus the second.  Retrace seconds are the
time spent tracing to a jaxpr, lowering to MLIR and loading executables.
"""
from __future__ import annotations

from collections import Counter

import jax

BACKEND = "/jax/core/compile/backend_compile_duration"
HITS = "/jax/compilation_cache/cache_hits"
MISSES = "/jax/compilation_cache/cache_misses"
RETRACE = ("/jax/core/compile/jaxpr_trace_duration",
           "/jax/core/compile/jaxpr_to_mlir_module_duration", BACKEND)


class CompileCounter:
    """Listens to JAX's monitoring events from construction on."""

    def __init__(self):
        self.count: Counter = Counter()
        self.seconds: Counter = Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.count[name] += 1

    def _duration(self, name, secs, **_):
        self.count[name] += 1
        self.seconds[name] += secs

    def snapshot(self) -> tuple:
        return Counter(self.count), Counter(self.seconds)

    def since(self, snap) -> dict:
        c0, s0 = snap
        n = lambda k: self.count[k] - c0[k]
        return {"backend_compiles": n(BACKEND) - n(HITS),
                "cache_misses": n(MISSES), "cache_hits": n(HITS),
                "retrace_s": sum(self.seconds[k] - s0[k] for k in RETRACE)}
