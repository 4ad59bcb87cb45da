"""Counts the program's own instruments give: XLA compiles and
persistent-cache traffic from JAX's monitoring events, and rows scored
per path from the search's spans."""
from __future__ import annotations

import threading


class CompileStats:
    """XLA compile time and persistent-cache traffic, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.secs += duration
                self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self):
        with self._lock:
            return self.secs, self.compiles, self.hits, self.misses


def row_counts(tracer):
    """(kernel rows, jnp rows) scored in a traced search, from the fused
    and per-arch dispatch spans."""
    kern = jnp_ = 0
    for s in tracer.buffer.snapshot():
        a = s.attrs
        if s.name == "fused.kernel-group":
            kern += a["rows"]
        elif s.name in ("fused.jnp-group", "fused.jnp-dispatch",
                        "backend.jnp"):
            jnp_ += a["rows"]
        elif s.name == "backend.pallas":
            kern += a["kernel_rows"]
            jnp_ += a["jnp_rows"]
    return kern, jnp_
