"""JAX's own compile work as spans and counters of the ambient tracer.

One process-wide set of `jax.monitoring` listeners, installed by the
first recording `Tracer` once JAX is loaded, routes each event into the
tracer that is ambient on the thread that raised it (`current_tracer()`);
under `NULL_TRACER` an event costs one contextvar read.

  jit.trace         span    /jax/core/compile/jaxpr_trace_duration
  jit.lower         span    /jax/core/compile/jaxpr_to_mlir_module_duration
  jit.compile       span    /jax/core/compile/backend_compile_duration
                            (XLA compile, or the persistent-cache load
                            that replaces it)
  jit.cache_loads   counter persistent compile-cache hits
  jit.cache_load_s  counter seconds reading those entries
  jit.cache_misses  counter persistent compile-cache misses

Each span carries `fun=<the jitted function's name>` and is parented to
the span open on its thread (`Tracer.record`); a trace inside a trace
nests.  `jit_summary` folds them, with `core.batch_eval`'s
`jit.dispatches` / `jit.bucket_rows`, into `SearchReport.summary()["jit"]`.
"""
from __future__ import annotations

import sys
import threading
from typing import Any, Dict, Sequence

from .trace import current_tracer

SPAN_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
COUNTER_OF_EVENT = {
    "/jax/compilation_cache/cache_hits": "jit.cache_loads",
    "/jax/compilation_cache/cache_misses": "jit.cache_misses",
}
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
JIT_SPANS = tuple(SPAN_OF_EVENT.values())

_LOCK = threading.Lock()
_INSTALLED = False


def fun_of(name) -> str:
    """JAX names a lowered or compiled program `jit(<fun>)`; -> `<fun>`."""
    name = str(name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kw) -> None:
    name = SPAN_OF_EVENT.get(event)
    if name is None:
        return
    tr = current_tracer()
    if tr.enabled:
        tr.record(name, start_time, end_time,
                  fun=fun_of(kw.get("fun_name")))


def _on_event(event: str, **kw) -> None:
    name = COUNTER_OF_EVENT.get(event)
    if name is not None:
        current_tracer().metrics.counter(name).inc()


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == CACHE_LOAD_EVENT:
        current_tracer().metrics.counter("jit.cache_load_s").inc(duration)


def install() -> bool:
    """Register the listeners once per process; a no-op (False) until
    JAX has been imported by someone else."""
    global _INSTALLED
    if _INSTALLED:
        return True
    if "jax" not in sys.modules:
        return False
    with _LOCK:
        if not _INSTALLED:
            import jax.monitoring as mon
            mon.register_event_time_span_listener(_on_time_span)
            mon.register_event_listener(_on_event)
            mon.register_event_duration_secs_listener(_on_duration)
            _INSTALLED = True
    return True


def jit_summary(spans: Sequence[Any], snapshot: Dict[str, Any]
                ) -> Dict[str, Any]:
    """JAX program preparation in a traced run: per kind (`trace`,
    `lower`, `compile`) the number of events and their seconds (seconds
    of outermost events only, so a nested trace is not counted twice),
    the same per jitted function under `by_fun`, the persistent-cache
    counters, and the fused scorer's dispatches and bucket sizes."""
    kinds = {n[4:]: {"n": 0, "s": 0.0} for n in JIT_SPANS}
    by_fun: Dict[str, Dict[str, Dict[str, float]]] = {}
    for s in spans:
        if s.name not in JIT_SPANS or s.t1 is None:
            continue
        kind = s.name[4:]
        nested = (s.parent is not None
                  and spans[s.parent].name in JIT_SPANS)
        kinds[kind]["n"] += 1
        if not nested:
            kinds[kind]["s"] += s.duration
        f = by_fun.setdefault(s.attrs.get("fun", ""), {}).setdefault(
            kind, {"n": 0, "s": 0.0})
        f["n"] += 1
        f["s"] += s.duration
    c = snapshot.get("counters", {})
    return {**kinds,
            "cache_loads": int(c.get("jit.cache_loads", 0)),
            "cache_load_s": float(c.get("jit.cache_load_s", 0.0)),
            "cache_misses": int(c.get("jit.cache_misses", 0)),
            "dispatches": int(c.get("jit.dispatches", 0)),
            "bucket_rows": snapshot.get("histograms", {}).get(
                "jit.bucket_rows", {"count": 0}),
            "by_fun": by_fun}
