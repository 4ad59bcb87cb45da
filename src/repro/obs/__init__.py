"""repro.obs — dependency-free observability for the DSE pipeline.

  trace      nestable spans -> thread-safe TraceBuffer with JSONL and
             Chrome trace_event (chrome://tracing / Perfetto) export, each
             recording span also a `jax.profiler.TraceAnnotation`;
             `NULL_TRACER` is the zero-overhead default and `activate()`
             scopes an ambient tracer (and the thread's role) for library
             code
  jax_events JAX's trace/lower/compile events as `jit.*` spans and its
             compile-cache traffic as counters of the ambient tracer
  metrics    named counters / gauges / histograms (p50/p95/max) with a
             JSON-safe `snapshot()`
  progress   typed ProgressEvent stream (arch evaluated/skipped, cache
             lookup, frontier grew, round finished) with pluggable sinks —
             `verbose=True` is the ConsoleSink; a service sink streams
             incremental frontier updates to clients
  manifest   RunManifest: git sha, backend, space/constraints digests,
             wall time by phase — written alongside cached results

Instrumentation rules: spans are host-side only (never inside jit-traced
code) and bracket the numpy conversion that forces async JAX dispatch, so
device time lands in the span that launched the work.
"""
from .jax_events import JIT_SPANS, jit_summary
from .manifest import (MANIFEST_DIR, RunManifest, build_manifest, git_sha,
                       space_digest)
from .metrics import (NULL_METRICS, Counter, Gauge, Histogram, Metrics,
                      NullMetrics)
from .progress import (EVENT_KINDS, CollectSink, ConsoleSink, EventCursor,
                       ProgressEvent, ProgressStream, ReplaySink, as_stream)
from .trace import (DRIVER_PHASES, NULL_TRACER, PHASES, NullTracer, Span,
                    TraceBuffer, Tracer, activate, as_tracer,
                    current_tracer, deferred_sync, family_of)

__all__ = [n for n in dir() if not n.startswith("_")]
