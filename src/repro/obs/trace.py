"""Structured tracing for the DSE pipeline: nestable spans, JSONL +
Chrome `trace_event` export, and the spans on the JAX profiler's clock.

The engine's speed claims (packed `run_search`, fused cross-arch kernel
calls, bandit sample-efficiency) rest on phase splits — host build vs
device score vs cache traffic — that were previously only measurable by
instrumenting benchmark scripts by hand.  A `Tracer` records *host-side*
spans into a thread-safe in-memory `TraceBuffer`:

    tr = Tracer()
    with tr.span("score", phase=True, rows=4096):
        ...
    tr.export_chrome("trace.json")      # load in chrome://tracing/Perfetto
    tr.phase_times()                    # {"score": 0.41, ...} seconds

Design rules (ISSUE: zero-overhead-when-off, never inside jit):

  * the default tracer everywhere is `NULL_TRACER`, whose `span()` returns
    one shared no-op context manager — the off path costs two attribute
    lookups and no allocation;
  * spans are host-side only and must never be created inside jit-traced
    code.  JAX dispatch is async: a span that should include device time
    must bracket the `np.asarray(...)`/`block_until_ready` that forces the
    result (every instrumented call site in `core.backend` /
    `search.batch_frontier` converts to numpy inside its span, so device
    time lands in the span that launched the work);
  * instrumented library code (mapper, backend, cache) reads the *ambient*
    tracer via `current_tracer()` instead of growing a `tracer=` parameter
    on every function; `activate(tr)` scopes it (contextvar — safe across
    threads and nested searches), and `activate(tr, role=...)` names the
    thread's role ("main" for the thread that runs `run_search`,
    "prefetch" for its builder thread), which every span records;
  * while a recording span is open, a `jax.profiler.TraceAnnotation` of
    the same name is open too, so under the JAX profiler the program's
    spans sit in the profile's host plane on the device ops' clock;
  * finished intervals measured elsewhere (JAX's own trace/lower/compile
    events, `obs.jax_events`) enter the buffer through `Tracer.record`.

Spans flagged `phase=True` are the driver's non-overlapping pipeline
phases (propose / static-filter / pack / validate / score / cache-* /
assemble / frontier-update, plus the streaming driver's prefetch-build /
device-wait / cache-flush); `phase_times()` sums exactly those, so
nested detail spans (kernel groups, per-lookup cache gets) never double
count.  Phase spans never nest inside each other *on one thread*; the
streaming driver's builder thread legitimately holds pack/validate spans
while the main thread sits in device-wait, so summed phase time may
exceed wall time exactly when host and device genuinely overlapped;
`phase_times_by_role()` splits the sum by thread role, so the main
thread's phases alone never exceed the wall time.
"""
from __future__ import annotations

import contextvars
import dataclasses
import io
import json
import sys
import threading
import time
from typing import Any, Dict, List, Optional

#: The driver's non-overlapping pipeline phases, in pipeline order.  This
#: is the one canonical source for `phase=True` span names: `phase_times()`
#: accounting, bench_obs coverage claims, and the R-TRACE static-analysis
#: rule (docs/static-analysis.md) all key off it — a phase name used
#: anywhere else must be added here first.
DRIVER_PHASES = ("propose", "static-filter", "pack", "validate",
                 "cache-key", "cache-get", "prefetch-build", "score",
                 "device-wait", "cache-put", "assemble", "frontier-update",
                 "cache-flush")

#: All phase-flagged span names repo-wide: the driver phases plus the
#: serving engine's per-tick phase.
PHASES = DRIVER_PHASES + ("serve.tick",)

# ---------------------------------------------------------------------------
# span records
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Span:
    """One finished (or open) span.  Times are `time.perf_counter()`
    seconds; `t_wall0` anchors the buffer to the unix clock once."""
    name: str
    t0: float
    t1: Optional[float] = None
    depth: int = 0
    parent: Optional[int] = None        # index into the buffer's span list
    index: int = -1
    thread: int = 0
    phase: bool = False
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    role: Optional[str] = None          # the thread's role (`activate`)

    @property
    def duration(self) -> float:
        return (self.t1 if self.t1 is not None else self.t0) - self.t0

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "t0": self.t0, "t1": self.t1,
                "depth": self.depth, "parent": self.parent,
                "index": self.index, "thread": self.thread,
                "phase": self.phase, "attrs": self.attrs,
                "role": self.role}


def family_of(name: str) -> str:
    """Lane grouping for the Chrome export: the part before the first
    '.' ("backend.jnp" -> "backend"); bare names are their own family."""
    return name.split(".", 1)[0]


class TraceBuffer:
    """Thread-safe store of spans."""

    def __init__(self):
        self._lock = threading.Lock()
        self.spans: List[Span] = []
        self.t_wall0 = time.time()
        self.t_perf0 = time.perf_counter()

    # -- recording -------------------------------------------------------
    def append(self, span: Span) -> int:
        with self._lock:
            span.index = len(self.spans)
            self.spans.append(span)
            return span.index

    def perf_of_wall(self, t: float) -> float:
        """A `time.time()` reading on this buffer's `perf_counter` clock."""
        return self.t_perf0 + (t - self.t_wall0)

    # -- views -----------------------------------------------------------
    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self.spans)

    def __len__(self) -> int:
        with self._lock:
            return len(self.spans)

    def phase_times(self) -> Dict[str, float]:
        """Total seconds per phase-flagged span name (the driver's
        non-overlapping pipeline phases — see module docstring)."""
        out: Dict[str, float] = {}
        for s in self.snapshot():
            if s.phase and s.t1 is not None:
                out[s.name] = out.get(s.name, 0.0) + s.duration
        return out

    def phase_times_by_role(self) -> Dict[str, Dict[str, float]]:
        """`phase_times()` split by the recording thread's role:
        {role: {phase: seconds}} ("other" for spans without a role)."""
        out: Dict[str, Dict[str, float]] = {}
        for s in self.snapshot():
            if s.phase and s.t1 is not None:
                d = out.setdefault(s.role or "other", {})
                d[s.name] = d.get(s.name, 0.0) + s.duration
        return out

    def t_end(self) -> float:
        """The latest span end (`t_perf0` for an empty buffer)."""
        return max((s.t1 for s in self.snapshot() if s.t1 is not None),
                   default=self.t_perf0)

    # -- exports ---------------------------------------------------------
    def to_jsonl(self, metrics: Optional[Dict[str, Any]] = None) -> str:
        """One JSON object per line: a `meta` header, then every span in
        record order, then one `metrics` line (a `Metrics.snapshot()`)."""
        buf = io.StringIO()
        buf.write(json.dumps({"meta": {"t_wall0": self.t_wall0,
                                       "t_perf0": self.t_perf0,
                                       "n_spans": len(self)}}) + "\n")
        for s in self.snapshot():
            buf.write(json.dumps({"span": s.to_dict()}) + "\n")
        buf.write(json.dumps({"metrics": metrics or {}}) + "\n")
        return buf.getvalue()

    @staticmethod
    def from_jsonl(text: str) -> "TraceBuffer":
        """Rebuild a buffer's spans from `to_jsonl()` output (round-trip
        tested; the `metrics` line is a snapshot and is not read back)."""
        buf = TraceBuffer()
        for line in text.splitlines():
            if not line.strip():
                continue
            row = json.loads(line)
            if "meta" in row:
                buf.t_wall0 = row["meta"]["t_wall0"]
                buf.t_perf0 = row["meta"]["t_perf0"]
            elif "span" in row:
                d = row["span"]
                buf.spans.append(Span(
                    name=d["name"], t0=d["t0"], t1=d["t1"],
                    depth=d["depth"], parent=d["parent"],
                    index=d["index"], thread=d["thread"],
                    phase=d["phase"], attrs=d["attrs"],
                    role=d.get("role")))
        return buf

    def chrome_trace(self, metrics: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
        """`trace_event`-format dict for chrome://tracing / Perfetto.

        One pid (the search process); one tid lane per span-name *family*
        so e.g. all `backend.*` dispatch spans share a lane separate from
        the driver phases.  Spans within a lane nest by time containment
        ("X" complete events), which matches the recorded nesting because
        families follow the call structure.  The counters of `metrics` (a
        `Metrics.snapshot()`) become "C" events stamped at the buffer's
        last span end."""
        events: List[Dict[str, Any]] = [{
            "ph": "M", "pid": 0, "tid": 0, "name": "process_name",
            "args": {"name": "repro-dse"}}]
        lanes: Dict[str, int] = {}
        spans = self.snapshot()
        for s in spans:
            fam = family_of(s.name)
            if fam not in lanes:
                lanes[fam] = len(lanes)
                events.append({"ph": "M", "pid": 0, "tid": lanes[fam],
                               "name": "thread_name",
                               "args": {"name": fam}})
        for s in spans:
            if s.t1 is None:
                continue
            args = {k: v for k, v in s.attrs.items()}
            if s.phase:
                args["phase"] = True
            events.append({
                "ph": "X", "pid": 0, "tid": lanes[family_of(s.name)],
                "name": s.name, "cat": "phase" if s.phase else "detail",
                "ts": (s.t0 - self.t_perf0) * 1e6,      # microseconds
                "dur": s.duration * 1e6,
                "args": args})
        ts_end = (self.t_end() - self.t_perf0) * 1e6
        counters = (metrics or {}).get("counters", {})
        for name, val in sorted(counters.items()):
            events.append({"ph": "C", "pid": 0, "tid": 0, "name": name,
                           "ts": ts_end, "args": {"value": val}})
        return {"traceEvents": events,
                "displayTimeUnit": "ms",
                "otherData": {"t_wall0": self.t_wall0}}


# ---------------------------------------------------------------------------
# tracers
# ---------------------------------------------------------------------------
def _annotation_cls():
    """`jax.profiler.TraceAnnotation`, once JAX is loaded (spans never
    import JAX themselves, so `repro.obs` stays usable without it)."""
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


_ANNOTATION = None


class _SpanCtx:
    """Live span handle: a context manager that records on exit.
    `set(**attrs)` attaches attributes discovered mid-span (row counts,
    group sizes)."""
    __slots__ = ("_tracer", "_span", "_ann")

    def __init__(self, tracer: "Tracer", span: Span, ann):
        self._tracer = tracer
        self._span = span
        self._ann = ann

    def set(self, **attrs) -> "_SpanCtx":
        self._span.attrs.update(attrs)
        return self

    def __enter__(self) -> "_SpanCtx":
        return self

    def __exit__(self, *exc) -> None:
        self._span.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._pop(self._span)
        return None


class _NullSpan:
    """Shared no-op span: the entire cost of tracing when it is off."""
    __slots__ = ()

    def set(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL_SPAN = _NullSpan()

#: finished intervals kept per thread for `Tracer.record`'s nesting
_RECORDED_KEEP = 64


class Tracer:
    """Records nestable spans into a `TraceBuffer`.

    Nesting is tracked per thread (a `threading.local` stack), so
    concurrent recorders interleave safely and each thread's spans parent
    correctly.  Metrics (`obs.metrics.Metrics`) ride along so instrumented
    code reaches both through one handle.  The first recording tracer of
    a process installs the JAX event listener (`obs.jax_events`)."""

    enabled = True

    def __init__(self, buffer: Optional[TraceBuffer] = None, metrics=None):
        from .jax_events import install
        from .metrics import Metrics
        self.buffer = buffer or TraceBuffer()
        self.metrics = metrics if metrics is not None else Metrics()
        self._local = threading.local()
        install()

    # -- span stack ------------------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, phase: bool = False, **attrs) -> _SpanCtx:
        st = self._stack()
        parent = st[-1] if st else None
        ann_cls = _annotation_cls()
        ann = ann_cls(name) if ann_cls is not None else None
        s = Span(name=name, t0=time.perf_counter(), depth=len(st),
                 parent=parent.index if parent else None,
                 thread=threading.get_ident(), phase=phase, attrs=attrs,
                 role=_ROLE.get())
        self.buffer.append(s)           # index assigned on append, so
        st.append(s)                    # children can reference it
        if ann is not None:
            ann.__enter__()
        return _SpanCtx(self, s, ann)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        elif span in st:                # tolerate out-of-order exits
            st.remove(span)

    def record(self, name: str, t0: float, t1: float, **attrs) -> Span:
        """Add a finished interval given on the wall clock (`time.time()`
        seconds, as JAX's monitoring events report them), as a child of
        the span open on the calling thread.  Intervals recorded earlier
        under the same parent that lie inside this one become its
        children, so nested events (a trace inside a trace) nest."""
        st = self._stack()
        parent = st[-1] if st else None
        pidx = parent.index if parent else None
        s = Span(name=name, t0=self.buffer.perf_of_wall(t0),
                 t1=self.buffer.perf_of_wall(t1),
                 depth=len(st), parent=pidx,
                 thread=threading.get_ident(), attrs=attrs,
                 role=_ROLE.get())
        self.buffer.append(s)
        done = getattr(self._local, "recorded", None)
        if done is None:
            done = self._local.recorded = []
        keep = []
        for r in done:
            if r.parent == pidx and r.t0 >= s.t0 and r.t1 <= s.t1:
                r.parent = s.index
            elif r.parent == pidx:
                keep.append(r)
        keep.append(s)
        self._local.recorded = keep[-_RECORDED_KEEP:]
        return s

    # -- convenience -----------------------------------------------------
    def phase_times(self) -> Dict[str, float]:
        return self.buffer.phase_times()

    def export_jsonl(self, path: str) -> str:
        text = self.buffer.to_jsonl(self.metrics.snapshot())
        with open(path, "w") as f:
            f.write(text)
        return path

    def export_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.buffer.chrome_trace(self.metrics.snapshot()), f)
        return path


class NullTracer:
    """The default tracer: every operation is a no-op.  `span()` hands
    back one shared object, so a disabled hot path allocates nothing."""

    enabled = False

    def __init__(self):
        from .metrics import NULL_METRICS
        self.buffer = None
        self.metrics = NULL_METRICS

    def span(self, name: str, phase: bool = False, **attrs) -> _NullSpan:
        return _NULL_SPAN

    def phase_times(self) -> Dict[str, float]:
        return {}


NULL_TRACER = NullTracer()

# ---------------------------------------------------------------------------
# ambient tracer (contextvar: thread- and nesting-safe)
# ---------------------------------------------------------------------------
_ACTIVE: "contextvars.ContextVar[object]" = contextvars.ContextVar(
    "repro_obs_tracer", default=NULL_TRACER)
_ROLE: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "repro_obs_role", default=None)


def current_tracer():
    """The ambient tracer instrumented library code records into
    (`NULL_TRACER` unless a scope activated one)."""
    return _ACTIVE.get()


class _Activation:
    __slots__ = ("_tracer", "_role", "_token", "_role_token")

    def __init__(self, tracer, role):
        self._tracer = tracer
        self._role = role
        self._token = self._role_token = None

    def __enter__(self):
        self._token = _ACTIVE.set(self._tracer)
        if self._role is not None:
            self._role_token = _ROLE.set(self._role)
        return self._tracer

    def __exit__(self, *exc):
        if self._role_token is not None:
            _ROLE.reset(self._role_token)
        _ACTIVE.reset(self._token)
        return None


def activate(tracer, role: Optional[str] = None) -> _Activation:
    """Scope `tracer` as the ambient tracer, and with `role` name the
    calling thread's role, which the spans opened in the scope record:

        with activate(tr, role="main"):
            run_search(...)             # library spans land in tr
    """
    return _Activation(tracer, role)


def deferred_sync(fn):
    """Mark `fn` as a *deferred-sync producer*: it deliberately returns
    un-forced JAX device values (async dispatch already issued) so a
    later consumer can overlap host work with device execution before
    forcing the results.

    The decorator is a runtime identity — it exists for the contract,
    which trimlint R-SYNC enforces statically:

      * every in-repo callsite of a `@deferred_sync` function must sit
        inside a trace span (the launch must be phase-attributed, just
        like a forcing sync must be);
      * the decorator may only mark functions that actually produce
        device values (a host-only `@deferred_sync` function is a
        finding, so the annotation cannot rot).

    The forcing side stays covered by the ordinary R-SYNC sync-site
    check: whoever converts the pending values to numpy must do so
    inside a span (the streaming driver's "device-wait" phase).
    """
    fn.__deferred_sync__ = True
    return fn


def as_tracer(trace) -> object:
    """Normalize a user-facing `trace=` argument:

    None       -> the ambient tracer (NULL_TRACER unless activated)
    False      -> NULL_TRACER (force off, even under an active ambient)
    True       -> a fresh recording Tracer
    a Tracer   -> itself
    """
    if trace is None:
        return current_tracer()
    if trace is False:
        return NULL_TRACER
    if trace is True:
        return Tracer()
    if hasattr(trace, "span") and hasattr(trace, "metrics"):
        return trace
    raise TypeError(f"trace must be None, a bool, or a Tracer-like "
                    f"object, got {type(trace).__name__}")
