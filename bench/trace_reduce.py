"""Reduce a JAX profiler trace of one traced query to the device numbers
the benchmark reports: busy time (the union of the intervals in which an
operation ran on a device), device time per operation and per program,
and the idle gaps, each labelled with the driver phases the host had open.

The reduction works on plain data, `[{"name": plane, "lines": [{"name":
line, "events": [[name, start_ns, duration_ns], ...]}]}]`, which
`from_profile` takes from the `.xplane.pb` the profiler writes, so a
small recorded trace can be kept as JSON and checked in a test."""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def from_profile(log_dir: str) -> List[dict]:
    """The newest `.xplane.pb` under `log_dir` as plain planes."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            lines.append({"name": ln.name,
                          "events": [[e.name, float(e.start_ns),
                                      float(e.duration_ns)]
                                     for e in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def _line(plane: dict, name: str) -> Optional[dict]:
    return next((ln for ln in plane["lines"] if ln["name"] == name), None)


def find_event(planes: Sequence[dict], name: str) -> Tuple[float, float]:
    """(start_ns, end_ns) of the host event `name` (e.g. the query's
    TraceAnnotation)."""
    for pl in planes:
        if _line(pl, OPS_LINE) is not None:
            continue
        for ln in pl["lines"]:
            for ev_name, t0, dur in ln["events"]:
                if ev_name == name:
                    return t0, t0 + dur
    raise KeyError(f"no host event named {name!r} in the trace")


def short_name(name: str) -> str:
    """An op's HLO text is its name: keep what precedes " = ", and drop a
    program's "(fingerprint)"."""
    return name.split(" = ", 1)[0].split("(", 1)[0]


def _clip(events, lo: float, hi: float):
    for name, t0, dur in events:
        a, b = max(t0, lo), min(t0 + dur, hi)
        if b > a:
            yield name, a, b


def _union(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


@dataclasses.dataclass
class DeviceWindow:
    """Device activity inside one host window, in seconds."""
    window_s: float
    busy_s: float                       # mean over the devices used
    n_devices: int
    op_s: Dict[str, float]              # per op name, summed over devices
    module_s: Dict[str, float]          # per program, summed over devices
    gaps: List[Tuple[float, float]]     # idle (start_ns, end_ns), device 0

    def time_matching(self, pattern: str) -> Optional[float]:
        """Device seconds of the ops whose name holds `pattern`, else of
        the programs whose name holds it; None when nothing matches."""
        for table in (self.op_s, self.module_s):
            hits = [s for n, s in table.items() if pattern in n]
            if hits:
                return sum(hits)
        return None


def reduce_window(planes: Sequence[dict], t0_ns: float,
                  t1_ns: float) -> DeviceWindow:
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    busy: List[float] = []
    gaps: List[Tuple[float, float]] = []
    for pl in planes:
        ops = _line(pl, OPS_LINE)          # device planes: the chips
        if ops is None:
            continue
        spans = []
        for name, a, b in _clip(ops["events"], t0_ns, t1_ns):
            op_s[short_name(name)] += (b - a) * 1e-9
            spans.append((a, b))
        if not spans:
            continue
        for name, a, b in _clip((_line(pl, MODULES_LINE) or
                                 {"events": []})["events"], t0_ns, t1_ns):
            module_s[short_name(name)] += (b - a) * 1e-9
        merged = _union(spans)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if not gaps:
            edges = [t0_ns] + [x for ab in merged for x in ab] + [t1_ns]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    return DeviceWindow(window_s=(t1_ns - t0_ns) * 1e-9,
                        busy_s=sum(busy) / len(busy) if busy else 0.0,
                        n_devices=len(busy), op_s=dict(op_s),
                        module_s=dict(module_s), gaps=gaps)


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host_spans: Sequence[Tuple[str, float, float]],
               top: int = 10) -> List[List]:
    """Idle seconds by what the host was doing: every gap is cut at the
    edges of the host spans (`host_spans` are (name, start_ns, end_ns) on
    the trace's clock), each piece is labelled with the phases open in it
    ("no-phase" when none), and pieces of one label are summed.
    -> the `top` labels by idle seconds, [[label, seconds], ...]."""
    by_label: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        inside = [(n, max(s0, a), min(s1, b)) for n, s0, s1 in host_spans
                  if s0 < b and s1 > a]
        cuts = sorted({a, b} | {x for _, s0, s1 in inside for x in (s0, s1)})
        for lo, hi in zip(cuts, cuts[1:]):
            names = sorted({n for n, s0, s1 in inside
                            if s0 <= lo and hi <= s1})
            by_label["+".join(names) or "no-phase"] += (hi - lo) * 1e-9
    return [[k, v] for k, v in sorted(by_label.items(),
                                      key=lambda kv: -kv[1])[:top]]


def top_ops(win: DeviceWindow, top: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(win.op_s.items(),
                                      key=lambda kv: -kv[1])[:top]]
