"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (TPU v5e constants):

  compute    = HLO_FLOPs_per_device / peak_FLOPs          (197 TFLOP/s bf16)
  memory     = HLO_bytes_per_device / HBM_bw              (819 GB/s)
  collective = ring-transfer bytes per device / link_bw   (~50 GB/s/link)

cost_analysis() reports the per-device (SPMD-partitioned) module, so no
division by chip count is needed.  Collective bytes are NOT in
cost_analysis — we parse the compiled HLO text and, per collective op,
convert the instruction shape into ring-transfer bytes using the
replica-group size k:

  all-reduce:          2 * bytes * (k-1)/k        (reduce-scatter + gather)
  all-gather:          bytes * (k-1)/k            (bytes = gathered result)
  reduce-scatter:      bytes * (k-1)               (bytes = scattered result)
  all-to-all:          bytes * (k-1)/k
  collective-permute:  bytes
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 0.5, "u4": 0.5, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")
_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """`compiled.cost_analysis()` as a plain dict of the entry program's
    properties (use .get("flops") / .get("bytes accessed"))."""
    return dict(compiled.cost_analysis())


def _shape_bytes(shape_str: str) -> float:
    """'bf16[2048,1408]' or tuple '(f32[..], f32[..])' -> total bytes."""
    total = 0.0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return max(1, m.group(1).count(",") + 1)
    return default


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]              # static instruction counts
    result_bytes: Dict[str, float]
    transfer_bytes: Dict[str, float]    # trip-count weighted

    @property
    def total_transfer(self) -> float:
        return sum(self.transfer_bytes.values())


_COMP_RE = re.compile(r"^(ENTRY\s+)?(%[\w.\-]+)\s*\(.*\)\s*->.*\{")
_WHILE_RE = re.compile(r"while\(.*?condition=(%[\w.\-]+),\s*"
                       r"body=(%[\w.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=(%[\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str):
    """-> (comps: name -> list[str] lines, entry_name)."""
    comps: Dict[str, List[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line.strip())
    return comps, entry


def _computation_multipliers(comps, entry) -> Dict[str, float]:
    """Execution multiplier per computation: while bodies run trip-count
    times (XLA cost analysis counts them once); nested loops compose."""
    # edges: (caller -> callee, weight)
    edges: Dict[str, List[Tuple[str, float]]] = {c: [] for c in comps}
    for name, lines in comps.items():
        for ls in lines:
            wm = _WHILE_RE.search(ls)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trip = 1.0
                consts = [int(x) for x in _CONST_RE.findall(
                    "\n".join(comps.get(cond, [])))]
                if consts:
                    trip = float(max(consts))
                edges[name].append((body, trip))
                edges[name].append((cond, trip))
                continue
            for callee in _CALLS_RE.findall(ls):
                if callee in comps:
                    edges[name].append((callee, 1.0))
    mult = {c: 0.0 for c in comps}
    if entry is None:
        return {c: 1.0 for c in comps}
    mult[entry] = 1.0
    # relax (call graph is a DAG)
    for _ in range(len(comps)):
        changed = False
        for name in comps:
            if mult[name] == 0.0:
                continue
            for callee, w in edges[name]:
                want = mult[name] * w
                if want > mult[callee]:
                    mult[callee] = want
                    changed = True
        if not changed:
            break
    return mult


# ---------------------------------------------------------------------------
# Trip-count-aware HLO cost analysis.
#
# XLA's compiled.cost_analysis() counts a while-loop body ONCE, so any
# scanned program (layer scan, KV-block scan, SSD chunk scan) is
# under-reported by its trip count.  We therefore re-derive FLOPs/bytes from
# the HLO text with per-computation execution multipliers:
#   * FLOPs: every `dot` op = 2 * prod(result_dims) * contraction_size
#     (matmuls dominate; elementwise flops are ignored — consistent with a
#     MACs-based roofline), weighted by the enclosing computation's
#     multiplier;
#   * bytes: operand + result sizes of data-moving top-level instructions
#     (fusion/dot/copy/slice/gather/collective...), skipping instructions
#     inside fusion bodies (fused intermediates never reach HBM).
# compiled.cost_analysis() is still recorded as a cross-check lower bound.
# ---------------------------------------------------------------------------
_INSTR_RE = re.compile(
    r"^(%[\w.\-]+)\s*=\s*((?:\([^)]*\))|[\w\[\],]+(?:\{[^}]*\})?)\s+"
    r"([\w\-]+)\(([^)]*)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_BYTE_OPS = ("fusion", "dot", "copy", "dynamic-update-slice",
             "dynamic-slice", "gather", "scatter", "reduce", "transpose",
             "concatenate", "convolution", "pad", "select-and-scatter",
             "reverse", "all-reduce", "all-gather", "reduce-scatter",
             "all-to-all", "collective-permute", "convert", "broadcast",
             "iota", "reshape", "slice", "add", "multiply", "custom-call")
_NO_BYTE_OPS = ("tuple", "get-tuple-element", "parameter", "constant",
                "bitcast", "while", "conditional", "after-all")


def _dims(shape_str: str):
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None, []
    dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    return m.group(1), dims


def parse_hlo_costs(hlo_text: str):
    """-> dict(flops=..., bytes=...) with while-trip weighting."""
    comps, entry = _split_computations(hlo_text)
    mult = _computation_multipliers(comps, entry)
    # computations called from fusion instructions: exclude from bytes
    fusion_bodies = set()
    for name, lines in comps.items():
        for ls in lines:
            if re.search(r"\bfusion\(", ls):
                for callee in _CALLS_RE.findall(ls):
                    fusion_bodies.add(callee)
    # symbol table: instruction name -> shape string (per computation)
    flops = 0.0
    byts = 0.0
    for name, lines in comps.items():
        w = mult.get(name, 1.0) or 1.0
        shapes: Dict[str, str] = {}
        for ls in lines:
            m = _INSTR_RE.match(ls.replace("ROOT ", ""))
            if not m:
                continue
            iname, shape_str, op, operands = m.groups()
            shapes[iname] = shape_str
            if op == "dot":
                _, rdims = _dims(shape_str)
                cm = _CONTRACT_RE.search(ls)
                contract = 1
                ops = [o for o in re.findall(r"%[\w.\-]+", operands)]
                if cm and ops:
                    lhs_shape = shapes.get(ops[0], "")
                    _, ldims = _dims(lhs_shape)
                    for ci in (int(x) for x in cm.group(1).split(",")
                               if x != ""):
                        if ci < len(ldims):
                            contract *= ldims[ci]
                import math as _m
                flops += 2.0 * _m.prod(rdims or [1]) * contract * w
            if name in fusion_bodies:
                continue
            if op in _NO_BYTE_OPS or op.endswith("-done"):
                continue
            b = _shape_bytes(shape_str)
            for o in re.findall(r"%[\w.\-]+", operands):
                if o in shapes:
                    b += _shape_bytes(shapes[o])
            byts += b * w
    return {"flops": flops, "bytes": byts}


def parse_collectives(hlo_text: str, n_devices: int) -> CollectiveStats:
    counts = {c: 0 for c in _COLLECTIVES}
    rbytes = {c: 0.0 for c in _COLLECTIVES}
    tbytes = {c: 0.0 for c in _COLLECTIVES}
    comps, entry = _split_computations(hlo_text)
    mult = _computation_multipliers(comps, entry)
    for name, lines in comps.items():
        w_exec = mult.get(name, 1.0) or 1.0
        for ls in lines:
            m = re.match(
                r"%?[\w.\-]+\s*=\s*"
                r"((?:\([^)]*\))|[\w\[\],]+(?:\{[^}]*\})?)\s+"
                r"([\w\-]+)(\(|\.)", ls.replace("ROOT ", ""))
            if not m:
                continue
            base = m.group(2).replace("-start", "")
            if base not in _COLLECTIVES:
                continue
            shape_bytes = _shape_bytes(m.group(1))
            k = _group_size(ls, n_devices)
            counts[base] += 1
            rbytes[base] += shape_bytes
            if base == "all-reduce":
                t = 2.0 * shape_bytes * (k - 1) / k
            elif base == "all-gather":
                t = shape_bytes * (k - 1) / k
            elif base == "reduce-scatter":
                t = shape_bytes * (k - 1)
            elif base == "all-to-all":
                t = shape_bytes * (k - 1) / k
            else:  # collective-permute
                t = shape_bytes
            tbytes[base] += t * w_exec
    return CollectiveStats(counts=counts, result_bytes=rbytes,
                           transfer_bytes=tbytes)


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float          # MODEL_FLOPS / (HLO_FLOPs x chips)
    roofline_fraction: float     # bound_term / sum? see EXPERIMENTS.md

    def as_dict(self):
        return dataclasses.asdict(self)


def make_roofline(*, flops_per_device: float, bytes_per_device: float,
                  collective_bytes: float, model_flops: float,
                  n_devices: int) -> Roofline:
    ct = flops_per_device / PEAK_FLOPS
    mt = bytes_per_device / HBM_BW
    lt = collective_bytes / ICI_BW
    terms = {"compute": ct, "memory": mt, "collective": lt}
    bottleneck = max(terms, key=terms.get)
    total_flops = flops_per_device * n_devices
    useful = model_flops / total_flops if total_flops else 0.0
    # fraction of the dominant term that is useful compute: how close the
    # achievable step time (max of terms) is to the ideal compute time of
    # the *model* flops.
    ideal = model_flops / (n_devices * PEAK_FLOPS)
    frac = ideal / max(max(terms.values()), 1e-30)
    return Roofline(flops_per_device=flops_per_device,
                    bytes_per_device=bytes_per_device,
                    collective_bytes=collective_bytes,
                    compute_s=ct, memory_s=mt, collective_s=lt,
                    bottleneck=bottleneck, model_flops=model_flops,
                    useful_ratio=useful, roofline_fraction=frac)


def model_flops_estimate(cfg, spec) -> float:
    """MODEL_FLOPS = 6*N*D for training (N = active params, D = tokens);
    2*N*D for inference steps."""
    n = cfg.active_param_count()
    if spec.kind == "train":
        tokens = spec.seq_len * spec.global_batch
        return 6.0 * n * tokens
    if spec.kind == "prefill":
        tokens = spec.seq_len * spec.global_batch
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * spec.global_batch
