"""Beyond-paper: streaming DSE pipeline (search/driver.py overlap=True).

Runs the same exhaustive search twice — sequential (`overlap=False`) and
streaming (`overlap=True`, round k+1's host build on a prefetch thread
while round k's fused dispatches execute) — and checks the pipeline
contract:

  * identity: the streaming loop elects bit-identical winners, history,
    and evaluation order (the whole point of the lookahead contract);
  * overlap: the exported trace proves *real* concurrency — summed
    per-thread phase busy-time exceeds the union wall-clock of all phase
    spans, which a single-threaded loop cannot do;
  * throughput: wall-clock speedup is recorded in every regime.  On a
    CPU host the "device" work executes on the same cores the build
    thread needs, so overlap is zero-sum once XLA saturates them — the
    speedup floor here is only a no-harm bound, and the >=1.25x (fast)
    / >=1.4x (full) speedup claim is enforced when a real accelerator
    backend is attached (same gating idiom as bench_backend_dispatch);
  * jit visibility: warm arms reuse every (sig, bucket, device)
    executable — `summary()['jit']` shows dispatches but no recompiles.

Both timed arms run against warm jit executables (a discarded warmup arm
compiles them) so the comparison is steady-state-vs-steady-state.
"""
from __future__ import annotations

import time
from collections import defaultdict

from repro.core import (Conv2D, FC, MapperConfig, TaskDescription,
                        generate_arch_space)
from repro.search import run_search

from .common import claim


def _task():
    return TaskDescription(
        name="overlap-bench", input_shape=(16, 16, 3), batch_size=4,
        processing_type="Inference",
        layers=(Conv2D(16, (3, 3), (1, 1), (1, 1), name="c1"),
                Conv2D(32, (3, 3), (1, 1), (1, 1), name="c2"),
                FC(10, name="fc")))


def _archs():
    return list(generate_arch_space(num_pes=(16, 32, 64, 128),
                                    rf_words=(64, 128),
                                    gbuf_words=(2048, 8192), bits=16))


def _interval_union(iv):
    iv = sorted(iv)
    tot, lo, hi = 0.0, None, None
    for a, b in iv:
        if lo is None:
            lo, hi = a, b
        elif a > hi:
            tot += hi - lo
            lo, hi = a, b
        else:
            hi = max(hi, b)
    if lo is not None:
        tot += hi - lo
    return tot


def _busy_ratio(rep):
    """Summed per-thread phase busy-time over the union wall of all
    phase spans.  > 1 only when two threads hold phase spans at the same
    instant — the signature of genuine build/score overlap."""
    by_thread = defaultdict(list)
    for s in rep.tracer.buffer.snapshot():
        if s.phase and s.t1 is not None:
            by_thread[s.thread].append((s.t0, s.t1))
    if not by_thread:
        return 1.0, 0
    busy = sum(_interval_union(v) for v in by_thread.values())
    wall = _interval_union([x for v in by_thread.values() for x in v])
    return busy / max(wall, 1e-12), len(by_thread)


def _fingerprint(rep):
    return (rep.best_coords, rep.goal_value(), rep.history,
            [r.hardware.name for r in rep.all_archs])


def run(max_mappings=2000):
    import jax
    task, archs = _task(), _archs()
    cfg = MapperConfig(max_mappings=max_mappings, seed=0)
    kw = dict(goal="edp", cfg=cfg, round_size=1, trace=True)

    def arm(overlap):
        t0 = time.time()
        rep = run_search(task, archs, overlap=overlap, **kw)
        return time.time() - t0, rep

    jax.clear_caches()
    arm(False)                          # warmup: compile every executable
    seq_s, seq = arm(False)
    str_s, stream = arm(True)

    backend = jax.default_backend()
    speedup = seq_s / str_s
    seq_ratio, _ = _busy_ratio(seq)
    str_ratio, n_threads = _busy_ratio(stream)
    res = {"n_archs": len(archs), "max_mappings": max_mappings,
           "backend": backend, "seq_s": seq_s, "stream_s": str_s,
           "speedup": speedup, "seq_busy_ratio": seq_ratio,
           "stream_busy_ratio": str_ratio, "stream_threads": n_threads,
           "seq_us": seq_s * 1e6 / len(archs),
           "stream_us": str_s * 1e6 / len(archs)}

    assert stream.overlap and not seq.overlap
    claim(res, "streaming pipeline elects bit-identical winners, history "
          "and evaluation order",
          _fingerprint(stream) == _fingerprint(seq),
          f"best={stream.best.hardware.name} "
          f"value={stream.goal_value():.4g}")

    claim(res, "trace proves real overlap: streaming per-thread busy-time "
          "exceeds union phase wall (sequential cannot)",
          str_ratio > 1.05 and str_ratio > seq_ratio and n_threads >= 2,
          f"stream={str_ratio:.2f}x over {n_threads} threads "
          f"vs sequential={seq_ratio:.2f}x")

    jit = stream.summary()["jit"]
    claim(res, "warm streaming arm reuses every (sig, bucket, device) "
          "executable (dispatches counted, zero compiles)",
          jit["dispatches"] >= len(archs) and jit["compile"]["n"] == 0,
          f"dispatches={jit['dispatches']} "
          f"compiles={jit['compile']['n']}")

    if backend != "cpu":
        floor = 1.25 if max_mappings <= 600 else 1.4
        claim(res, f"overlapped search >={floor}x sequential "
              f"({backend} backend)",
              speedup >= floor, f"{speedup:.2f}x")
    else:
        # CPU: XLA execution and the build thread share the same cores,
        # so overlap is contention-bound — record, don't race (the
        # speedup claim arms when an accelerator backend is attached)
        claim(res, "streaming never slower than sequential beyond noise "
              "on CPU (speedup claim deferred to accelerator backend)",
              speedup >= 0.85, f"{speedup:.2f}x on {backend}")
    return res


def rows(res):
    return [
        ("pipeline_sequential", res["seq_us"],
         f"{res['seq_s']:.2f}s/{res['n_archs']}archs"),
        ("pipeline_streaming", res["stream_us"],
         f"speedup={res['speedup']:.2f}x "
         f"busy={res['stream_busy_ratio']:.2f}x "
         f"backend={res['backend']}"),
    ]
