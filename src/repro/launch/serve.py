"""Serving drivers.

Two subcommands (the bare legacy form still runs the LM engine):

    # continuous-batching LM engine over synthetic requests
    PYTHONPATH=src python -m repro.launch.serve lm --arch smollm-135m \
        --requests 16 --batch 4

    # DSE-as-a-service demo: N clients submit the same design query
    # concurrently; identical in-flight requests coalesce onto one
    # run_search job and every client streams the same event history
    PYTHONPATH=src python -m repro.launch.serve dse --clients 4 \
        --strategy exhaustive --goal edp
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import List, Optional


def main_lm(argv: Optional[List[str]] = None):
    import jax
    import numpy as np

    from ..configs import get_config, reduced_config
    from ..models import init_model
    from ..serve.engine import Request, ServeEngine
    from ..train import checkpoint as ckpt

    ap = argparse.ArgumentParser(prog="repro.launch.serve lm")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch) if args.full else reduced_config(args.arch)
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    if args.ckpt_dir:
        step = ckpt.latest_step(args.ckpt_dir)
        if step is not None:
            params = ckpt.restore(args.ckpt_dir, step, params)
            print(f"[serve] restored params from step {step}")

    engine = ServeEngine(cfg, params, batch=args.batch,
                         max_len=args.max_len)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              size=int(rng.integers(2, 12)))
        engine.submit(Request(rid=rid, prompt=prompt.astype(np.int32),
                              max_new_tokens=args.max_new_tokens))
    ticks = engine.run_until_drained()
    dt = time.time() - t0
    total_toks = sum(len(r.out_tokens) for r in engine.done.values())
    print(f"[serve] {len(engine.done)} requests, {total_toks} tokens, "
          f"{ticks} ticks, {dt:.1f}s "
          f"({total_toks / max(dt, 1e-9):.1f} tok/s on CPU)")


def main_dse(argv: Optional[List[str]] = None):
    from ..core import Conv2D, FC, Pool2D, TaskDescription
    from ..obs import Tracer
    from ..search.space import ArchSpace
    from ..serve.dse_service import DSEService, SearchQuery

    ap = argparse.ArgumentParser(prog="repro.launch.serve dse")
    ap.add_argument("--clients", type=int, default=4,
                    help="concurrent identical submits (coalesce demo)")
    ap.add_argument("--distinct", type=int, default=1,
                    help="additional distinct queries (separate jobs)")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--strategy", default="exhaustive")
    ap.add_argument("--goal", default="edp")
    ap.add_argument("--budget", type=int, default=None)
    ap.add_argument("--constraints", default="",
                    help='e.g. "area_mm2<=5"')
    ap.add_argument("--timeout-s", type=float, default=None)
    ap.add_argument("--cache-dir", default="",
                    help="persistent warm cache tier (shared)")
    ap.add_argument("--stream", action="store_true",
                    help="print every client-0 progress event")
    ap.add_argument("--trace", default="",
                    help="write a Chrome trace of the service here")
    args = ap.parse_args(argv)

    task = TaskDescription(
        name="cnn-demo", input_shape=(16, 16, 3), batch_size=4,
        processing_type="Inference",
        layers=(Conv2D(8, (3, 3), (1, 1), (1, 1), name="c1"),
                Pool2D((2, 2), (2, 2), name="p1"),
                FC(10, name="fc")))
    space = ArchSpace.spatial(num_pes=(16, 32, 64), rf_words=(64,),
                              gbuf_words=(2048, 8192), bits=16)

    def query(seed: int = 0) -> SearchQuery:
        return SearchQuery(
            task=task, space=space, goal=args.goal,
            strategy=args.strategy, budget=args.budget, seed=seed,
            constraints=args.constraints or None)

    tracer = Tracer() if args.trace else None
    with DSEService(workers=args.workers,
                    cache=args.cache_dir or None,
                    default_timeout_s=args.timeout_s,
                    tracer=tracer) as svc:
        t0 = time.time()
        tickets = [svc.submit(query()) for _ in range(args.clients)]
        extra = [svc.submit(query(seed=s + 1))
                 for s in range(args.distinct)]
        if args.stream:
            for ev in tickets[0].events(timeout=300.0):
                print(f"  [{ev.kind}] " + " ".join(
                    f"{k}={v}" for k, v in ev.payload.items()))
        for i, tk in enumerate(tickets + extra):
            rep = tk.result(timeout=300.0)
            print(f"[dse] client {i}: {'coalesced' if tk.coalesced else 'admitted'} "
                  f"digest={tk.digest[:12]} best={rep.best.hardware.name} "
                  f"{args.goal}={rep.goal_value():.4e} "
                  f"evaluated={rep.n_evaluated}")
        snap = svc.snapshot()
        print(f"[dse] {time.time() - t0:.1f}s  stats: "
              + " ".join(f"{k}={v}" for k, v in snap.items()))
    if args.trace and tracer is not None:
        print(f"[dse] trace -> {tracer.export_chrome(args.trace)}")


def main(argv: Optional[List[str]] = None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "dse":
        return main_dse(argv[1:])
    if argv and argv[0] == "lm":
        return main_lm(argv[1:])
    return main_lm(argv)    # legacy flag-only invocation


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
