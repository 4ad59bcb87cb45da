"""Serving example: batched requests through the continuous-batching
engine (prefill + fused decode ticks, slot recycling).

    PYTHONPATH=src python examples/serve_lm.py
"""
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import reduced_config
from repro.models import init_model
from repro.serve.engine import Request, ServeEngine


def main():
    cfg = reduced_config("smollm-135m")
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, batch=4, max_len=64)

    rng = np.random.default_rng(0)
    for rid in range(8):
        prompt = rng.integers(0, cfg.vocab, size=rng.integers(2, 8))
        engine.submit(Request(rid=rid, prompt=prompt.astype(np.int32),
                              max_new_tokens=8))

    ticks = engine.run_until_drained()
    print(f"served {len(engine.done)} requests in {ticks} engine ticks "
          f"(batch={engine.batch} slots)\n")
    for rid in sorted(engine.done):
        req = engine.done[rid]
        print(f"  req {rid}: prompt[{len(req.prompt)}] -> "
              f"{req.out_tokens}")
    assert len(engine.done) == 8


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
