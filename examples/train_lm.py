"""End-to-end training driver: train SmolLM-135M-family model for a few
hundred steps with the full production stack (sharded train step, AdamW,
checkpointing + resume, deterministic data pipeline).

Default runs the reduced config on CPU in a couple of minutes; pass
--full --steps 300 on real hardware for the 135M model.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.train import train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    losses = train_loop(
        arch=args.arch, steps=args.steps, seq_len=args.seq_len,
        global_batch=args.global_batch, reduced=not args.full,
        ckpt_dir=args.ckpt_dir, log_every=20)
    drop = losses[0] - min(losses)
    print(f"\nloss: {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(best drop {drop:.4f})")
    assert losses[-1] < losses[0], "training did not reduce loss"
    print("OK: loss decreased over training")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
