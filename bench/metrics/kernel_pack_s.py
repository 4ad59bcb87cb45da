"""Kernel host packing: seconds per query in the `kernel.pack` spans (the
per-row kernel tensors, `kernels/mapspace_eval/ops.py:_multi_rows`).
Nothing when the kernel did not run or the harness holds no span
seconds."""


def read(ctx):
    return getattr(ctx, "span_s", {}).get("kernel.pack") or None
