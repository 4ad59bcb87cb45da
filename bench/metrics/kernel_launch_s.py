"""Kernel trace, lowering, program load and enqueue: seconds per query in
the `kernel.launch` spans (the `mapspace_eval_multi_fwd` call up to its
return).  Nothing when the kernel did not run or the harness holds no
span seconds."""


def read(ctx):
    return getattr(ctx, "span_s", {}).get("kernel.launch") or None
