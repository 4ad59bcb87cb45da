"""Kernel host-to-device copy: seconds per query in the `kernel.transfer`
spans (lane layout, padding and `jnp.asarray`).  Nothing when the kernel
did not run or the harness holds no span seconds."""


def read(ctx):
    return getattr(ctx, "span_s", {}).get("kernel.transfer") or None
