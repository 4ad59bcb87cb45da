"""Scoring kernel: share of its roofline in %.  The least time for the
bytes of the mapspace rows it scored (`bench/work.py`) at the device's
peak HBM bandwidth (`bench/peaks.json`), over its device time in the
traced query."""
from bench import work


def read(ctx):
    s = ctx.device.time_matching("mapspace_eval")
    if not s or not ctx.kernel_rows:
        return None
    ops, nbytes = work.rows_work(ctx.kernel_rows, *ctx.layout)
    ctx.notes["mapspace_eval_roofline"] = {
        "rows": ctx.kernel_rows, "ops": ops, "bytes": nbytes,
        "kernel_s": s}
    return work.roofline(nbytes, s, ctx.peaks)
