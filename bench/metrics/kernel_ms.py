"""Scoring kernel: device milliseconds of the `mapspace_eval*` kernel in
the traced query.  Nothing when the kernel did not run."""


def read(ctx):
    s = ctx.device.time_matching("mapspace_eval")
    return None if not s else 1e3 * s
