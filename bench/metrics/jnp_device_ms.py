"""jnp scorer: device milliseconds of the jitted
`evaluate_batch_multi` program in the traced query.  Nothing when it did
not run."""


def read(ctx):
    s = ctx.device.time_matching("evaluate_batch_multi")
    return None if not s else 1e3 * s
