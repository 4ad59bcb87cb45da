"""Device: idle share of the traced query in %, 1 - (union of the
intervals in which an operation ran on the device / the query's
window)."""


def read(ctx):
    w = ctx.device
    if not w.n_devices or w.window_s <= 0:
        return None
    return 100.0 * (1.0 - w.busy_s / w.window_s)
