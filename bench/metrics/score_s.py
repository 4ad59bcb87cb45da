"""Scoring dispatch: host seconds per query in the driver's `score` and
`device-wait` phases (host packing, transfer, dispatch and the wait for
the device)."""


def read(ctx):
    v = ctx.phases(("score", "device-wait"))
    return v if v > 0 else None
