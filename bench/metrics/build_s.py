"""Mapspace build: host seconds per query in the driver's `pack` and
`validate` phases (`repro.obs` phase spans, summed by thread)."""


def read(ctx):
    v = ctx.phases(("pack", "validate"))
    return v if v > 0 else None
