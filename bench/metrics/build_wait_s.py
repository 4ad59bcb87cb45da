"""Mapspace build the query waits for: seconds per query of the `main`
thread's `pack`, `validate` and `prefetch-build` phases (a build on the
prefetch thread counts only while the main thread waits for it).
Nothing when the harness holds no per-thread phase seconds."""


def read(ctx):
    main = getattr(ctx, "main_phase_s", None)
    if not main:
        return None
    v = sum(main.get(n, 0.0) for n in ("pack", "validate", "prefetch-build"))
    return v if v > 0 else None
