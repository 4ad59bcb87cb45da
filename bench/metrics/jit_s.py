"""JAX program preparation: seconds per query in JAX's own trace, lower
and compile events (`jit.trace`, `jit.lower`, `jit.compile` spans of
`repro.obs.jax_events`; a compile-cache load lies inside `jit.compile`,
a nested trace is counted once).  0 when nothing was prepared; nothing
when the harness holds no span seconds."""

JIT_SPANS = ("jit.trace", "jit.lower", "jit.compile")


def read(ctx):
    span_s = getattr(ctx, "span_s", None)
    if span_s is None:
        return None
    return sum(span_s.get(n, 0.0) for n in JIT_SPANS)
