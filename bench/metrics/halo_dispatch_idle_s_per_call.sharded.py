"""Device-idle seconds while the program's `halo.dispatch` span, or a span
inside it, was the innermost open (the sharded entry's host binning of
the source and receiver tables, and its jit's trace, lowering, compile or
load and enqueue, with nothing on the device to hide them), per
propagate."""

SPAN = "halo.dispatch"


def read(ctx):
    if ctx.spans is None or ctx.summary is None:
        return None
    if not any(r.name == SPAN for r in ctx.spans):
        return None
    labels = {SPAN} | {r.name for r in ctx.spans if r.parent == SPAN}
    idle = ctx.summary.idle_by_host_span()
    return sum(idle.get(name, 0.0) for name in labels) / ctx.propagates
