"""Device-idle seconds while the program's `ops.dispatch` span, or a span
inside it, was the innermost open (the eager driver tracing, lowering,
compiling or loading and enqueueing its time-tile scan and remainder
call, with nothing on the device to hide it), per propagate call.

The span's own length is no measure of this: where the host blocks
inside the dispatch until earlier device work ends, the span holds that
device time too."""

SPAN = "ops.dispatch"


def read(ctx):
    if ctx.spans is None or ctx.summary is None:
        return None
    if not any(r.name == SPAN for r in ctx.spans):
        return None
    # the span and those recorded inside it (the traced `ops.tile_pass`)
    labels = {SPAN} | {r.name for r in ctx.spans if r.parent == SPAN}
    idle = ctx.summary.idle_by_host_span()
    return sum(idle.get(name, 0.0) for name in labels) / ctx.propagates
