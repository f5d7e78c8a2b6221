"""Programs compiled or loaded from the persistent cache while the
program's `ops.dispatch` spans were open (their `compiles` attribute),
per propagate call."""


def read(ctx):
    if ctx.spans is None:
        return None
    counts = [r.attrs["compiles"] for r in ctx.spans
              if r.name == "ops.dispatch" and "compiles" in r.attrs]
    return sum(counts) / ctx.propagates if counts else None
