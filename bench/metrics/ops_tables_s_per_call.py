"""Host seconds of the program's `ops.tables` span (per-tile table binning
and parameter pads before the time loop), per propagate call."""
from harness import yardstick


def read(ctx):
    sec = yardstick.span_seconds(ctx, "ops.tables")
    return None if sec is None else sec / ctx.propagates
