"""Host seconds of the program's `survey.prep` spans (per-shot precompute,
table binning and batch stacking), per shot."""
from harness import yardstick


def read(ctx):
    sec = yardstick.span_seconds(ctx, "survey.prep")
    return None if sec is None else sec / sum(u.shots for u in
                                              ctx.window.units)
