"""`sources_precompute_s_per_call` in the survey cell: the same spans, per
shot."""
from harness import files

seconds = files.metric("sources_precompute_s_per_call").seconds


def read(ctx):
    sec = seconds(ctx)
    return None if sec is None else sec / sum(u.shots for u in
                                              ctx.window.units)
