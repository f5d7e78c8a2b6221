"""Host seconds of the program's sparse-operator precompute (spans
`sources.precompute` and `sources.precompute_receivers`: the dense SM/SID
grids and decomposed wavelets built on the host and moved to the device),
per propagate call."""
from harness import yardstick

SPANS = ("sources.precompute", "sources.precompute_receivers")


def seconds(ctx):
    """The spans' total seconds, or None where the program has neither."""
    got = [yardstick.span_seconds(ctx, name) for name in SPANS]
    got = [sec for sec in got if sec is not None]
    return sum(got) if got else None


def read(ctx):
    sec = seconds(ctx)
    return None if sec is None else sec / ctx.propagates
