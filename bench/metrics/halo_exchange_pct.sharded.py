"""Share of the traced window the chips spend in the halo exchange: the
device seconds of the collective-permute operations (start and done),
averaged over the chips traced, over the window's length."""
from harness import trace


def is_exchange(e: dict) -> bool:
    return "collective-permute" in trace.op_name(e)


def read(ctx):
    s = ctx.summary
    if s is None or not s.devices or not s.window_s > 0.0:
        return None
    seconds, n = s.seconds_of(is_exchange)
    if n == 0:
        return None
    return (100.0 * seconds / s.devices / s.window_s,
            f"{seconds!r} device seconds in {n} events on {s.devices} chips")
