"""Device time of every operation other than the temporally-blocked kernel
(the state pads, source-value gathers and the receiver combine around it),
as a share of all device operation time in the traced window."""
from harness import yardstick


def read(ctx):
    s = ctx.summary
    if s is None or not s.ops:
        return None
    kernel, n = s.seconds_of(yardstick.is_tb_kernel)
    if n == 0:
        return None
    total = sum(sec for _, sec in s.ops)
    return 100.0 * (total - kernel) / total
