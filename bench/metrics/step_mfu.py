"""Useful flops of the traced window over its length by the host clock, as
a share of the float32 vector-unit peak: the whole step's share of the
chip, which bounds what any one kernel's roofline can claim end to end."""
from harness import yardstick


def read(ctx):
    return yardstick.step_mfu(ctx)
