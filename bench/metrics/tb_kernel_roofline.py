"""The temporally-blocked kernel's share of its roofline in the traced
window: the least time the chip could take for the useful flops and
compulsory bytes of the propagates run, over the kernel's device time."""
from harness import yardstick


def read(ctx):
    return yardstick.kernel_roofline(ctx)
