"""Share of the traced window in which no operation ran on the device."""
from harness import yardstick


def read(ctx):
    return yardstick.device_idle(ctx)
