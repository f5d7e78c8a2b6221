"""Share of the traced window in which no operation ran on a chip,
averaged over the chips of the sharded cell."""
from harness import yardstick


def read(ctx):
    return yardstick.device_idle(ctx)
