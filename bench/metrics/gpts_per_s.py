"""Grid points x steps of the whole propagates the window completed, over
the window's length by the host clock, in billions per second."""


def read(ctx):
    w = ctx.window
    return sum(u.point_steps for u in w.units) / w.elapsed_s / 1e9
