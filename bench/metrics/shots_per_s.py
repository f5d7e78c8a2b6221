"""Shots whose traces reached the host, over the window's length by the
host clock."""


def read(ctx):
    w = ctx.window
    return sum(u.shots for u in w.units) / w.elapsed_s
