"""Seconds from the process's start to the end of set-up: JAX's start,
the earth model on the device, planning, compiling or loading every
program, and one warm unit of the cell's own shapes."""


def read(ctx):
    return ctx.setup_s
