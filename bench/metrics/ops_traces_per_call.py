"""Traces of the one-chip propagate's jitted program while the program's
`ops.dispatch` spans were open (their `traced` attribute: true where the
call traced, lowered and compiled or loaded the program, false where the
jit's cache served it), per propagate call."""


def read(ctx):
    if ctx.spans is None:
        return None
    traced = [bool(r.attrs["traced"]) for r in ctx.spans
              if r.name == "ops.dispatch" and "traced" in r.attrs]
    return sum(traced) / ctx.propagates if traced else None
