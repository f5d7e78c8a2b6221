"""Points at which the TB kernel's updates produce a value, over the
points the propagate needs: over the program's `ops.tables` spans, the
`update_points` attribute (tiles x steps x slabs x planes x rows x
lanes) over `useful_points` (grid points x steps), main and remainder
tiles together."""


def redundancy(ctx, span: str):
    if ctx.spans is None:
        return None
    done = useful = 0
    for r in ctx.spans:
        a = r.attrs
        if r.name != span or "update_points" not in a:
            continue
        done += sum(a["update_points"])
        useful += sum(a["useful_points"])
    return done / useful if useful else None


def read(ctx):
    return redundancy(ctx, "ops.tables")
