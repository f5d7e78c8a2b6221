"""Share of the sparse-term slots the TB kernel computes that hold a live
source or receiver entry: over the program's `ops.tables` spans, the live
entries times the steps each table serves, over the tiles x cap slots
times those steps, sources and receivers together."""


def fill(ctx, span: str):
    if ctx.spans is None:
        return None
    live = slots = 0
    for r in ctx.spans:
        a = r.attrs
        if r.name != span or "steps" not in a:
            continue
        for i, steps in enumerate(a["steps"]):
            live += (a["src_live"][i] + a["rec_live"][i]) * steps
            slots += (a["src_slots"][i] + a["rec_slots"][i]) * steps
    return 100.0 * live / slots if slots else None


def read(ctx):
    return fill(ctx, "ops.tables")
