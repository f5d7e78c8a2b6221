"""`sparse_slot_fill_pct` in the survey cell, over the engine's per-shot
`survey.tables` spans (the bucket's worst-case caps)."""
from harness import files

fill = files.metric("sparse_slot_fill_pct").fill


def read(ctx):
    return fill(ctx, "survey.tables")
