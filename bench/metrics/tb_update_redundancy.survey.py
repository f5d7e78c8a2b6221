"""`tb_update_redundancy` in the survey cell, over the engine's per-shot
`survey.tables` spans."""
from harness import files

redundancy = files.metric("tb_update_redundancy").redundancy


def read(ctx):
    return redundancy(ctx, "survey.tables")
