"""Points at which the sharded layer's updates produce a value, over the
points the propagate needs: over the program's `halo.tables` spans, the
`update_points` attribute (every shard's split first step, interior and
rim strips, and its inner passes' kernel trapezoids, over all tiles and
steps: the deep-halo rims and the kernel's own redundancy together) over
`useful_points` (global grid points x steps)."""
from harness import files


def read(ctx):
    return files.metric("tb_update_redundancy").redundancy(ctx,
                                                            "halo.tables")
