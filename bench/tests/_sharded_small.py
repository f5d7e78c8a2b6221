"""A small copy of the sharded cell (32^3 on a 2x2 mesh of forced host
devices, the Pallas kernel in interpret mode), run through the harness;
`test_sharded_cell.py` runs this file in a subprocess, since XLA fixes the
device count at its first use.  Prints the result line.

    python bench/tests/_sharded_small.py sound|fault
"""
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import jax  # noqa: E402

from harness import files, program, runner  # noqa: E402

CELL = "acoustic-so4-1024.sharded"


def main(mode):
    program.on_path()
    if mode == "fault":
        # leave out the x-exchange from the low neighbour: the strips
        # the halo should bring in stay zero
        from repro.distributed import halo
        shift = halo._shift_from_low
        halo._shift_from_low = lambda x, h, axis, dim: (
            jax.numpy.zeros_like(shift(x, h, axis, dim)) if dim == 0
            else shift(x, h, axis, dim))
    bench = files.benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    cfg = files.config(bench, cell["config"])
    # every key and formula as the real cell's, less work: a (16, 16)
    # block a chip, a 4-cell sponge, 10 steps, the planner's inner tiles
    # held to 8 wide
    cfg.update(shape=[32, 32, 32], time_ms=12.0, nbl=4,
               planner={"tiles": [8]})
    result = runner.run(bench, cell, 3015000123, 0.05, False,
                        jax.devices()[:4], time.perf_counter(), cfg=cfg,
                        mix=files.mix(cell["traffic"]),
                        peaks={"f32_vpu_flops_per_s": 1.0,
                               "hbm_bytes_per_s": 1.0})
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1])
