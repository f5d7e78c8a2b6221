"""Time one jitted `tb_time_tile` call: the TB kernel alone, without the
eager propagate loop's tables, pads and re-lowering around it.

Runs the plan the planner picks at the size asked for (acoustic
`(32, 32)` T 8, TTI `(16, 32)` T 2 at 512^3), and prints one line per
case: the best of `--reps` calls after a warm one, and the x-planes the
call's steps keep (summed over tiles and steps).  Sparse slots are
either empty, at window point 0 as the tile tables pad them, with no live
entry in any tile, or live, all at window point (h, h, h) and every slot
counted live, so that the slab holding plane h runs every slot's body
each step.  A kernel that bounds its slot loops by the tile's live count
runs no iteration over empty slots; one that loops over the caps (a
checkout before the counts) runs them all, bodies skipped.

  python3 benchmarks/tb_kernel_call.py [--src DIR] [--physics acoustic tti]
      [--n 512] [--caps 8,56 8,256 1,1] [--slots empty]

Caps `8,56` are the acoustic propagate's at 512^3 (sized to its fullest
tile), `8,256` the survey's (worst cases from its bucket key, 1 source
and 32 receivers), and `1,1` the least a table holds.  `--src` is the
`src/` directory of the checkout whose kernel is timed (default: this
one's), so two commits can be timed by one script.
"""
from __future__ import annotations

import argparse
import functools
import inspect
import os
import sys
import time

PLANS = {"acoustic": ((32, 32), 8), "tti": ((16, 32), 2)}


def kept_planes(ker, spec) -> int:
    """x-planes the call's steps keep, over tiles and steps."""
    ntx, nty = spec.ntiles
    r = spec.halo // spec.T
    if hasattr(ker, "step_slabs"):
        per_tile = sum(ker.step_slabs(spec, k)[2] * ker.step_slabs(spec, k)[3]
                       for k in range(spec.T))
    else:       # the schedule before `step_slabs`: [r, wx - r) every step
        b, nslab = ker._slab_planes(spec, r)
        per_tile = spec.T * b * nslab
    return ntx * nty * per_tile


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--src", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "src"))
    ap.add_argument("--physics", nargs="+", default=["acoustic"],
                    choices=sorted(PLANS))
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--caps", nargs="+", default=["8,56", "1,1"],
                    help="source,receiver slots a tile, one case each")
    ap.add_argument("--slots", default="empty", choices=("empty", "live"))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--label", default="")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.src))
    import jax
    import jax.numpy as jnp
    from repro.core.temporal_blocking import TBPlan
    from repro.kernels import ops, stencil_tb as ker, tb_physics as phys

    n = args.n
    interpret = jax.devices()[0].platform != "tpu"
    takes_counts = "counts" in inspect.signature(ker.tb_time_tile).parameters
    cases = [(name, caps) for name in args.physics for caps in args.caps]
    for name, caps in cases:
        physics = phys.PHYSICS[name]
        tile, T = PLANS[name]
        fills = dict(physics.param_fills)
        src_cap, rec_cap = (int(c) for c in caps.split(","))
        plan = TBPlan(tile=tile, T=T, radius=physics.step_radius(4))
        spec = ops.make_spec((n, n, n), plan, 4, 1e-3, (10.0,) * 3, src_cap,
                             rec_cap, physics=physics)
        h = spec.halo
        ntiles = spec.ntiles[0] * spec.ntiles[1]
        shape = (n + 2 * h, n + 2 * h, n)
        keys = jax.random.split(jax.random.PRNGKey(0),
                                len(physics.state_fields))
        states = tuple(0.01 * jax.random.normal(k, shape) for k in keys)
        params = tuple(jnp.full(shape, fills.get(f, 0.0))
                       for f in physics.param_fields)
        at = 0 if args.slots == "empty" else h
        src_coords = jnp.full((ntiles, src_cap, 3), at, jnp.int32)
        src_vals = jnp.zeros((ntiles, T, src_cap))
        rec_coords = jnp.full((ntiles, rec_cap, 3), at, jnp.int32)
        rec_w = jnp.zeros((ntiles, rec_cap))
        live = (0, 0) if args.slots == "empty" else (src_cap, rec_cap)
        counts = jnp.tile(jnp.asarray([live], jnp.int32), (ntiles, 1))
        call = jax.jit(functools.partial(ker.tb_time_tile, spec, physics,
                                         interpret=interpret))
        operands = (states, params, src_coords, src_vals, rec_coords, rec_w,
                    *((counts,) if takes_counts else ()))
        jax.block_until_ready(call(*operands))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(call(*operands))
            times.append(time.perf_counter() - t0)
        best = min(times)
        planes = kept_planes(ker, spec)
        print(f"{args.label} {name} {n}^3 tile {tile} T {T} caps "
              f"({src_cap},{rec_cap}) {args.slots} slots: call {best!r} s "
              f"(runs {[round(t, 5) for t in times]}), {planes} kept "
              f"planes, {best / planes * 1e6!r} us a kept plane", flush=True)


if __name__ == "__main__":
    main()
