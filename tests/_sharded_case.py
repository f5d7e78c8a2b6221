"""Checks of the sharded entry point and the sharded layer's counters that
need four devices: `test_sharded_entry.py` runs this file in a subprocess
with four forced host devices (XLA fixes the device count at its first
use).  Each check prints one JSON line.

    python tests/_sharded_case.py parity|counts
"""
import dataclasses
import json
import sys

import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import sources as S
from repro.core.grid import Grid
from repro.core.temporal_blocking import TBPlan
from repro.distributed import halo as H
from repro.kernels import ops, ref
from repro.kernels import stencil_tb as ker
from repro.kernels import tb_physics as phys
from repro.launch import mesh as mesh_lib

SHAPE = (32, 32, 16)        # a (16, 16) block a shard on the 2x2 mesh
ORDER = 4


def case(seed=0, nt=7):
    """A seeded layered model; the source's trilinear cell straddles the
    x = 16 and y = 16 shard faces (a four-shard corner), the receivers
    cross the x face."""
    grid = Grid(shape=SHAPE, spacing=(10.0,) * 3)
    dt = grid.cfl_dt(3500.0, ORDER)
    rng = np.random.default_rng(seed)
    layer = np.floor(np.arange(SHAPE[2]) * 4 / SHAPE[2]) / 3.0
    vp = 1500.0 + 2000.0 * layer + rng.uniform(0, 50, SHAPE)
    m = jnp.asarray(1.0 / vp ** 2, jnp.float32)
    damp = jnp.asarray(rng.uniform(0, 0.05, SHAPE), jnp.float32)
    src = np.array([[155.0, 155.0, 70.0]]) + rng.uniform(0, 9.0, (1, 3))
    rec = np.stack([np.linspace(110.0, 200.0, 6), np.full(6, 163.0),
                    np.full(6, 72.0)], axis=1) + rng.uniform(0, 9.0, (6, 3))
    g = S.precompute(S.SparseOperator(src), grid,
                     S.ricker_wavelet(nt, dt, f0=15.0))
    gr = S.precompute_receivers(S.SparseOperator(rec), grid)
    return grid, dt, m, damp, g, gr


def the_1024_class(mesh, dt, physics=phys.ACOUSTIC, inner="pallas"):
    """The plan class `plan_hierarchy` picks at 1024^3: inner and outer T
    4, overlap, per-field depths (6, 8), an inner tile narrower in x than
    in y."""
    return H.DistTBPlan(mesh=mesh, grid_shape=SHAPE, physics=physics,
                        order=ORDER, T=4, dt=dt, spacing=(10.0,) * 3,
                        inner=inner, inner_plan=TBPlan((4, 8), 4, 2),
                        overlap=True)


def parity():
    mesh = mesh_lib.make_xy_mesh()
    nt = 7
    grid, dt, m, damp, g, gr = case(nt=nt)
    plan = the_1024_class(mesh, dt)
    shard = NamedSharding(mesh, P("data", "model", None))
    zero = jnp.zeros(SHAPE, jnp.float32)
    (r0, r1), rrec = ref.acoustic_reference(nt, zero, zero, m, damp, dt,
                                            grid.spacing, ORDER, g=g,
                                            receivers=gr)
    state = tuple(jax.device_put(zero, shard) for _ in range(2))
    params = {"m": jax.device_put(m, shard),
              "damp": jax.device_put(damp, shard)}
    (d0, d1), drec = H.sharded_propagate(plan, nt, state, params, g, gr)
    errs = {}
    for name, dv, rv in (("u_prev", d0, r0), ("u", d1, r1),
                         ("rec", drec[..., 0], rrec)):
        scale = float(jnp.max(jnp.abs(rv)))
        errs[name] = [float(jnp.max(jnp.abs(dv - rv))), scale]
    print(json.dumps({
        "errs": errs, "mesh": list(mesh.shape.values()),
        "T": plan.T, "inner_T": plan.inner_T, "tile": plan.inner_tile,
        "overlap": plan.overlap, "depths": plan.field_depths(plan.T),
        "remainder": nt % plan.T,
        "donated": all(a.is_deleted() for a in state),
        "shards": sorted({tuple(s.data.shape)
                          for s in d1.addressable_shards})}))


def _ppermutes(jaxpr, mesh, out):
    """(count, bytes sent over all shards) of every ppermute in `jaxpr`
    and the jaxprs inside it (each traced once): a shard's strip, times
    the pairs of its perm, times the groups along the other mesh axis."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ppermute":
            aval = eqn.invars[0].aval
            axes = eqn.params["axis_name"]
            axes = axes if isinstance(axes, tuple) else (axes,)
            groups = mesh.size // int(np.prod([mesh.shape[a] for a in axes]))
            out[0] += 1
            out[1] += (int(np.prod(aval.shape)) * aval.dtype.itemsize
                       * len(eqn.params["perm"]) * groups)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jex.core.ClosedJaxpr):
                    _ppermutes(sub.jaxpr, mesh, out)
                elif isinstance(sub, jex.core.Jaxpr):
                    _ppermutes(sub, mesh, out)
    return out


def _split_points(plan, h):
    """Points the split first step computes in one shard: the interior
    block and the four rim strips, enumerated as index boxes."""
    bx, by = plan.block
    r = plan.r_step
    wx, wy = bx + 2 * h, by + 2 * h
    band = h + 2 * r
    boxes = [(range(bx), range(by)), (range(band), range(wy)),
             (range(wx - band, wx), range(wy))]
    if bx > 2 * r:
        boxes += [(range(h, wx - h), range(band)),
                  (range(h, wx - h), range(wy - band, wy))]
    return sum(len(xs) * len(ys) for xs, ys in boxes) * SHAPE[2]


def _kernel_points(plan, T_depth):
    """Points the Pallas passes of one shard's tile compute: every tile,
    step and x-slab of `stencil_tb.step_slabs`, b planes of wy rows."""
    n = 0
    for geom in H._geoms(plan, T_depth):
        spec = ops.pass_inner_spec(geom, SHAPE[2], ORDER, plan.dt,
                                   plan.spacing, 1, 1, jnp.float32,
                                   plan.physics)
        _, wy, nz = spec.window
        for _ti in range(geom.ntiles[0]):
            for _tj in range(geom.ntiles[1]):
                for k in range(geom.T):
                    _, _, b, nslab = ker.step_slabs(spec, k)
                    for _s in range(nslab):
                        n += b * wy * nz
    return n


def counts():
    mesh = mesh_lib.make_xy_mesh()
    px, py = mesh.shape["data"], mesh.shape["model"]
    grid, dt, m, damp, g, gr = case()
    out = []
    seen = []

    def counting(state, params, spec, mask_fn):
        new = phys.ACOUSTIC.update(state, params, spec, mask_fn)
        seen.append(int(np.prod(new["u"].shape)))
        return new

    counted = dataclasses.replace(phys.ACOUSTIC, update=counting)
    # jnp executor: every update call counted as it is traced; nested
    # passes (inner T 2 of outer 4, then 1) after the split first step
    plans = [the_1024_class(mesh, dt),
             the_1024_class(mesh, dt, counted, inner="jnp")._replace(
                 inner_plan=TBPlan((4, 8), 2, 2))]
    for plan in plans:
        nt = plan.T + 3          # one main tile (the scan body traces
        #                          once) and a remainder tile
        state = (jnp.zeros(SHAPE, jnp.float32),) * 2
        params = {"m": m, "damp": damp}
        seen.clear()
        with mesh:
            jaxpr = jax.make_jaxpr(
                lambda s, p: H.sharded_tb_propagate(plan, nt, s, p, g, gr)
            )(state, params)
        nperm, nbytes = _ppermutes(jaxpr.jaxpr, mesh, [0, 0])
        c = H.sharded_counts(plan, nt)
        if plan.inner == "jnp":
            brute = sum(seen) * px * py
        else:
            brute = sum((_split_points(plan, d * plan.r_step)
                         + _kernel_points(plan, d)) * px * py
                        for d in (plan.T, nt % plan.T))
        out.append({"inner": plan.inner, "ppermutes": nperm,
                    "bytes": nbytes, "update_points": brute,
                    "useful_points": int(np.prod(SHAPE)) * nt,
                    "counters": c})
    print(json.dumps(out))


if __name__ == "__main__":
    {"parity": parity, "counts": counts}[sys.argv[1]]()
