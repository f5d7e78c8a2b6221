"""The TB kernel compiles natively (Mosaic, no interpret mode) for a TPU v5e
that is described, not attached, at the paper's 512^3 widths.

Each case lowers one `tb_time_tile` call with the plan the autotuner picks
for 512^3 and compiles it for one v5e chip — what the chip's compiler would
refuse (unaligned blocks, scalar stores to VMEM, SMEM or scoped-VMEM
overflow) fails here, without a chip.  The scoped-VMEM limit is the
planner's own price of the plan (`TBPlan.kernel_vmem_bytes`), so a pass
also shows that the price the autotuner planned with bounds what Mosaic
allocates.  Two further checks compile whole entry programs and hold
their footprint under a chip's memory: the one-chip jitted propagate at
512^3 and the sharded one at 1024^3 on a v5e:2x2.  The topology is
described inside a fixture, so collection is identical in every test
worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.temporal_blocking import PHYSICS_COSTS, plan_for_physics
from repro.kernels import ops
from repro.kernels import stencil_tb as ker
from repro.kernels import tb_physics as phys

N = 512
SRC_CAP, REC_CAP = 8, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("physics,order,remainder_T,batch", [
    ("acoustic", 4, None, None),
    ("acoustic", 8, None, None),
    ("acoustic", 12, None, None),
    ("tti", 4, None, None),
    ("tti", 8, None, None),
    ("tti", 12, None, None),
    ("elastic", 4, None, None),
    ("elastic", 8, None, None),
    ("acoustic", 4, 7, None),   # the nt % T tile of the paper's nt = 399
    ("acoustic", 4, None, 2),   # a loop over shots, as the survey runs it
])
def test_tb_time_tile_compiles_for_v5e(one_chip, monkeypatch, physics,
                                       order, remainder_T, batch):
    physics = phys.PHYSICS[physics]
    plan, _ = plan_for_physics(physics.name, N, order)
    if remainder_T is not None:
        plan = plan.__class__(plan.tile, remainder_T, plan.radius)
    pc = PHYSICS_COSTS[physics.name]
    monkeypatch.setattr(ker, "VMEM_BUDGET", plan.kernel_vmem_bytes(
        N, pc.fields, pc.write_fields, pc.read_fields))
    spec = ops.make_spec((N, N, N), plan, order, 1e-3, (10.0,) * 3,
                         SRC_CAP, REC_CAP, physics=physics)
    ntiles = spec.ntiles[0] * spec.ntiles[1]
    h = spec.halo

    lead = () if batch is None else (batch,)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pad = (N + 2 * h, N + 2 * h, N)
    args = ([arg(lead + pad)] * len(physics.state_fields),
            [arg(pad)] * len(physics.param_fields),
            arg(lead + (ntiles, SRC_CAP, 3), jnp.int32),
            arg(lead + (ntiles, plan.T, SRC_CAP)),
            arg(lead + (ntiles, REC_CAP, 3), jnp.int32),
            arg(lead + (ntiles, REC_CAP)),
            arg(lead + (ntiles, 2), jnp.int32))

    def tile(state, params, sc, sv, rc, rw, n):
        def one(state, sc, sv, rc, rw, n):
            return ker.tb_time_tile(spec, physics, state, params, sc, sv,
                                    rc, rw, n, interpret=False)
        if batch is None:
            return one(state, sc, sv, rc, rw, n)
        # the survey engine maps the kernel over the shots of a
        # batch, with the model shared
        return jax.lax.map(lambda a: one(*a), (state, sc, sv, rc, rw, n))

    compiled = jax.jit(tile).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        (batch or 1) * len(physics.state_fields) * N ** 3 * 4


# what XLA:TPU holds back of a v5e chip's HBM beside a program (the
# "reserved" line of its out-of-memory report)
XLA_RESERVED = 258 * 2 ** 20


def test_sharded_entry_fits_v5e_2x2_at_1024(topo):
    """The sharded entry's program for acoustic SO-4 at 1024^3 (nt 399,
    the plan the joint autotuner picks for a 512x512 block) compiles for
    a described v5e:2x2, one 512x512x1024 block a chip, and fits a chip:
    arguments + temps + XLA's reserved share at most 15.0 GB a device,
    the final state taking the donated initial state's memory."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import sources as S
    from repro.core.grid import Grid
    from repro.distributed import halo

    n, nt, order = 1024, 399, 4
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    grid = Grid(shape=(n,) * 3, spacing=(10.0,) * 3)
    dt = grid.cfl_dt(3500.0, order)
    plan = halo.sharded_plan(mesh, phys.ACOUSTIC, grid.shape, order, dt,
                             grid.spacing)
    assert plan.block == (512, 512) and nt % plan.T
    c = 5.0 * (n - 1)
    g = S.precompute(S.SparseOperator([[c + 3.3, c + 4.4, 155.0]]), grid,
                     S.ricker_wavelet(nt, dt, f0=10.0))
    rec = np.stack([c + 10.0 * np.linspace(-150, 150, 32),
                    np.full(32, c + 13.0), np.full(32, 105.0)], axis=1)
    gr = S.precompute_receivers(S.SparseOperator(rec), grid)
    field = jax.ShapeDtypeStruct(grid.shape, jnp.float32,
                                 sharding=NamedSharding(mesh, P("data",
                                                                "model",
                                                                None)))
    compiled = halo.sharded_lower(
        plan, nt, (field, field), {"m": field, "damp": field}, g, gr,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                  + XLA_RESERVED)
    assert per_device <= 15.0e9, per_device
    # the output holds the state (aliased to the donated input) and the
    # traces
    assert ma.alias_size_in_bytes == 2 * 512 * 512 * n * 4


@pytest.mark.parametrize("physics,spacing,nt", [
    ("acoustic", 10.0, 399),    # the T 8 scan and the T 7 remainder tile
    ("tti", 20.0, 200),
])
def test_one_chip_entry_fits_v5e_at_512(one_chip, physics, spacing, nt):
    """The one-chip entry's jitted program (`ops._tb_propagate_jit`: the
    param pads, the scan over depth-T tiles and the remainder tile) for SO-4 at 512^3
    and the benchmark's nt, at the plan `plan_for_physics` picks, compiles
    for a described v5e and fits a chip: arguments + output + temps +
    XLA's reserved share at most 15.0 GB (no argument is donated, so the
    final state takes memory of its own)."""
    import numpy as np

    from repro.core import sources as S
    from repro.core.grid import Grid

    physics = phys.PHYSICS[physics]
    order = 4
    grid = Grid(shape=(N,) * 3, spacing=(spacing,) * 3)
    dt = grid.cfl_dt(3500.0, order)
    plan, _ = plan_for_physics(physics.name, N, order)
    assert (nt % plan.T > 0) == (physics.name == "acoustic")
    c = spacing * (N - 1) / 2
    g = S.precompute(S.SparseOperator([[c + 3.3, c + 4.4, 155.0]]), grid,
                     S.ricker_wavelet(nt, dt, f0=10.0))
    rec = np.stack([c + 10.0 * np.linspace(-150, 150, 32),
                    np.full(32, c + 13.0), np.full(32, 105.0)], axis=1)
    gr = S.precompute_receivers(S.SparseOperator(rec), grid)
    field = jax.ShapeDtypeStruct(grid.shape, jnp.float32, sharding=one_chip)
    # the host binning reads the injection scale at the source's points:
    # constant models as zero-copy views, not 512^3 arrays
    params = {f: np.broadcast_to(np.float32(1.0 / 1500.0 ** 2), grid.shape)
              for f in physics.param_fields}
    static, args = ops._prepare(
        physics, nt, (field,) * len(physics.state_fields), params, g, gr,
        plan, order, dt, grid.spacing)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    compiled = ops._tb_propagate_jit.lower(*static, False, *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    per_chip = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes + XLA_RESERVED)
    assert per_chip <= 15.0e9, (ma.argument_size_in_bytes,
                                ma.output_size_in_bytes,
                                ma.temp_size_in_bytes)
    state_bytes = len(physics.state_fields) * N ** 3 * 4
    assert ma.alias_size_in_bytes == 0
    assert ma.output_size_in_bytes >= state_bytes
