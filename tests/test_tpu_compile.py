"""The TB kernel compiles natively (Mosaic, no interpret mode) for a TPU v5e
that is described, not attached, at the paper's 512^3 widths.

Each case lowers one `tb_time_tile` call with the plan the autotuner picks
for 512^3 and compiles it for one v5e chip — what the chip's compiler would
refuse (unaligned blocks, scalar stores to VMEM, SMEM or scoped-VMEM
overflow) fails here, without a chip.  The scoped-VMEM limit is the
planner's own price of the plan (`TBPlan.kernel_vmem_bytes`), so a pass
also shows that the price the autotuner planned with bounds what Mosaic
allocates.  The topology is described inside a fixture, so collection is
identical in every test worker.
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
            arg(lead + (ntiles, REC_CAP)))

    def tile(state, params, sc, sv, rc, rw):
        def one(state, sc, sv, rc, rw):
            return ker.tb_time_tile(spec, physics, state, params, sc, sv,
                                    rc, rw, interpret=False)
        if batch is None:
            return one(state, sc, sv, rc, rw)
        # the survey engine's Pallas executor maps over the shots of a
        # batch, with the model shared
        return jax.lax.map(lambda a: one(*a), (state, sc, sv, rc, rw))

    compiled = jax.jit(tile).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        (batch or 1) * len(physics.state_fields) * N ** 3 * 4
