"""The sharded driver's calls into the system under test for the
isotropic acoustic physics."""
from repro.distributed import halo
from repro.kernels import tb_physics


def plan(mesh, shape, order, dt, spacing, **planner):
    """The plan the program's joint autotuner picks for one shard."""
    return halo.sharded_plan(mesh, tb_physics.ACOUSTIC, tuple(shape), order,
                             dt, spacing, **planner)


def run(nt, state, model, g, gr, plan):
    """One propagate from `state` (donated); returns (state in the
    reference's STATE order, traces (nt, nrec))."""
    state, traces = halo.sharded_propagate(
        plan, nt, state, {"m": model["m"], "damp": model["damp"]}, g, gr)
    return tuple(state), traces[..., 0]
