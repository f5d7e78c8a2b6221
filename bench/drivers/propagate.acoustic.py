"""The propagate driver's call into the system under test for the
isotropic acoustic physics."""
from repro.kernels import ops


def run(nt, zero, model, g, gr, plan, order, dt, spacing):
    """One propagate from rest; returns (state in the reference's STATE
    order, traces (nt, nrec))."""
    state, traces = ops.acoustic_tb_propagate(
        nt, zero, zero, model["m"], model["damp"], g, gr, plan, order, dt,
        spacing)
    return tuple(state), traces
