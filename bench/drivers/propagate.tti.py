"""The propagate driver's call into the system under test for the
pseudo-acoustic TTI physics."""
from repro.core.propagators import tti
from repro.kernels import ops


def run(nt, zero, model, g, gr, plan, order, dt, spacing):
    """One propagate from rest; returns (state in the reference's STATE
    order, traces (nt, nrec))."""
    state = tti.TTIState(zero, zero, zero, zero)
    params = tti.TTIParams(**{k: model[k] for k in tti.TTIParams._fields})
    final, traces = ops.tti_tb_propagate(nt, state, params, g, gr, plan,
                                         order, dt, spacing)
    return tuple(final), traces
