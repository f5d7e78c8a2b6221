"""Quickstart: the paper's scheme in ~40 lines.

Off-the-grid sources -> grid-aligned precompute (SM/SID/src_dcmp) ->
temporally-blocked propagation via the Pallas kernel, checked against the
naive Listing-1 reference.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import boundary, sources as S
from repro.core.grid import Grid
from repro.core.temporal_blocking import TBPlan
from repro.kernels import ops, ref

# -- 1. problem setup: two-layer velocity model, one off-the-grid source ----
grid = Grid(shape=(48, 48, 32), spacing=(10.0, 10.0, 10.0))
vp = np.full(grid.shape, 1500.0)
vp[:, :, 16:] = 2500.0
m = jnp.asarray(1.0 / vp ** 2, jnp.float32)          # squared slowness
damp = boundary.damping_field(grid.shape, nbl=6, spacing=grid.spacing)
dt = grid.cfl_dt(2500.0, order=4)
nt = 24

# source at a coordinate that is NOT a grid point (the paper's subject)
src = S.SparseOperator(np.array([[237.3, 214.9, 61.7]]))
wavelet = S.ricker_wavelet(nt, dt, f0=12.0)

# -- 2. the paper's precompute: align the source to the grid ----------------
g = S.precompute(src, grid, wavelet)           # affected points, src_dcmp
print(f"source decomposed onto {g.npts} grid points "
      f"(trilinear, paper Fig. 5)")

# receivers (off-the-grid measurement interpolation)
rec = S.SparseOperator(np.array([[100.0, 214.9, 61.7],
                                 [350.0, 214.9, 61.7]]))
gr = S.precompute_receivers(rec, grid)

# -- 3. temporally-blocked propagation (Pallas TPU kernel, interpret on CPU)
u0 = jnp.zeros(grid.shape, jnp.float32)
plan = TBPlan(tile=(16, 16), T=4, radius=2)          # 4 steps per VMEM trip
(u_prev, u), recs = ops.acoustic_tb_propagate(
    nt, u0, u0, m, damp, g, gr, plan, order=4, dt=dt, spacing=grid.spacing)

# -- 4. validate against the naive Listing-1 reference ----------------------
(_, u_ref), recs_ref = ref.acoustic_reference(
    nt, u0, u0, m, damp, dt, grid.spacing, 4, g=g, receivers=gr)
err = float(jnp.max(jnp.abs(u - u_ref)))
print(f"TB(T=4) vs reference: max|err| = {err:.2e} "
      f"(field scale {float(jnp.max(jnp.abs(u_ref))):.2e})")
print(f"receiver traces shape: {recs.shape}; "
      f"match: {np.allclose(np.asarray(recs), np.asarray(recs_ref), atol=1e-5)}")
assert err < 1e-4
print("OK — temporal blocking with off-the-grid sources is exact.")
