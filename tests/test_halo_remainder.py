"""Remainder-tile exchange costs (ISSUE 5 satellite / ROADMAP open item).

The `nt % T` remainder tile is strictly shallower than the main tiles, so
it reads the params in the frames the main tiles' exchange filled (the
domain mask is an iota predicate, never an array) — it must run ZERO
param ppermute rounds, and the overlapped (split-first-step) schedule
must cover the remainder exactly like full tiles.  Runs in-process on a
1x1 mesh (the ppermute algebra is identical; no device forcing needed).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.distributed.halo as H
from repro.core import boundary, sources as S
from repro.core.grid import Grid
from repro.kernels import ref
from repro.kernels import tb_physics as phys
from repro.launch import mesh as mesh_lib


@pytest.fixture
def acoustic_case():
    shape = (16, 16, 8)
    grid = Grid(shape=shape, spacing=(10.0,) * 3)
    order = 4
    dt = grid.cfl_dt(3000.0, order)
    rng = np.random.RandomState(0)
    vp = 1500.0 + 1000.0 * rng.rand(*shape)
    m = jnp.asarray(1.0 / vp ** 2, jnp.float32)
    damp = boundary.damping_field(shape, nbl=3, spacing=grid.spacing)
    ext = np.asarray(grid.extent)
    src = S.SparseOperator(5.0 + rng.rand(2, 3) * (ext - 10.0))
    nt = 5  # nt % T == 1: the remainder tile runs
    g = S.precompute(src, grid, S.ricker_wavelet(nt, dt, f0=12.0, num=2))
    rec = S.SparseOperator(5.0 + rng.rand(3, 3) * (ext - 10.0))
    gr = S.precompute_receivers(rec, grid)
    mesh = mesh_lib.make_xy_mesh()
    plan = H.DistTBPlan(mesh=mesh, grid_shape=shape, physics=phys.ACOUSTIC,
                        order=order, T=2, dt=dt, spacing=grid.spacing)
    state = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
    params = {"m": m, "damp": damp}
    return plan, nt, state, params, g, gr, (m, damp, dt, grid, order)


def test_remainder_setup_runs_no_param_exchange(acoustic_case, monkeypatch):
    """The params are exchanged into their frames once per propagate, at
    the main tiles' depth; the remainder tile reads the same frames
    deeper in, with no second param ppermute round."""
    plan, nt, state, params, g, gr, _ = acoustic_case
    assert nt % plan.T, "the case must run a remainder tile"
    calls = []
    orig = H._param_frames
    monkeypatch.setattr(
        H, "_param_frames",
        lambda *a, **k: calls.append(1) or orig(*a, **k))
    depths = []
    orig_x = H.exchange_to_depth
    monkeypatch.setattr(
        H, "exchange_to_depth",
        lambda x, depth, h, *a, **k: depths.append(depth) or
        orig_x(x, depth, h, *a, **k))
    with plan.mesh:     # tracing the propagate is enough to count
        jax.make_jaxpr(lambda s, p: H.sharded_tb_propagate(
            plan, nt, s, p, g=g, receivers=gr))(state, params)
    assert calls == [1], "params were exchanged more than once"
    # one exchange per param at the main depth, then the state fields'
    # per-field depths for the main tile and for the remainder tile
    npar = len(phys.ACOUSTIC.param_fields)
    assert depths[:npar] == [plan.halo] * npar
    assert depths[npar:] == (list(plan.field_depths(plan.T))
                             + list(plan.field_depths(nt % plan.T)))


def test_remainder_reuse_parity(acoustic_case):
    """The cropped-pad remainder must be bit-compatible with the reference
    (wavefields AND per-step traces), overlap on and off."""
    plan, nt, state, params, g, gr, (m, damp, dt, grid, order) = \
        acoustic_case
    (r0, r1), rrec = ref.acoustic_reference(
        nt, state[0], state[1], m, damp, dt, grid.spacing, order,
        g=g, receivers=gr)
    for overlap in (False, True):
        p = plan._replace(overlap=overlap)
        with p.mesh:
            (d0, d1), drec = H.sharded_tb_propagate(p, nt, state, params,
                                                    g=g, receivers=gr)
        for name, dv, rv in (("u_prev", d0, r0), ("u", d1, r1)):
            scale = float(jnp.max(jnp.abs(rv))) + 1e-30
            err = float(jnp.max(jnp.abs(dv - rv)))
            assert err <= 5e-4 * scale + 1e-6, (overlap, name, err)
        err = float(np.max(np.abs(np.asarray(drec)[..., 0]
                                  - np.asarray(rrec))))
        scale = float(np.max(np.abs(np.asarray(rrec)))) + 1e-30
        assert err <= 5e-4 * scale + 1e-6, (overlap, "rec", err)


def test_remainder_tile_is_overlapped_too(acoustic_case, monkeypatch):
    """`_split_first_step` must be traced for BOTH the main depth and the
    remainder depth when the plan overlaps its exchange (the ROADMAP
    claim that the remainder serializes is retired by this + the
    zero-exchange test above)."""
    plan, nt, state, params, g, gr, _ = acoustic_case
    seen = []
    orig = H._split_first_step
    monkeypatch.setattr(
        H, "_split_first_step",
        lambda p, sspec, h, *a, **k: seen.append(h) or
        orig(p, sspec, h, *a, **k))
    p = plan._replace(overlap=True)
    with p.mesh:
        H.sharded_tb_propagate(p, nt, state, params, g=g, receivers=gr)
    r = plan.r_step
    assert sorted(seen) == sorted([plan.T * r, (nt % plan.T) * r])
