"""Elastic and TTI temporally-blocked Pallas kernels vs their reference
propagators (interpret mode).

The paper's §III claim, enforced kernel-level: grid-aligning the sparse
off-the-grid sources makes temporal blocking legal for *all* propagators —
the same trapezoidal VMEM schedule that passes the acoustic parity suite
(test_kernel_stencil_tb.py) must reproduce the 9-field staggered elastic
and the coupled-field TTI references exactly, with sources and receivers
active, across multiple time tiles and through the remainder-tile path.
"""
import dataclasses

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import boundary, sources as S
from repro.core.grid import Grid
from repro.core.propagators import elastic as el
from repro.core.propagators import tti as tt
from repro.core.temporal_blocking import TBPlan
from repro.kernels import ops, ref
from repro.kernels import tb_physics as phys

ATOL = 1e-5
RTOL = 2e-4


def _geometry(shape, order, nt, nsrc=2, nrec=3, seed=0):
    grid = Grid(shape=shape, spacing=(10.0,) * 3)
    rng = np.random.RandomState(seed)
    vp = 2000.0 + 500.0 * rng.rand(*shape)
    damp = boundary.damping_field(shape, nbl=3,
                                  spacing=grid.spacing).astype(jnp.float32)
    dt = grid.cfl_dt(3000.0, order)
    ext = np.asarray(grid.extent)
    src = S.SparseOperator(5.0 + rng.rand(nsrc, 3) * (ext - 10.0))
    wav = S.ricker_wavelet(nt, dt, f0=12.0, num=nsrc) \
        + 0.1 * rng.randn(nt, nsrc)
    g = S.precompute(src, grid, wav)
    rec = S.SparseOperator(5.0 + rng.rand(nrec, 3) * (ext - 10.0))
    gr = S.precompute_receivers(rec, grid)
    return grid, rng, vp, damp, dt, g, gr


def _elastic_setup(shape=(12, 12, 8), order=4, nt=4, seed=0):
    grid, rng, vp, damp, dt, g, gr = _geometry(shape, order, nt, seed=seed)
    rho = 2000.0 + 100.0 * rng.rand(*shape)
    vs = vp / 1.9
    params = el.ElasticParams(
        lam=jnp.asarray(rho * (vp ** 2 - 2 * vs ** 2) * 1e-6, jnp.float32),
        mu=jnp.asarray(rho * vs ** 2 * 1e-6, jnp.float32),
        b=jnp.asarray(1.0 / rho, jnp.float32),
        damp=damp)
    state = el.ElasticState(
        *[jnp.asarray(0.01 * rng.randn(*shape), jnp.float32)
          for _ in range(9)])
    return grid, params, state, dt, g, gr


def _tti_setup(shape=(12, 12, 8), order=4, nt=4, seed=0):
    grid, rng, vp, damp, dt, g, gr = _geometry(shape, order, nt, seed=seed)
    params = tt.TTIParams(
        m=jnp.asarray(1.0 / vp ** 2, jnp.float32), damp=damp,
        epsilon=jnp.asarray(0.2 * rng.rand(*shape), jnp.float32),
        delta=jnp.asarray(0.1 * rng.rand(*shape), jnp.float32),
        theta=jnp.asarray(0.3 * rng.randn(*shape), jnp.float32),
        phi=jnp.asarray(0.3 * rng.randn(*shape), jnp.float32))
    state = tt.TTIState(
        *[jnp.asarray(0.01 * rng.randn(*shape), jnp.float32)
          for _ in range(4)])
    return grid, params, state, dt, g, gr


def _plan(physics, order, tile, T):
    return TBPlan(tile=tile, T=T, radius=physics.step_radius(order))


@pytest.mark.parametrize("T,tile,nt", [
    (2, (6, 6), 4),   # 2 time tiles (the acceptance minimum)
    (1, (6, 6), 2),   # spatially-blocked baseline path
    (2, (6, 6), 5),   # nt % T != 0 -> remainder tile
    # shrinking trapezoid: n_k = 22, 14, 6 (overlapping last slabs, then
    # a range under one slab), remainder tile of depth 1
    (3, (6, 6), 4),
])
def test_elastic_tb_matches_reference(T, tile, nt):
    order = 4
    grid, params, state, dt, g, gr = _elastic_setup(nt=nt)
    plan = _plan(phys.ELASTIC, order, tile, T)
    kst, krec = ops.elastic_tb_propagate(
        nt, state, params, g, gr, plan, order, dt, grid.spacing)
    rst, rrec = ref.elastic_reference(
        nt, state, params, dt, grid.spacing, order, g=g, receivers=gr)
    for f in el.ElasticState._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(kst, f)), np.asarray(getattr(rst, f)),
            rtol=RTOL, atol=ATOL, err_msg=f"elastic field {f}")
    assert krec.shape == (nt, 3, 2)  # (t, receiver, [vz, pressure proxy])
    np.testing.assert_allclose(np.asarray(krec), np.asarray(rrec),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("T,tile,nt", [
    (2, (6, 6), 4),   # 2 time tiles (the acceptance minimum)
    (2, (12, 6), 4),  # asymmetric tile
    (2, (6, 6), 5),   # nt % T != 0 -> remainder tile
    (3, (6, 6), 4),   # shrinking trapezoid, as in the elastic case
])
def test_tti_tb_matches_reference(T, tile, nt):
    order = 4
    grid, params, state, dt, g, gr = _tti_setup(nt=nt)
    plan = _plan(phys.TTI, order, tile, T)
    kst, krec = ops.tti_tb_propagate(
        nt, state, params, g, gr, plan, order, dt, grid.spacing)
    rst, rrec = ref.tti_reference(
        nt, state, params, dt, grid.spacing, order, g=g, receivers=gr)
    for f in tt.TTIState._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(kst, f)), np.asarray(getattr(rst, f)),
            rtol=RTOL, atol=ATOL, err_msg=f"tti field {f}")
    np.testing.assert_allclose(np.asarray(krec), np.asarray(rrec),
                               rtol=RTOL, atol=ATOL)


def test_acoustic_tb_remainder_tile():
    """nt % T != 0 remainder-tile path for the third physics (elastic and
    TTI cover it in the parametrized suites above): the final depth-(nt%T)
    tile rebuilds spec/tables with the shallower halo."""
    nt, T, order = 5, 2, 4
    shape = (12, 12, 8)
    grid, rng, vp, damp, dt, g, gr = _geometry(shape, order, nt)
    m = jnp.asarray(1.0 / vp ** 2, jnp.float32)
    u0 = jnp.asarray(0.01 * rng.randn(*shape), jnp.float32)
    u1 = jnp.asarray(0.01 * rng.randn(*shape), jnp.float32)
    plan = _plan(phys.ACOUSTIC, order, (6, 6), T)
    (k0, k1), krec = ops.acoustic_tb_propagate(
        nt, u0, u1, m, damp, g, gr, plan, order, dt, grid.spacing)
    (r0, r1), rrec = ref.acoustic_reference(
        nt, u0, u1, m, damp, dt, grid.spacing, order, g=g, receivers=gr)
    np.testing.assert_allclose(np.asarray(k1), np.asarray(r1),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(k0), np.asarray(r0),
                               rtol=RTOL, atol=ATOL)
    assert krec.shape == (nt, 3)
    np.testing.assert_allclose(np.asarray(krec), np.asarray(rrec),
                               rtol=RTOL, atol=ATOL)


def test_elastic_no_sources_no_receivers():
    nt, order = 4, 4
    grid, params, state, dt, _, _ = _elastic_setup(nt=nt)
    plan = _plan(phys.ELASTIC, order, (6, 6), 2)
    kst, krec = ops.elastic_tb_propagate(
        nt, state, params, None, None, plan, order, dt, grid.spacing)
    rst, _ = ref.elastic_reference(nt, state, params, dt, grid.spacing,
                                   order)
    assert krec is None
    np.testing.assert_allclose(np.asarray(kst.vz), np.asarray(rst.vz),
                               rtol=RTOL, atol=ATOL)


def test_step_radius_per_physics():
    """Elastic/TTI consume twice the acoustic halo per in-VMEM step: their
    updates chain two derivative passes (paper Fig. 8b dependence angle)."""
    for order in (2, 4, 8):
        assert phys.ACOUSTIC.step_radius(order) == order // 2
        assert phys.ELASTIC.step_radius(order) == order
        assert phys.TTI.step_radius(order) == order


def test_multiphysics_kernel_cost():
    from repro.kernels import stencil_tb as ker
    spec = ker.TBKernelSpec(nx=24, ny=24, nz=16, tile=(12, 12), T=2,
                            order=4, dt=1e-3, spacing=(10.0,) * 3,
                            src_cap=4, rec_cap=4,
                            step_radius=phys.ELASTIC.step_radius(4),
                            rec_channels=2)
    c = ker.kernel_cost(spec, phys.ELASTIC)
    # 13 windows read (9 of them held in pairs), 9 fields written back
    assert c["vmem_bytes"] == spec.vmem_bytes(22)
    assert c["flops"] > c["useful_flops"] > 0
    ca = ker.kernel_cost(spec, phys.ACOUSTIC)
    assert c["hbm_bytes"] > ca["hbm_bytes"]  # elastic moves more data


# ---------------------------------------------------------------------------
# Slot loops bounded by each tile's live count
# ---------------------------------------------------------------------------

SLOT_SHAPE = (24, 12, 8)      # 4 x 2 tiles of (6, 6)


def _slot_case(name, nt):
    """State and model of `name` on SLOT_SHAPE, with one source near the
    x = 0 face and receivers spread over the middle tiles: the tables
    hold tiles with no live entry, with some, and (sized by cap=None) a
    full one."""
    physics = phys.PHYSICS[name]
    if name == "acoustic":
        grid, rng, vp, damp, dt, _, _ = _geometry(SLOT_SHAPE, 4, nt)
        state = tuple(jnp.asarray(0.01 * rng.randn(*SLOT_SHAPE), jnp.float32)
                      for _ in range(2))
        params = {"m": jnp.asarray(1.0 / vp ** 2, jnp.float32),
                  "damp": damp}

        def reference(g, gr):
            return ref.acoustic_reference(nt, *state, params["m"], damp, dt,
                                          grid.spacing, 4, g=g,
                                          receivers=gr)[1]
    else:
        setup = _elastic_setup if name == "elastic" else _tti_setup
        grid, p, st, dt, _, _ = setup(shape=SLOT_SHAPE, nt=nt)
        state = tuple(getattr(st, f) for f in physics.state_fields)
        params = {f: getattr(p, f) for f in physics.param_fields}
        oracle = (ref.elastic_reference if name == "elastic"
                  else ref.tti_reference)

        def reference(g, gr):
            return oracle(nt, st, p, dt, grid.spacing, 4, g=g,
                          receivers=gr)[1]
    src = S.SparseOperator([[14.0, 33.0, 35.0]])
    rec = S.SparseOperator([[72.0, 27.0, 41.0], [101.0, 36.0, 33.0],
                            [113.0, 64.0, 42.0], [84.0, 58.0, 22.0]])
    g = S.precompute(src, grid, 1e2 * S.ricker_wavelet(nt, dt, f0=12.0))
    gr = S.precompute_receivers(rec, grid)
    return physics, grid, dt, state, params, g, gr, reference


def _run_prepared(physics, nt, state, params, g, gr, plan, dt, spacing,
                  pad=1, silent=False):
    """The jitted one-chip propagate on `_prepare`'s inputs, with every
    table's caps raised `pad`-fold over its fullest tile, or (`silent`)
    every tile's live count set to 0.  Returns (outputs, tables)."""
    static, (st, prm, dcmp, tables) = ops._prepare(
        physics, nt, state, params, g, gr, plan, 4, dt, spacing)
    _, _, spec, rspec, nrec = static
    if pad > 1:
        specs, tables = [], ()
        for s in (spec, rspec):
            if s is not None:
                s = dataclasses.replace(s, src_cap=pad * s.src_cap,
                                        rec_cap=pad * s.rec_cap)
                tables += ops.build_tables(s, g, gr, params, physics,
                                           src_cap=s.src_cap,
                                           rec_cap=s.rec_cap)
            else:
                tables += (None, None)
            specs.append(s)
        spec, rspec = specs
    if silent:
        tables = tuple(None if t is None else t._replace(
            nnz=np.zeros_like(t.nnz)) for t in tables)
    out = ops._tb_propagate_jit(physics, nt, spec, rspec, nrec, None, st,
                                prm, dcmp, tables)
    return out, tables


@pytest.mark.parametrize("name", ["acoustic", "tti", "elastic"])
def test_padded_caps_are_bit_identical(name):
    """Tables padded to 4x their fullest tile give the bits of the tables
    sized to it (cap=None), wavefields and traces, main and remainder
    tiles: the slot loops run each tile's live entries and nothing
    else, over tiles with none, some, and a full row of them; the
    traces are the reference's."""
    nt = 5
    physics, grid, dt, state, params, g, gr, reference = _slot_case(name,
                                                                    nt)
    plan = _plan(physics, 4, (6, 6), 2)
    want, tight = _run_prepared(physics, nt, state, params, g, gr, plan,
                                dt, grid.spacing)
    got, padded = _run_prepared(physics, nt, state, params, g, gr, plan,
                                dt, grid.spacing, pad=4)
    fills = set()
    for t, p in zip(tight, padded):
        cap = t.coords.shape[1]
        assert p.coords.shape[1] == 4 * cap
        np.testing.assert_array_equal(t.nnz, p.nnz)
        fills |= {"none" if n == 0 else "full" if n == cap else "some"
                  for n in t.nnz}
    assert fills == {"none", "some", "full"}
    assert float(np.max(np.abs(np.asarray(want[1])))) > 0.0
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    recs = np.asarray(want[1])
    if physics.rec_channels == 1:
        recs = recs[..., 0]
    np.testing.assert_allclose(recs, np.asarray(reference(g, gr)),
                               rtol=RTOL, atol=ATOL)


def test_kernel_reads_no_slot_past_the_count():
    """Live tables whose counts read 0 inject and record nothing: the
    wavefields are those of a propagate with no sources or receivers,
    and the traces are zero."""
    nt = 5
    physics, grid, dt, state, params, g, gr, _ = _slot_case("acoustic", nt)
    plan = _plan(physics, 4, (6, 6), 2)
    (got, recs), tables = _run_prepared(physics, nt, state, params, g, gr,
                                        plan, dt, grid.spacing, silent=True)
    assert all(np.sum(t.nnz) == 0 and t.coords.shape[1] > 1
               for t in tables)
    (want, _), _ = _run_prepared(physics, nt, state, params, None, None,
                                 plan, dt, grid.spacing)
    assert recs.shape == (nt, 4, 1) and not np.any(np.asarray(recs))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
