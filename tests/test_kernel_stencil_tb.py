"""Pallas TB kernel vs pure-jnp oracle (interpret mode).

The paper's central correctness claim, enforced kernel-level: the
temporally-blocked schedule with fused grid-aligned injection reproduces the
naive Listing-1 computation exactly, for any tile shape and time depth.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_stub import given, hst, settings

from repro.core import boundary, sources as S
from repro.core.grid import Grid
from repro.core.temporal_blocking import TBPlan
from repro.kernels import ops, ref


def _setup(shape=(16, 16, 12), order=4, nt=8, nsrc=2, nrec=3, seed=0,
           spacing=10.0, dtype=jnp.float32):
    grid = Grid(shape=shape, spacing=(spacing,) * 3)
    rng = np.random.RandomState(seed)
    vp = 1500.0 + 1000.0 * rng.rand(*shape)
    m = jnp.asarray(1.0 / vp ** 2, dtype)
    damp = boundary.damping_field(shape, nbl=3, spacing=grid.spacing).astype(dtype)
    dt = grid.cfl_dt(2500.0, order)
    ext = np.asarray(grid.extent)
    src = S.SparseOperator(5.0 + rng.rand(nsrc, 3) * (ext - 10.0))
    wav = S.ricker_wavelet(nt, dt, f0=12.0, num=nsrc) \
        + 0.1 * rng.randn(nt, nsrc)
    g = S.precompute(src, grid, wav)
    rec = S.SparseOperator(5.0 + rng.rand(nrec, 3) * (ext - 10.0))
    gr = S.precompute_receivers(rec, grid)
    u0 = jnp.asarray(0.01 * rng.randn(*shape), dtype)
    u1 = jnp.asarray(0.01 * rng.randn(*shape), dtype)
    return grid, m, damp, dt, g, gr, u0, u1


@pytest.mark.parametrize("T,tile", [
    (1, (8, 8)),     # spatially-blocked baseline
    (2, (8, 8)),
    (4, (8, 8)),
    (2, (4, 8)),     # asymmetric tiles
    (4, (16, 16)),   # single tile in x/y
    (3, (8, 8)),     # nt % T != 0 -> remainder tile
    # the shrinking trapezoid (`stencil_tb.step_slabs`): steps whose range
    # is under one slab (n_k = 12, 8, 4; remainder 8, 4), and last slabs
    # that overlap the one before (n_k = 20, 16, 12, 4)
    (3, (4, 8)),
    (5, (4, 8)),
])
def test_tb_kernel_matches_reference(T, tile):
    nt, order = 8, 4
    grid, m, damp, dt, g, gr, u0, u1 = _setup(order=order, nt=nt)
    plan = TBPlan(tile=tile, T=T, radius=order // 2)
    (ku0, ku1), krec = ops.acoustic_tb_propagate(
        nt, u0, u1, m, damp, g, gr, plan, order, dt, grid.spacing)
    (ru0, ru1), rrec = ref.acoustic_reference(
        nt, u0, u1, m, damp, dt, grid.spacing, order, g=g, receivers=gr)
    np.testing.assert_allclose(np.asarray(ku1), np.asarray(ru1),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ku0), np.asarray(ru0),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(krec), np.asarray(rrec),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("order", [2, 4, 8])
def test_space_order_sweep(order):
    nt = 6
    grid, m, damp, dt, g, gr, u0, u1 = _setup(shape=(16, 16, 10), order=order,
                                              nt=nt)
    plan = TBPlan(tile=(8, 8), T=2, radius=order // 2)
    (ku0, ku1), krec = ops.acoustic_tb_propagate(
        nt, u0, u1, m, damp, g, gr, plan, order, dt, grid.spacing)
    (ru0, ru1), rrec = ref.acoustic_reference(
        nt, u0, u1, m, damp, dt, grid.spacing, order, g=g, receivers=gr)
    np.testing.assert_allclose(np.asarray(ku1), np.asarray(ru1),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(krec), np.asarray(rrec),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("shape", [(8, 8, 8), (16, 8, 12), (24, 16, 10)])
def test_shape_sweep(shape):
    nt = 4
    grid, m, damp, dt, g, gr, u0, u1 = _setup(shape=shape, nt=nt)
    plan = TBPlan(tile=(8, 8), T=2, radius=2)
    (ku0, ku1), _ = ops.acoustic_tb_propagate(
        nt, u0, u1, m, damp, g, gr, plan, 4, dt, grid.spacing)
    (ru0, ru1), _ = ref.acoustic_reference(
        nt, u0, u1, m, damp, dt, grid.spacing, 4, g=g, receivers=gr)
    np.testing.assert_allclose(np.asarray(ku1), np.asarray(ru1),
                               rtol=2e-4, atol=1e-6)


def test_no_sources_no_receivers():
    nt = 4
    grid, m, damp, dt, _, _, u0, u1 = _setup(nt=nt)
    plan = TBPlan(tile=(8, 8), T=2, radius=2)
    (ku0, ku1), krec = ops.acoustic_tb_propagate(
        nt, u0, u1, m, damp, None, None, plan, 4, dt, grid.spacing)
    (ru0, ru1), _ = ref.acoustic_reference(
        nt, u0, u1, m, damp, dt, grid.spacing, 4)
    assert krec is None
    np.testing.assert_allclose(np.asarray(ku1), np.asarray(ru1),
                               rtol=2e-4, atol=1e-6)


def test_bf16_runs_and_tracks_f32():
    """bf16 variant stays finite and loosely tracks the f32 field."""
    nt = 4
    grid, m, damp, dt, g, gr, u0, u1 = _setup(nt=nt)
    plan = TBPlan(tile=(8, 8), T=2, radius=2)
    (f0, f1), _ = ops.acoustic_tb_propagate(
        nt, u0, u1, m, damp, g, gr, plan, 4, dt, grid.spacing)
    (b0, b1), _ = ops.acoustic_tb_propagate(
        nt, u0.astype(jnp.bfloat16), u1.astype(jnp.bfloat16),
        m.astype(jnp.bfloat16), damp.astype(jnp.bfloat16), g, gr, plan, 4,
        dt, grid.spacing)
    b = np.asarray(b1.astype(jnp.float32))
    f = np.asarray(f1)
    assert np.all(np.isfinite(b))
    # loose: bf16 has ~3 decimal digits
    assert np.abs(b - f).max() <= 0.1 * max(np.abs(f).max(), 1e-3) + 1e-2


def test_sb_baseline_is_t1():
    nt = 4
    grid, m, damp, dt, g, gr, u0, u1 = _setup(nt=nt)
    (s0, s1), srec = ops.acoustic_sb_propagate(
        nt, u0, u1, m, damp, g, gr, (8, 8), 4, dt, grid.spacing)
    plan = TBPlan(tile=(8, 8), T=1, radius=2)
    (t0, t1), trec = ops.acoustic_tb_propagate(
        nt, u0, u1, m, damp, g, gr, plan, 4, dt, grid.spacing)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(t1))
    np.testing.assert_array_equal(np.asarray(srec), np.asarray(trec))


def test_kernel_cost_model_sane():
    from repro.kernels import stencil_tb as ker
    spec = ker.TBKernelSpec(nx=64, ny=64, nz=64, tile=(32, 32), T=4,
                            order=4, dt=1e-3, spacing=(10.0,) * 3,
                            src_cap=8, rec_cap=8)
    c = ker.kernel_cost(spec)
    assert c["flops"] > c["useful_flops"] > 0
    # a pair of windows per state field (u_prev, u) plus m and damp
    assert c["vmem_bytes"] == spec.vmem_bytes(6)
    # temporal blocking must reduce HBM traffic vs 5-field naive traffic
    naive = 64 * 64 * 64 * 4 * 5 * spec.T
    assert c["hbm_bytes"] < naive


@pytest.mark.parametrize("physics,tile,T,nt,redundancy", [
    # the plans `plan_for_physics` picks at 512^3 for SO-4, over the
    # benchmark's record lengths: acoustic (32, 32) T 8 with a T 7
    # remainder tile, TTI (16, 32) T 2
    ("acoustic", (32, 32), 8, 8, 3.0),
    ("acoustic", (32, 32), 8, 399, (392 * 3.0 + 7 * 40 * 8 * 64 / 7168)
     / 399),
    ("tti", (16, 32), 2, 200, 1.875),
])
def test_kernel_cost_counts_the_update_schedule(physics, tile, T, nt,
                                                redundancy):
    """`kernel_cost`'s stencil points are the `update_points` the span
    counter reports, both from the one schedule (`step_slabs`); their
    ratio to the useful points is the trapezoid's (arithmetic only)."""
    from repro.kernels import stencil_tb as ker
    from repro.kernels import tb_physics as phys
    physics = phys.PHYSICS[physics]
    plan = TBPlan(tile=tile, T=T, radius=physics.step_radius(4))
    spec = ops.make_spec((512, 512, 512), plan, 4, 1e-3, (10.0,) * 3, 8, 64,
                         physics=physics)
    one = ops.update_counts(spec, T)
    assert ker.kernel_cost(spec, physics)["stencil_points"] == \
        one["update_points"][0]
    assert one["useful_points"] == [512 ** 3 * T]
    got = ops.update_counts(spec, nt)
    assert sum(got["update_points"]) / sum(got["useful_points"]) == \
        pytest.approx(redundancy)


@pytest.mark.parametrize("physics", ["acoustic", "tti"])
def test_kernel_call_timer_counts_the_schedule(physics, capsys):
    """`benchmarks/tb_kernel_call.py` times one kernel call and counts its
    kept planes from the same schedule as the `update_points` counter."""
    import importlib.util
    import os
    from repro.kernels import stencil_tb as ker
    from repro.kernels import tb_physics as phys
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "tb_kernel_call.py")
    spec_ = importlib.util.spec_from_file_location("tb_kernel_call", path)
    tool = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(tool)
    tool.main(["--physics", physics, "--n", "32", "--caps", "1,2",
               "--reps", "1", "--slots", "live"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    tile, T = tool.PLANS[physics]
    plan = TBPlan(tile=tile, T=T, radius=phys.PHYSICS[physics].step_radius(4))
    spec = ops.make_spec((32, 32, 32), plan, 4, 1e-3, (10.0,) * 3, 1, 2,
                         physics=phys.PHYSICS[physics])
    _, wy, wz = spec.window
    planes = ker.update_points(spec) // (wy * wz)
    assert "caps (1,2) live slots: call " in line
    assert f" {planes} kept planes" in line


@settings(max_examples=8, deadline=None)
@given(seed=hst.integers(0, 2 ** 16), T=hst.sampled_from([1, 2, 4]),
       nsrc=hst.integers(1, 3))
def test_property_kernel_equals_oracle(seed, T, nsrc):
    """Property: kernel == oracle for random models/sources/tiles."""
    nt = 4
    grid, m, damp, dt, g, gr, u0, u1 = _setup(shape=(16, 8, 8), nt=nt,
                                              nsrc=nsrc, seed=seed)
    plan = TBPlan(tile=(8, 8), T=T, radius=2)
    (ku0, ku1), krec = ops.acoustic_tb_propagate(
        nt, u0, u1, m, damp, g, gr, plan, 4, dt, grid.spacing)
    (ru0, ru1), rrec = ref.acoustic_reference(
        nt, u0, u1, m, damp, dt, grid.spacing, 4, g=g, receivers=gr)
    np.testing.assert_allclose(np.asarray(ku1), np.asarray(ru1),
                               rtol=5e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(krec), np.asarray(rrec),
                               rtol=5e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# The jitted entry: one trace per plan, nt and array shapes
# ---------------------------------------------------------------------------

@pytest.fixture
def spans_on():
    from repro.telemetry import spans as tsp
    c = tsp.enable(jax_profiler=False)
    yield c
    tsp.disable()


def _call_spans(c, run):
    """Run one propagate under the collector `c`; returns its result, the
    attributes of its `ops.dispatch` and of its `ops.tables`, and the names
    of every span it recorded."""
    c.clear()
    out = run()
    recs = c.records()
    (d,) = [r for r in recs if r.name == "ops.dispatch"]
    (t,) = [r for r in recs if r.name == "ops.tables"]
    return out, d.attrs, t.attrs, {r.name for r in recs}


@pytest.mark.parametrize("executor", ["pallas", "jnp"])
def test_warm_call_reuses_the_compiled_propagate(spans_on, executor):
    nt, order = 5, 4
    grid, m, damp, dt, g, gr, u0, u1 = _setup(order=order, nt=nt)
    plan = TBPlan(tile=(8, 8), T=2, radius=order // 2)
    ops._tb_propagate_jit.clear_cache()

    def run():
        return ops.acoustic_tb_propagate(nt, u0, u1, m, damp, g, gr, plan,
                                         order, dt, grid.spacing,
                                         executor=executor)

    cold, d0, _, names0 = _call_spans(spans_on, run)
    warm, d1, _, names1 = _call_spans(spans_on, run)
    assert d0["traced"] is True and d0["compiles"] >= 1
    assert "ops.tile_pass" in names0
    assert d1 == {"traced": False, "compiles": 0}
    assert "ops.tile_pass" not in names1
    for a, b in zip(jax.tree.leaves(cold), jax.tree.leaves(warm)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("executor", ["pallas", "jnp"])
@pytest.mark.parametrize("change", ["nt", "sources"])
def test_new_nt_or_caps_trace_again(spans_on, executor, change):
    order = 4
    plan = TBPlan(tile=(8, 8), T=2, radius=order // 2)
    ops._tb_propagate_jit.clear_cache()

    def run(nt, nsrc):
        grid, m, damp, dt, g, gr, u0, u1 = _setup(order=order, nt=6,
                                                  nsrc=nsrc, nrec=3)
        got = ops.acoustic_tb_propagate(nt, u0, u1, m, damp, g, gr, plan,
                                        order, dt, grid.spacing,
                                        executor=executor)
        want = ref.acoustic_reference(nt, u0, u1, m, damp, dt,
                                      grid.spacing, order, g=g,
                                      receivers=gr)
        return got, want

    _, _, t0, _ = _call_spans(spans_on, lambda: run(4, 1))
    nt, nsrc = (6, 1) if change == "nt" else (4, 3)
    ((got, want), d, t, names) = _call_spans(spans_on, lambda: run(nt, nsrc))
    assert d["traced"] is True and "ops.tile_pass" in names
    if change == "sources":
        # the caps the tables are sized to grew with the sources
        assert t["src_slots"][0] > t0["src_slots"][0]
    (ku0, ku1), krec = got
    (ru0, ru1), rrec = want
    assert krec.shape == (nt, 3) and ku1.shape == (16, 16, 12)
    for k, r in ((ku0, ru0), (ku1, ru1), (krec, rrec)):
        np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                                   rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("executor", ["pallas", "jnp"])
@pytest.mark.parametrize("physics,nt,T", [
    ("acoustic", 7, 3),     # two depth-3 tiles and a depth-1 remainder
    ("tti", 4, 2),
])
def test_jitted_entry_matches_eager_core(physics, nt, T, executor):
    """`_tb_propagate_jit` on the prepared inputs gives what the traced
    core, `tb_propagate_prepared`, gives called eagerly on them."""
    from repro.kernels import tb_physics as phys
    from test_kernel_multiphysics import _tti_setup

    physics = phys.PHYSICS[physics]
    order = 4
    if physics.name == "acoustic":
        grid, m, damp, dt, g, gr, u0, u1 = _setup(order=order, nt=nt)
        state, params = (u0, u1), {"m": m, "damp": damp}
    else:
        grid, p, st, dt, g, gr = _tti_setup(shape=(12, 12, 8), nt=nt)
        state = tuple(getattr(st, f) for f in physics.state_fields)
        params = {f: getattr(p, f) for f in physics.param_fields}
    plan = TBPlan(tile=(8, 8) if physics.name == "acoustic" else (6, 6),
                  T=T, radius=physics.step_radius(order))
    static, args = ops._prepare(physics, nt, state, params, g, gr, plan,
                                order, dt, grid.spacing)
    _, _, spec, rspec, nrec = static
    assert (rspec is not None) == (nt % T > 0)
    st_, params_, src_dcmp, tables = args
    pads = tuple(ops._pad_xy(q, spec.halo, "edge") for q in params_)
    rpads = (tuple(ops._pad_xy(q, rspec.halo, "edge") for q in params_)
             if rspec is not None else None)
    eager = ops.tb_propagate_prepared(physics, nt, spec, rspec, st_, pads,
                                      rpads, src_dcmp, *tables, nrec,
                                      executor=executor)
    jitted = ops._tb_propagate_jit(*static, None, executor, *args)
    assert jitted[1].shape == (nt, nrec, physics.rec_channels)
    for a, b in zip(jax.tree.leaves(jitted), jax.tree.leaves(eager)):
        a, b = np.asarray(a), np.asarray(b)
        if executor == "pallas":
            np.testing.assert_array_equal(a, b)
        else:
            # XLA:CPU contracts a*b+c across what the eager call ran as
            # separate programs: a few ulps of the field's scale
            np.testing.assert_allclose(
                a, b, rtol=0, atol=4 * np.finfo(np.float32).eps
                * np.abs(b).max())
