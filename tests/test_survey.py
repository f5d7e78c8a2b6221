"""Survey engine: bucketing, batched parity, and the amortization contract.

The acceptance points of the survey subsystem (ISSUE 5):

  * shots bucket by padded (nsrc, nrec) with zero-amplitude padding that
    cannot change results (ragged buckets included);
  * a vmapped bucket of K shots matches K sequential `*_tb_propagate`
    calls for every physics and both executors;
  * a multi-bucket survey runs EXACTLY one autotune sweep and one jit
    trace per bucket — rerunning adds neither.
"""
import numpy as np
import pytest

from repro.core import sources as S
from repro.core.grid import Grid
from repro.core.temporal_blocking import TBPlan
from repro.kernels import tb_physics as phys
# the CLI's model builder and sequential oracle ARE the test fixtures —
# one copy, shared with benchmarks/fig13_survey.py
from repro.launch.stencil_survey import build_model, sequential_traces
from repro.survey import PlanCache, Shot, SurveyEngine, bucket_shots
from repro.survey.shots import pad_count

ORDER = 4
NT = 3  # not a multiple of T=2: every run exercises the remainder tile


def _case(physics_name, n=12, nz=8, seed=0):
    shape = (n, n, nz)
    grid = Grid(shape=shape, spacing=(10.0,) * 3)
    dt = grid.cfl_dt(3000.0, ORDER)
    params = build_model(physics_name, shape, grid,
                         np.random.RandomState(seed))
    return grid, dt, params


def _shot(grid, dt, nsrc, nrec, seed):
    """Receivers interleaved near the sources so traces carry signal."""
    rng = np.random.RandomState(seed)
    ext = np.asarray(grid.extent)
    src = 5.0 + rng.rand(nsrc, 3) * (ext - 10.0)
    rec = np.clip(src[rng.randint(nsrc, size=nrec)]
                  + 4.0 * rng.randn(nrec, 3), 2.0, ext - 2.0)
    return Shot(src_coords=src,
                wavelet=1e3 * S.ricker_wavelet(NT, dt, f0=12.0, num=nsrc),
                rec_coords=rec, shot_id=seed)


def _sequential(physics_name, shots, grid, params, plan, dt):
    return sequential_traces(physics_name, shots, grid, params, plan,
                             ORDER, dt, NT)


# ---------------------------------------------------------------------------
# Bucketing
# ---------------------------------------------------------------------------

def test_pad_count_powers_of_two():
    assert [pad_count(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]
    with pytest.raises(ValueError):
        pad_count(0)


def test_bucket_shots_bounds_shapes():
    grid, dt, _ = _case("acoustic")
    # nsrc 1..5, nrec 3 -> pad keys (1,4), (2,4), (4,4), (4,4), (8,4)
    shots = [_shot(grid, dt, nsrc, 3, seed=nsrc) for nsrc in range(1, 6)]
    buckets = bucket_shots(shots)
    assert set(buckets) == {(1, 4), (2, 4), (4, 4), (8, 4)}
    assert len(buckets[(4, 4)]) == 2          # nsrc 3 and 4 share a shape
    # every padded shot matches its bucket shape exactly
    for key, b in buckets.items():
        for s in b.shots:
            assert (s.nsrc, s.nrec) == key
    # indices reassemble the survey order
    all_idx = sorted(i for b in buckets.values() for i in b.indices)
    assert all_idx == list(range(len(shots)))


def test_shot_padding_is_silent():
    grid, dt, _ = _case("acoustic")
    s = _shot(grid, dt, 3, 3, seed=7)
    p = s.padded(4, 8)
    assert (p.nsrc, p.nrec) == (4, 8)
    # padding sources carry exactly zero amplitude
    assert np.all(p.wavelet[:, 3:] == 0.0)
    assert np.all(p.wavelet[:, :3] == s.wavelet)
    with pytest.raises(ValueError):
        s.padded(2, 8)  # cannot pad down


# ---------------------------------------------------------------------------
# Batched parity: vmapped bucket == K sequential *_tb_propagate calls
# ---------------------------------------------------------------------------

# One fast representative stays in tier-1; the rest of the physics x
# executor matrix is multi-minute jit work and runs in the CI slow job.
@pytest.mark.parametrize("physics_name,executor", [
    ("acoustic", "pallas"),
    pytest.param("acoustic", "jnp", marks=pytest.mark.slow),
    pytest.param("tti", "jnp", marks=pytest.mark.slow),
    pytest.param("tti", "pallas", marks=pytest.mark.slow),
    pytest.param("elastic", "jnp", marks=pytest.mark.slow),
    pytest.param("elastic", "pallas", marks=pytest.mark.slow),
])
def test_batched_parity(physics_name, executor):
    grid, dt, params = _case(physics_name, n=8)
    plan = TBPlan(tile=(8, 8), T=2,
                  radius=phys.PHYSICS[physics_name].step_radius(ORDER))
    # a ragged bucket: nsrc 3 pads to 4 (zero-amplitude source) next to an
    # exact-shape nsrc-4 shot — one vmapped batch of both
    shots = [_shot(grid, dt, 3, 3, seed=1), _shot(grid, dt, 4, 3, seed=2)]
    engine = SurveyEngine(physics_name, grid, params, NT, dt, order=ORDER,
                          executor=executor, plan=plan,
                          plan_cache=PlanCache(), bucket_cap=2)
    result = engine.run(shots)
    refs = _sequential(physics_name, shots, grid, params, plan, dt)
    for i, (got, ref) in enumerate(zip(result.traces, refs)):
        assert got.shape == ref.shape, (i, got.shape, ref.shape)
        scale = float(np.max(np.abs(ref))) + 1e-30
        err = float(np.max(np.abs(got - ref)))
        assert err <= 5e-4 * scale + 1e-6, (i, err, scale)


# ---------------------------------------------------------------------------
# The amortization contract (acceptance criterion)
# ---------------------------------------------------------------------------

def test_engine_one_sweep_one_trace_per_bucket():
    """>= 4 shots across >= 2 buckets: exactly one autotune sweep total
    and one jit trace per bucket, with batched traces matching sequential
    execution — including a rerun that must add neither sweeps nor
    traces."""
    grid, dt, params = _case("acoustic")
    shots = [_shot(grid, dt, 1, 3, seed=1), _shot(grid, dt, 1, 4, seed=2),
             _shot(grid, dt, 2, 3, seed=3), _shot(grid, dt, 2, 3, seed=4),
             _shot(grid, dt, 1, 3, seed=5)]
    cache = PlanCache()
    engine = SurveyEngine("acoustic", grid, params, NT, dt, order=ORDER,
                          executor="jnp", plan_cache=cache, bucket_cap=2)
    result = engine.run(shots)
    assert result.stats["buckets"] >= 2
    assert cache.sweeps == 1
    assert set(engine.trace_counts.values()) == {1}

    # a second engine over the same configuration: the sweep is cached
    engine2 = SurveyEngine("acoustic", grid, params, NT, dt, order=ORDER,
                           executor="jnp", plan_cache=cache, bucket_cap=2)
    assert cache.sweeps == 1 and engine2.cache_info.hit

    # rerunning the first engine re-traces nothing
    result2 = engine.run(shots)
    assert set(engine.trace_counts.values()) == {1}
    for a, b in zip(result.traces, result2.traces):
        np.testing.assert_array_equal(a, b)

    refs = _sequential("acoustic", shots, grid, params, engine.plan, dt)
    for got, ref in zip(result.traces, refs):
        scale = float(np.max(np.abs(ref))) + 1e-30
        assert float(np.max(np.abs(got - ref))) <= 5e-4 * scale + 1e-6


def test_run_stats_keys_and_cold_warm_split():
    """`SurveyEngine.run` stats schema is pinned (downstream benchmarks
    and the telemetry exporters key into it), and the cold/warm split is
    consistent: cold = plan + compile, warm = run - compile, throughput
    quoted from WARM time, and a rerun claims neither plan nor compile."""
    from repro.survey import RUN_STATS_KEYS

    grid, dt, params = _case("acoustic")
    shots = [_shot(grid, dt, 1, 3, seed=1), _shot(grid, dt, 2, 3, seed=2)]
    engine = SurveyEngine("acoustic", grid, params, NT, dt, order=ORDER,
                          executor="jnp", plan_cache=PlanCache(),
                          bucket_cap=2)
    s = engine.run(shots).stats
    assert set(s) == set(RUN_STATS_KEYS)
    assert s["plan_seconds"] > 0.0          # this engine ran the sweep
    assert s["compile_seconds"] > 0.0       # first run traced its buckets
    assert s["cold_seconds"] == pytest.approx(
        s["plan_seconds"] + s["compile_seconds"])
    assert s["warm_seconds"] == pytest.approx(
        max(s["seconds"] - s["compile_seconds"], 1e-12))
    assert s["shots_per_s"] == pytest.approx(len(shots) / s["warm_seconds"])
    assert set(s["metrics"]) == {"counters", "gauges", "histograms"}

    # steady state: no re-plan, no re-trace -> cold cost fully amortized
    s2 = engine.run(shots).stats
    assert set(s2) == set(RUN_STATS_KEYS)
    assert s2["plan_seconds"] == 0.0 and s2["compile_seconds"] == 0.0
    assert s2["warm_seconds"] == pytest.approx(s2["seconds"])
    assert s2["cold_seconds"] == 0.0


def test_survey_spans_reach_trace_annotation(monkeypatch):
    """Every span a survey run records enters the profiler's
    `TraceAnnotation`; `survey.prep` holds the sparse-operator precompute,
    the wavelets' readback, the table binning (with its slot fill) and the
    batch stacking; the dispatch that traced carries the compiles it saw,
    a warm one none."""
    import jax

    from repro.telemetry import spans as tsp

    annotated = []

    class Recorder:
        def __init__(self, name, **kw):
            annotated.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    grid, dt, params = _case("acoustic", n=8)
    plan = TBPlan(tile=(8, 8), T=2, radius=2)
    shots = [_shot(grid, dt, 1, 3, seed=1), _shot(grid, dt, 1, 3, seed=2)]
    engine = SurveyEngine("acoustic", grid, params, NT, dt, order=ORDER,
                          executor="jnp", plan=plan, plan_cache=PlanCache(),
                          bucket_cap=2)
    c = tsp.enable(jax_profiler=True)
    try:
        engine.run(shots)
        engine.run(shots)
    finally:
        tsp.disable()
    recs = c.records()
    names = {r.name for r in recs}
    assert names == {"survey.run", "survey.prep", "survey.dcmp",
                     "survey.tables", "survey.stack", "survey.dispatch",
                     "survey.readback",
                     "sources.precompute", "sources.precompute_receivers",
                     "ops.tile_pass"}
    assert names <= set(annotated)
    parent = {r.name: r.parent for r in recs}
    for child in ("survey.dcmp", "survey.tables", "survey.stack",
                  "sources.precompute", "sources.precompute_receivers"):
        assert parent[child] == "survey.prep", child
    assert parent["survey.prep"] == parent["survey.dispatch"] == "survey.run"
    first, warm = [r.attrs for r in recs if r.name == "survey.dispatch"]
    assert first["traced"] is True and first["compiles"] >= 1
    assert warm["traced"] is False and warm["compiles"] == 0
    _, src_cap, rec_cap = engine._caps((1, pad_count(3)))
    tables = [r.attrs for r in recs if r.name == "survey.tables"]
    assert len(tables) == 2 * len(shots)
    for t in tables:   # one (8, 8) tile; main T 2 and remainder T 1
        assert t["steps"] == [2, 1]
        assert t["src_slots"] == [src_cap] * 2
        assert t["rec_slots"] == [rec_cap] * 2
        assert all(0 < n <= src_cap for n in t["src_live"])
        assert all(0 < n <= rec_cap for n in t["rec_live"])


def test_sharded_route_matches_vmap_route():
    """`run_sharded` (shot round-robin through `sharded_tb_propagate` on a
    1x1 mesh) must produce the same traces as the vmapped single-device
    route."""
    from repro.distributed.halo import DistTBPlan
    from repro.launch import mesh as mesh_lib

    grid, dt, params = _case("acoustic", n=16)
    shots = [_shot(grid, dt, 2, 3, seed=1), _shot(grid, dt, 1, 4, seed=2)]
    engine = SurveyEngine("acoustic", grid, params, NT, dt, order=ORDER,
                          executor="jnp", plan_cache=PlanCache(),
                          bucket_cap=2)
    vres = engine.run(shots)
    dplan = DistTBPlan(mesh=mesh_lib.make_xy_mesh(),
                       grid_shape=tuple(grid.shape),
                       physics=phys.ACOUSTIC, order=ORDER, T=2, dt=dt,
                       spacing=grid.spacing)
    sres = engine.run_sharded(shots, dplan)
    assert sres.stats["route"] == "sharded"
    for got, ref in zip(sres.traces, vres.traces):
        assert got.shape == ref.shape
        scale = float(np.max(np.abs(ref))) + 1e-30
        assert float(np.max(np.abs(got - ref))) <= 5e-4 * scale + 1e-6


def test_engine_rejects_mismatched_nt():
    grid, dt, params = _case("acoustic")
    engine = SurveyEngine("acoustic", grid, params, NT, dt, order=ORDER,
                          executor="jnp", plan_cache=PlanCache())
    bad = _shot(grid, dt, 1, 2, seed=1)
    bad = Shot(src_coords=bad.src_coords,
               wavelet=np.zeros((NT + 2, 1)), rec_coords=bad.rec_coords)
    with pytest.raises(ValueError, match="nt"):
        engine.run([bad])


# ---------------------------------------------------------------------------
# Interpolation spec threading (ISSUE 9): caps scale with (2r)**3 and the
# sinc kernel runs the full batched path against the sequential oracle
# ---------------------------------------------------------------------------

def test_caps_scale_with_interp_footprint():
    from repro.core import interp as interp_mod

    grid, dt, params = _case("acoustic")
    lin = SurveyEngine("acoustic", grid, params, NT, dt, order=ORDER,
                       executor="jnp", plan_cache=PlanCache())
    assert lin._caps((4, 8)) == (8 * 4, 8 * 4, 8 * 8)
    snc = SurveyEngine("acoustic", grid, params, NT, dt, order=ORDER,
                       executor="jnp", plan_cache=PlanCache(),
                       interp="sinc", interp_order=2)
    assert snc.interp == interp_mod.InterpSpec(kernel="sinc", radius=2)
    assert snc._caps((4, 8)) == (64 * 4, 64 * 4, 64 * 8)


@pytest.mark.slow
def test_batched_parity_sinc():
    """The sinc survey path end-to-end: engine (batched, jnp executor)
    vs the sequential oracle with the SAME spec — traces must agree.

    Slow: the footprint-64 scatter/gather makes this the most expensive
    jit in the suite; tier-1 sinc coverage lives in test_interp.py /
    test_sources.py (inject + receiver parity without a TB propagate)."""
    from repro.core import interp as interp_mod

    spec = interp_mod.InterpSpec(kernel="sinc", radius=2)
    grid, dt, params = _case("acoustic", n=16, nz=8)
    # interior shots: radius-2 supports must not clip (clipping is legal
    # but the oracle comparison is cleanest without it)
    rng = np.random.RandomState(5)
    ext = np.asarray(grid.extent)
    shots = []
    for i in range(2):
        src = 30.0 + rng.rand(2, 3) * (ext - 60.0)
        rec = 30.0 + rng.rand(3, 3) * (ext - 60.0)
        shots.append(Shot(src_coords=src,
                          wavelet=1e3 * S.ricker_wavelet(NT, dt, f0=12.0,
                                                         num=2),
                          rec_coords=rec, shot_id=i))
    engine = SurveyEngine("acoustic", grid, params, NT, dt, order=ORDER,
                          executor="jnp", plan_cache=PlanCache(),
                          bucket_cap=2, interp=spec)
    result = engine.run(shots)
    assert result.stats["interp"] == spec.to_dict()
    assert result.stats["footprint"] == 64
    seq = sequential_traces("acoustic", shots, grid, params, engine.plan,
                            ORDER, dt, NT, interp=spec)
    for got, ref in zip(result.traces, seq):
        scale = float(np.max(np.abs(ref))) + 1e-30
        assert float(np.max(np.abs(got - ref))) <= 5e-4 * scale + 1e-6
