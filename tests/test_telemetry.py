"""Telemetry layer: spans and metrics.

Acceptance points under test:

  * spans nest per thread, record in exit order with correct depths and
    parents, and export a schema-valid Chrome-trace / Perfetto event
    stream;
  * the whole subsystem is a shared no-op object when disabled;
  * a propagate's spans (sparse-operator precompute, table binning with
    its slot fill, the dispatch with its compile count) all reach the
    profiler's timeline, and its tile pass lowers with fixed scope and
    kernel names whether or not telemetry is on;
  * a corrupt/truncated disk plan-cache entry is a counted miss (warning,
    not a crash) and the next sweep's `store` overwrites it.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core.temporal_blocking import TBPlan
from repro.telemetry import metrics as tm
from repro.telemetry import spans as tsp


@pytest.fixture
def coll():
    c = tsp.enable(jax_profiler=False)
    yield c
    tsp.disable()


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_disabled_spans_are_shared_noop():
    tsp.disable()
    assert not tsp.active()
    s = tsp.span("x", depth=3)
    assert s is tsp.span("y") is tsp.annotate("z")  # one shared object
    with s as live:
        assert live.sync("payload") == "payload"


def test_span_nesting_order_and_depth(coll):
    with tsp.span("a", k=1):
        with tsp.span("a.b"):
            pass
        with tsp.span("a.c", depth=7):  # attr named `depth` must survive
            pass
    recs = coll.records()
    # children complete (and record) before the parent
    assert [(r.name, r.depth) for r in recs] == \
        [("a.b", 1), ("a.c", 1), ("a", 0)]
    assert recs[2].attrs == {"k": 1}
    assert recs[1].attrs == {"depth": 7}
    # the parent's interval contains both children
    a = recs[2]
    for child in recs[:2]:
        assert a.start <= child.start
        assert child.start + child.dur <= a.start + a.dur + 1e-6


def test_span_cancel_and_manual_add(coll):
    with tsp.span("dropped") as sp:
        sp.cancel()
    t0 = time.perf_counter()
    with tsp.span("kept", bucket=(1, 2)) as sp:
        sp.set(n=np.int64(3))
    recs = coll.records()
    assert [r.name for r in recs] == ["kept"]
    assert 0.0 <= recs[0].dur <= time.perf_counter() - t0
    # tuples and numpy scalars made JSON-able
    assert recs[0].attrs == {"bucket": [1, 2], "n": 3}
    assert type(recs[0].attrs["n"]) is int


def test_span_nesting_is_per_thread(coll):
    done = threading.Event()

    def worker():
        with tsp.span("thread.inner"):
            pass
        done.set()

    with tsp.span("main.outer"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert done.is_set()
    by_name = {r.name: r for r in coll.records()}
    assert by_name["thread.inner"].depth == 0  # not nested under main's
    assert by_name["main.outer"].depth == 0
    assert by_name["thread.inner"].tid != by_name["main.outer"].tid


def test_span_parent_nested_and_per_thread(coll):
    def worker():
        with tsp.span("thread.outer"):
            with tsp.span("thread.inner"):
                pass

    with tsp.span("main.outer"):
        with tsp.span("main.inner"):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
        with tsp.span("main.after"):
            pass
    assert not t.is_alive()
    assert {r.name: r.parent for r in coll.records()} == {
        "main.outer": None, "main.inner": "main.outer",
        "main.after": "main.outer",
        "thread.outer": None, "thread.inner": "thread.outer"}


def test_device_sync_blocks_on_pytree(coll):
    import jax.numpy as jnp

    x = jnp.arange(8.0)
    with tsp.span("sync", device_sync={"x": x}) as sp:
        assert sp.sync((x, x)) == (x, x)
    assert coll.names() == ["sync"]


def test_annotate_records_span_and_named_scope(coll):
    import jax

    @jax.jit
    def f(v):
        with tsp.annotate("region.traced", T=2):
            return v * 2.0

    f(3.0)  # records the TRACING time of the region
    with tsp.annotate("region.eager"):
        pass
    names = coll.names()
    assert names == ["region.traced", "region.eager"]
    assert all(r.attrs.get("trace_region") for r in coll.records())
    f(4.0)  # cached executable: no re-trace, no second record
    assert coll.names() == names


def test_chrome_trace_schema(coll):
    with tsp.span("outer", physics="acoustic"):
        with tsp.span("inner"):
            pass
    trace = coll.chrome_trace()
    assert trace["displayTimeUnit"] == "ms"
    events = trace["traceEvents"]
    assert [e["name"] for e in events] == ["outer", "inner"]  # start order
    for e in events:
        assert e["ph"] == "X"
        assert set(e) >= {"name", "ph", "cat", "ts", "dur", "pid", "tid",
                          "args"}
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        assert e["cat"] == e["name"].split(".")[0]
    # microseconds: inner nests inside outer on the timeline
    o, i = events
    assert o["ts"] <= i["ts"] <= i["ts"] + i["dur"] <= \
        o["ts"] + o["dur"] + 1.0
    json.dumps(trace)  # args must be JSON-clean


def test_export_roundtrip(tmp_path, coll):
    with tsp.span("e"):
        pass
    p = coll.export(str(tmp_path / "trace.json"))
    loaded = json.load(open(p))
    assert [e["name"] for e in loaded["traceEvents"]] == ["e"]


# ---------------------------------------------------------------------------
# Program spans: a tiny acoustic propagate
# ---------------------------------------------------------------------------

# two (8, 8) tiles along x; T 2 over nt 3 leaves a remainder tile of T 1.
# The source's 8 trilinear points (x 2..3) lie in tile 0's centre and
# outside tile 1's halo-4 window; the receiver's straddle the tile edge
# (x 7 | 8), 4 points on each side.
TINY = dict(shape=(16, 8, 12), src=[[25.0, 35.0, 35.0]],
            rec=[[75.0, 35.0, 35.0]], nt=3, T=2)


def _tiny_case():
    from repro.core import sources as S
    from repro.core.grid import Grid

    grid = Grid(shape=TINY["shape"], spacing=(10.0,) * 3)
    wav = np.linspace(1.0, 2.0, TINY["nt"])[:, None]
    g = S.precompute(S.SparseOperator(TINY["src"]), grid, wav)
    gr = S.precompute_receivers(S.SparseOperator(TINY["rec"]), grid)
    return grid, g, gr


@pytest.fixture(scope="module")
def tiny_propagate():
    """One tiny acoustic propagate, cold, under a collector mirrored into
    the profiler, with `TraceAnnotation` replaced by a recorder of names
    and a compile listener of the test's own beside the collector's."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    from repro.core.temporal_blocking import TBPlan
    from repro.kernels import ops

    annotated, compiled_at = [], []

    class Recorder:
        def __init__(self, name, **kw):
            annotated.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def listen(event, secs, **kw):
        if event == tsp.BACKEND_COMPILE:
            compiled_at.append(time.perf_counter())

    shape = TINY["shape"]
    # a cold call, even where an earlier test in this process already
    # compiled the jitted propagate for these shapes
    ops._tb_propagate_jit.clear_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.profiler, "TraceAnnotation", Recorder)
        monitoring.register_event_duration_secs_listener(listen)
        c = tsp.enable(jax_profiler=True)
        try:
            grid, g, gr = _tiny_case()
            zero = jnp.zeros(shape, jnp.float32)
            _, rec = ops.acoustic_tb_propagate(
                TINY["nt"], zero, zero, jnp.full(shape, 1e-7),
                jnp.zeros(shape), g, gr,
                TBPlan(tile=(8, 8), T=TINY["T"], radius=2), 4, 1e-3,
                grid.spacing)
            jax.block_until_ready(rec)
        finally:
            tsp.disable()
            monitoring.unregister_event_duration_listener(listen)
    return c, annotated, compiled_at


def test_propagate_spans_reach_trace_annotation(tiny_propagate):
    c, annotated, _ = tiny_propagate
    names = set(c.names())
    assert names >= {"sources.precompute", "sources.precompute_receivers",
                     "ops.tables", "ops.propagate", "ops.dispatch",
                     "ops.tile_pass"}
    assert names <= set(annotated)
    parent = {r.name: r.parent for r in c.records()}
    assert parent["ops.dispatch"] == "ops.propagate"
    assert parent["ops.tile_pass"] == "ops.dispatch"


def test_ops_dispatch_counts_compiles(tiny_propagate):
    c, _, compiled_at = tiny_propagate
    (d,) = [r for r in c.records() if r.name == "ops.dispatch"]
    t0 = c.epoch + d.start
    seen = [t for t in compiled_at if t0 <= t <= t0 + d.dur]
    assert d.attrs["compiles"] == len(seen) >= 1


def test_ops_tables_slot_fill_hand_count(tiny_propagate):
    c, _, _ = tiny_propagate
    (t,) = [r for r in c.records() if r.name == "ops.tables"]
    # main tables (T 2, halo 4): src 8 live in cap 8 x 2 tiles, rec 8
    # live in cap 4 x 2 tiles, serving 2 steps; the remainder (T 1)
    # bins the same points by centre, serving 1
    assert {k: t.attrs[k] for k in ("src_live", "src_slots", "rec_live",
                                    "rec_slots", "steps")} == {
        "src_live": [8, 8], "src_slots": [16, 16], "rec_live": [8, 8],
        "rec_slots": [8, 8], "steps": [2, 1]}


def test_ops_tables_slot_iters_hand_count(tiny_propagate):
    c, _, _ = tiny_propagate
    (t,) = [r for r in c.records() if r.name == "ops.tables"]
    # main call (T 2, window 16 planes in x, slabs of 8): step 0 keeps
    # planes [2, 14) in 2 slabs, step 1 [4, 12) in 1, so each of the 2
    # tiles runs 3 slabs; the remainder (T 1, window 12) keeps [2, 10)
    # in 1.  Live: (8 + 8) x 3 + (8 + 8) x 1; caps: 2 tiles x (8 + 4) x
    # 3 + 2 tiles x (8 + 4) x 1
    assert (t.attrs["slot_iters"], t.attrs["slot_iters_at_cap"]) == \
        (16 * 3 + 16 * 1, 24 * 3 + 24 * 1)


def test_sources_precompute_records_host_bytes(tiny_propagate):
    c, _, _ = tiny_propagate
    by = {r.name: r.attrs for r in c.records()}
    # the host builds no dense SM / SID grid: only the per-point wavelets
    assert by["sources.precompute"] == {
        "nsrc": 1, "npts": 8, "sm_bytes": 0, "sid_bytes": 0,
        "src_dcmp_bytes": 8 * TINY["nt"] * 8}
    assert by["sources.precompute_receivers"] == {
        "nrec": 1, "npts": 8, "indices_bytes": 8 * 3 * 4,
        "weights_bytes": 8 * 8}


def test_tile_pass_lowers_named_scopes_with_telemetry_off():
    import jax
    import jax.numpy as jnp

    from repro.core.temporal_blocking import TBPlan
    from repro.kernels import ops
    from repro.kernels import tb_physics as phys

    tsp.disable()
    shape = TINY["shape"]
    grid, g, gr = _tiny_case()
    plan = TBPlan(tile=(8, 8), T=TINY["T"], radius=2)
    params = {"m": jnp.ones(shape), "damp": jnp.zeros(shape)}
    st, rt = ops.build_tables(
        ops.make_spec(shape, plan, 4, 1e-3, grid.spacing, 1, 1), g, gr,
        params)
    spec = ops.make_spec(shape, plan, 4, 1e-3, grid.spacing, st.cap,
                         rt.coords.shape[1])
    pads = tuple(ops._pad_xy(params[f], spec.halo, "edge")
                 for f in phys.ACOUSTIC.param_fields)

    def tile_pass(state, pads, dcmp, st, rt):
        return ops._run_time_tile(spec, phys.ACOUSTIC, state, pads, dcmp,
                                  st, rt, 0, 1, True)

    zero = jnp.zeros(shape)
    text = jax.jit(tile_pass).lower((zero, zero), pads, g.src_dcmp, st,
                                    rt).as_text(debug_info=True)
    for name in ("ops.state_pad", "ops.src_vals", "ops.rec_combine",
                 "tb_time_tile"):
        assert name in text, name


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_metrics_registry_and_snapshot():
    r = tm.MetricsRegistry()
    r.counter("hits").inc()
    r.counter("hits").inc(4)
    r.gauge("depth").set(3.5)
    h = r.histogram("lat")
    for v in (1.0, 3.0):
        h.observe(v)
    snap = r.snapshot()
    assert snap["counters"] == {"hits": 5}
    assert snap["gauges"] == {"depth": 3.5}
    assert snap["histograms"]["lat"] == {
        "count": 2, "total": 4.0, "min": 1.0, "max": 3.0, "mean": 2.0}
    with pytest.raises(TypeError):
        r.gauge("hits")  # name already registered as a counter
    r.clear()
    assert r.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# ---------------------------------------------------------------------------
# Plan-cache corruption (satellite: corrupt disk entry == counted miss)
# ---------------------------------------------------------------------------

def _consult(cache):
    from repro.survey.plan_cache import cached_plan_for_physics

    return cached_plan_for_physics("acoustic", 16, 4, cache=cache,
                                   tiles=(8, 16), depths=(1, 2))


def test_plan_cache_corrupt_disk_entry_is_a_miss(tmp_path):
    from repro.survey.plan_cache import PlanCache

    d = str(tmp_path / "plans")
    cache = PlanCache(disk_dir=d)
    plan, entry, info = _consult(cache)
    assert not info.hit and cache.sweeps == 1
    (path,) = [os.path.join(d, f) for f in os.listdir(d)]

    for garbage in ('{"plan": {"tile": [8,', b"\xff\xfe\x00garbage"):
        mode = "wb" if isinstance(garbage, bytes) else "w"
        with open(path, mode) as f:
            f.write(garbage)
        fresh = PlanCache(disk_dir=d)  # cold memory tier -> disk read
        before = fresh.disk_corrupt
        with pytest.warns(UserWarning, match="corrupt entry"):
            plan2, entry2, info2 = _consult(fresh)
        assert not info2.hit
        assert fresh.disk_corrupt == before + 1
        assert fresh.sweeps == 1
        assert plan2.to_dict() == plan.to_dict()
        # the re-sweep's store overwrote the bad file: next consult hits
        again = PlanCache(disk_dir=d)
        _, _, info3 = _consult(again)
        assert info3.hit and again.sweeps == 0


def test_plan_cache_schema_corrupt_entry_is_a_miss(tmp_path):
    from repro.survey.plan_cache import PlanCache

    d = str(tmp_path / "plans")
    cache = PlanCache(disk_dir=d)
    _consult(cache)
    (path,) = [os.path.join(d, f) for f in os.listdir(d)]
    with open(path, "w") as f:
        json.dump({"wrong": "schema"}, f)  # valid JSON, unusable payload
    fresh = PlanCache(disk_dir=d)
    with pytest.warns(UserWarning, match="corrupt entry"):
        _, _, info = _consult(fresh)
    assert not info.hit
    assert fresh.disk_corrupt == 1 and fresh.sweeps == 1
    again = PlanCache(disk_dir=d)
    _, _, info2 = _consult(again)
    assert info2.hit and again.sweeps == 0
