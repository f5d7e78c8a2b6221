"""SurveyEngine: shot-parallel execution over the TB stack (DESIGN.md §6).

One survey = one model, many independent shots.  The engine amortizes
everything shot-invariant:

  plan      ONE autotune sweep per configuration via the plan cache
            (`survey/plan_cache.py`) — never per shot, never per bucket.
  compile   ONE jit trace per (physics, bucket shape): shots are bucketed
            by padded (nsrc, nrec) (`survey/shots.py`) and each bucket's
            executable runs the single-device TB propagator
            (`kernels/ops.tb_propagate_prepared`) over a stacked shot
            axis: `jax.vmap` with the jnp executor, a per-shot
            `jax.lax.map` with the Pallas one (the kernel takes no batch
            axis), so Pallas shots of a batch run one after another in
            one dispatch.  Batches are padded to a FIXED leading dim
            (`bucket_cap`) with silent null shots, so ragged buckets and
            repeat runs never re-trace.
  transfer  receiver traces are double-buffered: batch i+1 is dispatched
            (async) before batch i's traces are pulled to host, so the
            device computes under the host transfer.

Host-side per-shot work (the paper's §II precompute + per-tile table
binning) is the only per-shot serial cost; it is the paper's "negligible
overhead" path and stays off the device.

All static shapes derive from the bucket key alone: a window can hold at
most all of a shot's affected points (<= footprint * nsrc_pad, where
footprint = (2r)**3 for the engine's interpolation radius r — 8 for the
default trilinear kernel), and a tile at most all receiver gather entries
(<= footprint * nrec_pad), so table caps — hence compiled shapes — are
functions of (physics, bucket, interp spec), not of any particular shot
geometry.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import interp as interp_mod
from repro.core import sources as src_mod
from repro.core.grid import Grid
from repro.core.temporal_blocking import TBPlan
from repro.kernels import ops as ops_mod
from repro.kernels import tb_physics as phys
from repro.kernels.platform import resolve_interpret
from repro.survey.plan_cache import (CacheInfo, PlanCache,
                                     cached_plan_for_physics, default_cache)
from repro.survey.shots import Shot, Survey, bucket_shots
from repro.telemetry import metrics as _tm
from repro.telemetry import spans as _spans

class _ShotArrays(NamedTuple):
    """Per-shot traced operands of `ops.tb_propagate_prepared` (stacked
    along a new leading shot axis by the batch builder)."""

    src_dcmp: jnp.ndarray
    src_tab: src_mod.TileSourceTable
    rec_tab: src_mod.TileReceiverTable
    rsrc_tab: Optional[src_mod.TileSourceTable]
    rrec_tab: Optional[src_mod.TileReceiverTable]


class SurveyResult(NamedTuple):
    """Traces per shot (survey order) + throughput/caching statistics.

    traces: list of (nt, nrec) arrays ((nt, nrec, 2) for elastic), one
            per shot, already cropped to the shot's ACTUAL receiver count.
    stats:  seconds, shots_per_s, mpoints_per_s, buckets, batches, plan,
            cache {key, hit, sweeps}, traces_per_bucket.
    wavefields: final state tuples per shot when requested, else None.
    """

    traces: List[np.ndarray]
    stats: dict
    wavefields: Optional[list] = None


# The public contract of `SurveyEngine.run(...).stats` — every key the
# downstream consumers (benchmarks/fig13_survey.py, check_regression's
# SURVEY_KEY, the CI survey gate) may read.  tests/test_survey.py pins
# this set, so growing it is deliberate and shrinking it is loud.
RUN_STATS_KEYS = frozenset({
    "route", "physics", "executor", "shots",
    "seconds", "cold_seconds", "warm_seconds",
    "plan_seconds", "compile_seconds",
    "shots_per_s", "mpoints_per_s",
    "buckets", "batches", "bucket_cap", "bucket_keys",
    "interp", "footprint", "plan", "cache", "traces_per_bucket",
    "metrics",
})


def _default_tiles(nx: int, ny: int) -> Tuple[int, ...]:
    """Candidate inner tiles that divide the grid (the single-device
    analogue of the mesh-block feasibility filter)."""
    cands = tuple(t for t in (4, 8, 16, 32, 64, 128)
                  if nx % t == 0 and ny % t == 0)
    return cands or (nx,)


class SurveyEngine:
    """Compile-once, run-many multi-shot executor for one physics/model.

    Args:
      physics:    "acoustic" | "tti" | "elastic".
      grid:       the shared FD grid.
      params:     physics.param_fields -> (nx, ny, nz) model arrays
                  (shared by every shot — a survey is one model).
      nt:         timesteps per shot (uniform across the survey).
      dt:         timestep.
      order:      space order.
      executor:   "pallas" (the TB kernel; interpreted on CPU) or "jnp"
                  (the same window schedule in pure jnp).
      plan:       a TBPlan to skip planning; default consults the plan
                  cache (ONE sweep per configuration).
      plan_cache: PlanCache instance (default: the process-wide cache).
      bucket_cap: compiled batch size — every dispatch has exactly this
                  many shots (partial batches pad with null shots), so a
                  bucket never re-traces.
      interpret:  Pallas interpret mode; None picks by backend
                  (`kernels.platform.resolve_interpret`).
      interp:     interpolation kernel — an `interp.InterpSpec`, or the
                  kernel name ("linear" / "sinc") resolved together with
                  `interp_order` (the support radius r) by
                  `interp.spec_for`.  Every per-shot precompute and all
                  bucket caps derive from this one spec: caps scale as
                  (2r)**3 * n instead of the trilinear 8 * n.
      interp_order: support radius for a string `interp`; ignored when
                  `interp` is already an InterpSpec.
    """

    def __init__(self, physics: str, grid: Grid,
                 params: Dict[str, jnp.ndarray], nt: int, dt: float,
                 order: int = 4, executor: str = "pallas",
                 plan: Optional[TBPlan] = None,
                 plan_cache: Optional[PlanCache] = None,
                 bucket_cap: int = 4, interpret: Optional[bool] = None,
                 plan_kwargs: Optional[dict] = None,
                 interp: Union[str, interp_mod.InterpSpec] = "linear",
                 interp_order: Optional[int] = None):
        if executor not in ("pallas", "jnp"):
            raise ValueError(f"unknown executor {executor!r}")
        self.interp = (interp if isinstance(interp, interp_mod.InterpSpec)
                       else interp_mod.spec_for(interp, interp_order))
        self._footprint = self.interp.footprint(3)
        self.physics = phys.PHYSICS[physics]
        self.physics_name = physics
        self.grid = grid
        self.shape = tuple(grid.shape)
        self.params = {f: params[f] for f in self.physics.param_fields}
        self.nt = int(nt)
        self.dt = float(dt)
        self.order = int(order)
        self.executor = executor
        self.bucket_cap = int(bucket_cap)
        if self.bucket_cap < 1:
            raise ValueError("bucket_cap must be >= 1")
        self.interpret = resolve_interpret(interpret)
        self.cache = plan_cache or default_cache()
        self.cache_info: Optional[CacheInfo] = None
        self.plan_entry: Optional[dict] = None
        # planning is COLD time: the first run() claims it into
        # cold_seconds (satellite: cold/warm split)
        t_plan = time.perf_counter()
        if plan is None:
            # dict literal, not dict(tiles=..., **plan_kwargs): caller
            # overrides of tiles/depths must win, not TypeError
            kw = {"tiles": _default_tiles(*self.shape[:2]),
                  "depths": (1, 2, 4, 8), **(plan_kwargs or {})}
            with _spans.span("survey.sweep", physics=physics):
                plan, self.plan_entry, self.cache_info = \
                    cached_plan_for_physics(
                        physics, self.shape[2], self.order, cache=self.cache,
                        key_extra={"grid_shape": list(self.shape),
                                   "use": "survey-single-device"}, **kw)
        self._plan_seconds = time.perf_counter() - t_plan
        self._plan_claimed = False
        self.plan = plan
        self.metrics = _tm.MetricsRegistry()
        self._zero_state = tuple(
            jnp.zeros(self.shape, jnp.float32)
            for _ in self.physics.state_fields)
        # one executable + one trace counter per bucket key
        self._execs: Dict[Tuple[int, int], object] = {}
        self._param_pads: Dict[int, tuple] = {}
        self.trace_counts: Dict[Tuple[int, int], int] = {}

    # --- static shapes from the bucket key ---------------------------------

    def _caps(self, key: Tuple[int, int]) -> Tuple[int, int, int]:
        """(npts_cap, src_cap, rec_cap): every cap is the worst case over
        ANY shot of this bucket shape, so compiled shapes depend on the
        (key, interp spec) alone — footprint = (2r)**3 support points per
        off-grid coordinate."""
        nsrc_pad, nrec_pad = key
        npts_cap = self._footprint * nsrc_pad
        return npts_cap, npts_cap, self._footprint * nrec_pad

    def _specs(self, key: Tuple[int, int]):
        _, src_cap, rec_cap = self._caps(key)
        spec = ops_mod.make_spec(self.shape, self.plan, self.order, self.dt,
                                 self.grid.spacing, src_cap, rec_cap,
                                 physics=self.physics)
        rem = self.nt % spec.T
        rspec = None
        if rem > 0:
            rplan = dataclasses.replace(self.plan, T=rem)
            rspec = ops_mod.make_spec(self.shape, rplan, self.order, self.dt,
                                      self.grid.spacing, src_cap, rec_cap,
                                      physics=self.physics)
        return spec, rspec

    def _pads_for(self, halo: int):
        if halo not in self._param_pads:
            self._param_pads[halo] = tuple(
                ops_mod._pad_xy(self.params[f], halo, "edge")
                for f in self.physics.param_fields)
        return self._param_pads[halo]

    # --- host-side per-shot precompute (paper §II) --------------------------

    def _prep_shot(self, shot: Shot, key: Tuple[int, int],
                   spec, rspec) -> _ShotArrays:
        npts_cap, src_cap, rec_cap = self._caps(key)
        g = src_mod.precompute(src_mod.SparseOperator(shot.src_coords),
                               self.grid, shot.wavelet, interp=self.interp)
        gr = src_mod.precompute_receivers(
            src_mod.SparseOperator(shot.rec_coords), self.grid,
            interp=self.interp)
        # the decomposed wavelets back from the device, padded to the cap
        with _spans.span("survey.dcmp", shot=shot.shot_id):
            dcmp = np.zeros((self.nt, npts_cap), np.float32)
            dcmp[:, :g.npts] = np.asarray(g.src_dcmp)[:self.nt]

        def tabs(s):
            st = src_mod.tile_source_tables(
                g, self.shape, s.tile, s.halo, scale=scale, cap=src_cap,
                include_halo=s.T > 1)
            rt = src_mod.tile_receiver_tables(gr, self.shape, s.tile,
                                              s.halo, cap=rec_cap)
            return st, rt

        with _spans.span("survey.tables", shot=shot.shot_id) as sp:
            scale = np.asarray(
                self.physics.inject_scale(self.params, g, self.dt),
                np.float32)
            src_tab, rec_tab = tabs(spec)
            rsrc_tab = rrec_tab = None
            if rspec is not None:
                rsrc_tab, rrec_tab = tabs(rspec)
            if _spans.active():
                sp.set(**ops_mod.slot_fill(spec, self.nt, (src_tab, rec_tab),
                                           (rsrc_tab, rrec_tab)),
                       **ops_mod.update_counts(spec, self.nt))
        return _ShotArrays(jnp.asarray(dcmp), src_tab, rec_tab,
                           rsrc_tab, rrec_tab)

    # --- the per-bucket executable ------------------------------------------

    def _executable(self, key: Tuple[int, int]):
        if key in self._execs:
            return self._execs[key]
        spec, rspec = self._specs(key)
        physics, nt = self.physics, self.nt
        nrec_pad = key[1]
        interpret, executor = self.interpret, self.executor
        self.trace_counts.setdefault(key, 0)

        def one_shot(param_pads, rparam_pads, arrs: _ShotArrays):
            return ops_mod.tb_propagate_prepared(
                physics, nt, spec, rspec, self._zero_state, param_pads,
                rparam_pads, arrs.src_dcmp, arrs.src_tab, arrs.rec_tab,
                arrs.rsrc_tab, arrs.rrec_tab, nrec_pad,
                interpret=interpret, executor=executor)

        def batched(param_pads, rparam_pads, batch: _ShotArrays):
            # fires once per jit trace: the compile counter the acceptance
            # test pins to 1 per bucket
            self.trace_counts[key] += 1
            if executor == "pallas":
                return jax.lax.map(
                    lambda a: one_shot(param_pads, rparam_pads, a), batch)
            return jax.vmap(one_shot, in_axes=(None, None, 0))(
                param_pads, rparam_pads, batch)

        fn = jax.jit(batched)
        self._execs[key] = (fn, spec, rspec)
        return self._execs[key]

    # --- run ---------------------------------------------------------------

    def _stack_batch(self, preps: List[_ShotArrays], pad_to: int
                     ) -> _ShotArrays:
        """Stack per-shot pytrees along a new shot axis; partial batches
        replicate the last shot with a ZEROED wavelet table (a silent
        shot — its outputs are computed and discarded)."""
        short = pad_to - len(preps)
        if short > 0:
            null = preps[-1]._replace(
                src_dcmp=jnp.zeros_like(preps[-1].src_dcmp))
            preps = preps + [null] * short
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *preps)

    def run(self, survey: Union[Survey, Sequence[Shot]],
            return_wavefields: bool = False) -> SurveyResult:
        """Execute every shot; returns traces in survey order.

        Dispatch is pipelined: while batch i computes on the device, batch
        i-1's receiver traces stream to host (`np.asarray` blocks only on
        the already-dispatched older batch) and batch i+1's tables are
        host-built — the double-buffering row of the DESIGN.md §6 map.
        """
        shots = list(survey.shots if isinstance(survey, Survey) else survey)
        for s in shots:
            if s.nt != self.nt:
                raise ValueError(f"shot {s.shot_id} has nt={s.nt}, engine "
                                 f"compiled for nt={self.nt}")
        t_start = time.perf_counter()
        buckets = bucket_shots(shots)
        traces: List[Optional[np.ndarray]] = [None] * len(shots)
        fields: List = [None] * len(shots)
        pending = None  # (indices, shots, device recs, device state)
        n_batches = 0
        compile_seconds = 0.0  # wall spent inside dispatches that TRACED

        def collect(p):
            idxs, recs, st = p
            with _spans.span("survey.readback", shots=len(idxs)):
                host = np.asarray(recs)  # blocks on an already-running batch
            for row, i in enumerate(idxs):
                tr = host[row, :, :shots[i].nrec]  # crop the bucket padding
                if self.physics.rec_channels == 1:
                    tr = tr[..., 0]
                traces[i] = tr
                if return_wavefields:
                    fields[i] = tuple(np.asarray(f[row]) for f in st)

        with _spans.span("survey.run", shots=len(shots)):
            for key, bucket in buckets.items():
                fn, spec, rspec = self._executable(key)
                param_pads = self._pads_for(spec.halo)
                rparam_pads = (self._pads_for(rspec.halo)
                               if rspec is not None else None)
                for lo in range(0, len(bucket), self.bucket_cap):
                    chunk = bucket.shots[lo:lo + self.bucket_cap]
                    idxs = bucket.indices[lo:lo + self.bucket_cap]
                    with _spans.span("survey.prep", bucket=key,
                                     n=len(chunk)):
                        preps = [self._prep_shot(s, key, spec, rspec)
                                 for s in chunk]
                        with _spans.span("survey.stack", n=len(preps)):
                            batch = self._stack_batch(preps,
                                                      self.bucket_cap)
                    # the dispatch wall includes trace+compile the first
                    # time a bucket runs: attribute it by watching the
                    # per-bucket trace counter around the call
                    traced_before = self.trace_counts[key]
                    t0 = time.perf_counter()
                    with _spans.span("survey.dispatch", count_compiles=True,
                                     bucket=key) as sp:
                        state_b, recs_b = fn(param_pads, rparam_pads, batch)
                        traced = self.trace_counts[key] > traced_before
                        sp.set(traced=traced)
                    d = time.perf_counter() - t0
                    if traced:
                        compile_seconds += d
                        self.metrics.histogram("survey.compile_s").observe(d)
                    else:
                        self.metrics.histogram("survey.dispatch_s").observe(d)
                    n_batches += 1
                    if pending is not None:
                        collect(pending)
                    pending = (idxs, recs_b, state_b)
            if pending is not None:
                collect(pending)
        seconds = time.perf_counter() - t_start

        # cold = planning (first run only) + jit traces; warm = the
        # steady-state execution wall the throughput numbers must use, so
        # BENCH_survey stops depending on cache state
        plan_seconds = 0.0 if self._plan_claimed else self._plan_seconds
        self._plan_claimed = True
        cold_seconds = plan_seconds + compile_seconds
        warm_seconds = max(seconds - compile_seconds, 1e-12)

        n = len(shots)
        pts = float(np.prod(self.shape)) * self.nt * n
        self.metrics.counter("survey.shots").inc(n)
        self.metrics.counter("survey.batches").inc(n_batches)
        stats = {
            "route": "map" if self.executor == "pallas" else "vmap",
            "physics": self.physics_name, "executor": self.executor,
            "shots": n, "seconds": seconds,
            "cold_seconds": cold_seconds, "warm_seconds": warm_seconds,
            "plan_seconds": plan_seconds,
            "compile_seconds": compile_seconds,
            "shots_per_s": n / warm_seconds,
            "mpoints_per_s": pts / warm_seconds / 1e6,
            "buckets": len(buckets), "batches": n_batches,
            "bucket_cap": self.bucket_cap,
            "bucket_keys": [list(k) for k in buckets],
            "interp": self.interp.to_dict(),
            "footprint": self._footprint,
            "plan": self.plan.to_dict(),
            "cache": {"sweeps": self.cache.sweeps,
                      **({"key": self.cache_info.key,
                          "hit": self.cache_info.hit}
                         if self.cache_info else {})},
            "traces_per_bucket": {str(k): v
                                  for k, v in self.trace_counts.items()},
            "metrics": self.metrics.snapshot(),
        }
        return SurveyResult(traces=traces, stats=stats,
                            wavefields=fields if return_wavefields else None)

    # --- the mesh route: shot round-robin through the sharded layer --------

    def run_sharded(self, survey: Union[Survey, Sequence[Shot]],
                    dist_plan) -> SurveyResult:
        """Round-robin the survey's shots through `sharded_tb_propagate`
        on `dist_plan`'s mesh — DOMAIN-parallel per shot instead of
        shot-parallel, for models too large for one device.

        The per-shot table binning of the sharded layer sizes its caps
        from each shot's geometry, so this route is dispatched eagerly
        (no per-bucket jit amortization yet — ROADMAP: fixed-cap sharded
        tables would make the buckets jittable here too); the plan cache
        still amortizes the planning, and traces come back in survey
        order exactly like `run`.
        """
        from repro.distributed.halo import sharded_tb_propagate

        shots = list(survey.shots if isinstance(survey, Survey) else survey)
        if dist_plan.physics.name != self.physics.name:
            raise ValueError(f"dist_plan is for {dist_plan.physics.name}, "
                             f"engine for {self.physics.name}")
        t_start = time.perf_counter()
        traces: List[np.ndarray] = []
        with dist_plan.mesh:
            for s in shots:
                if s.nt != self.nt:
                    raise ValueError(f"shot {s.shot_id} has nt={s.nt}, "
                                     f"engine built for nt={self.nt}")
                g = src_mod.precompute(
                    src_mod.SparseOperator(s.src_coords), self.grid,
                    s.wavelet, interp=self.interp)
                gr = src_mod.precompute_receivers(
                    src_mod.SparseOperator(s.rec_coords), self.grid,
                    interp=self.interp)
                with _spans.span("survey.sharded_shot",
                                 shot=s.shot_id) as sp:
                    _, rec = sharded_tb_propagate(
                        dist_plan, self.nt, self._zero_state, self.params,
                        g=g, receivers=gr, interpret=self.interpret)
                    sp.sync(rec)
                tr = np.asarray(rec)
                traces.append(tr[..., 0] if self.physics.rec_channels == 1
                              else tr)
        seconds = time.perf_counter() - t_start
        n = len(shots)
        pts = float(np.prod(self.shape)) * self.nt * n
        stats = {
            "route": "sharded", "physics": self.physics_name,
            "shots": n, "seconds": seconds,
            "shots_per_s": n / seconds if seconds else float("inf"),
            "mpoints_per_s": pts / seconds / 1e6 if seconds else 0.0,
            "mesh": dict(dist_plan.mesh.shape),
            "outer_T": dist_plan.T, "inner": dist_plan.inner,
            "cache": {"sweeps": self.cache.sweeps},
        }
        return SurveyResult(traces=traces, stats=stats)


__all__ = ["RUN_STATS_KEYS", "SurveyEngine", "SurveyResult"]
