"""jit'd drivers for the Pallas kernels.

`acoustic_tb_propagate` / `tti_tb_propagate` / `elastic_tb_propagate` are
the production entry points: the outer time-tile loop of the paper's
Listing 6 (scan over depth-T time tiles, one `pallas_call` each), with the
per-tile source/receiver tables precomputed once from the paper's
grid-aligned structures.  All three share one physics-agnostic driver
(`_tb_propagate`) parameterized by a `tb_physics.TBPhysics` step spec —
the paper's point that the enabling transformation is independent of the
propagator.  `acoustic_sb_propagate` (T = 1) is the spatially-blocked
baseline the paper compares against.

The driver is split at the host/device boundary (DESIGN.md §6): the
host-side table binning happens in `_tb_propagate`, and everything after
it — `tb_propagate_prepared` — is a pure traced function of jnp pytrees
(state, padded params, `src_dcmp`, the per-tile tables).  That split is
what makes the survey engine possible: `survey/engine.py` stacks the
prepared tables of a whole shot bucket and runs `tb_propagate_prepared`
over the shot axis (a per-shot `jax.lax.map` for the Pallas executor,
`jax.vmap` for jnp), one jit trace per bucket.  The one-chip entry points
run it under `_tb_propagate_jit`, once per plan and array shapes.
Each time tile runs through one of two executors sharing the same window
schedule: `executor="pallas"` (the `stencil_tb` kernel, interpret mode
off-TPU) or `executor="jnp"` (`_jnp_time_tile`, the same per-window
trapezoid in pure jnp — also the oracle the sharded layer reuses).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sources as src_mod
from repro.core.temporal_blocking import TBPassGeom, TBPlan
from repro.kernels import stencil_tb as ker
from repro.kernels import tb_physics as phys
from repro.telemetry import spans as _spans


def _pad_xy(a: jnp.ndarray, h: int, mode: str) -> jnp.ndarray:
    return jnp.pad(a, ((h, h), (h, h), (0, 0)), mode=mode)


def _dummy_tables(ntiles: int, T: int):
    coords = jnp.zeros((ntiles, 1, 3), jnp.int32)
    vals = jnp.zeros((ntiles, T, 1), jnp.float32)
    return coords, vals


def build_tables(spec: ker.TBKernelSpec,
                 g: Optional[src_mod.GriddedSources],
                 receivers: Optional[src_mod.GriddedReceivers],
                 params: Dict[str, jnp.ndarray],
                 physics: phys.TBPhysics = phys.ACOUSTIC,
                 src_cap: Optional[int] = None,
                 rec_cap: Optional[int] = None):
    """Host-side precompute of the per-tile tables (paper §II.A, TPU layout).

    `params` maps physics.param_fields names to the (unpadded) model arrays;
    the physics supplies the per-point injection factor (dt^2/m for
    acoustic/TTI, dt for the elastic explosive source).

    The table builders are geometry adapters over the unified
    `core/tables.py` binning core, so they're kernel-agnostic: whatever
    interpolation footprint produced `g`/`receivers` (trilinear or wider
    Kaiser-sinc), entries bin the same way.  `src_cap`/`rec_cap` bound
    entries per tile (e.g. the survey engine's bucket-derived worst
    cases); None auto-sizes, a too-small cap raises the unified overflow
    error naming the tile, the supplied cap, and the required cap.

    Returns (src_tab | None, rec_tab | None).
    """
    shape = (spec.nx, spec.ny, spec.nz)
    src_tab = rec_tab = None
    if g is not None:
        scale = np.asarray(physics.inject_scale(params, g, spec.dt),
                           np.float32)
        src_tab = src_mod.tile_source_tables(g, shape, spec.tile, spec.halo,
                                             scale=scale, cap=src_cap,
                                             include_halo=spec.T > 1)
    if receivers is not None:
        rec_tab = src_mod.tile_receiver_tables(receivers, shape, spec.tile,
                                               spec.halo, cap=rec_cap)
    return src_tab, rec_tab


def slot_fill(spec: ker.TBKernelSpec, nt: int, tables, rtables
              ) -> Dict[str, list]:
    """Span attributes for the sparse terms' slot fill.  `tables` is the
    (src_tab, rec_tab) pair of the depth-`spec.T` tiles, serving
    `(nt // T)·T` steps; `rtables` the remainder tile's, serving
    `nt % T`.  Per pair: live entries (the tables' host-side `nnz`), the
    `n_tiles x cap` slots the kernel computes every step, and the steps.
    A missing table stands for the one-slot dummy the kernel computes
    over in its place."""
    ntx, nty = spec.ntiles
    rem = nt % spec.T
    out = {k: [] for k in ("src_live", "src_slots", "rec_live",
                           "rec_slots", "steps")}
    for pair, steps in ((tables, nt - rem), (rtables, rem)):
        if steps == 0:
            continue
        for kind, tab in zip(("src", "rec"), pair):
            live = 0 if tab is None else int(np.sum(tab.nnz))
            cap = 1 if tab is None else tab.coords.shape[1]
            out[kind + "_live"].append(live)
            out[kind + "_slots"].append(ntx * nty * cap)
        out["steps"].append(steps)
    return out


def update_counts(spec: ker.TBKernelSpec, nt: int) -> Dict[str, list]:
    """Span attributes for the kernel's update redundancy, for the depth-T
    tiles and the `nt % T` remainder tile (when there is one):
    `update_points`, the points at which the kernel's updates produce a
    value over all tiles and steps (`stencil_tb.update_points`), and
    `useful_points`, the grid's points times the steps served."""
    rem = nt % spec.T
    out = {"update_points": [], "useful_points": []}
    for T, steps in ((spec.T, nt - rem), (rem, rem)):
        if steps == 0:
            continue
        calls = steps // T
        out["update_points"].append(
            calls * ker.update_points(dataclasses.replace(spec, T=T)))
        out["useful_points"].append(spec.nx * spec.ny * spec.nz * steps)
    return out


def _src_vals_for_tile(src_dcmp: jnp.ndarray, src_tab, t0, T: int):
    """(ntiles, T, cap) injection values for time tile starting at t0.

    `src_dcmp` is the (nt, npts) decomposed-wavelet table
    (`GriddedSources.src_dcmp`) — passed as a bare array so the whole
    call stays a traced pytree function (mappable over a shot axis)."""
    npts = src_dcmp.shape[1]
    vals = jax.lax.dynamic_slice(src_dcmp, (t0, 0), (T, npts))  # (T, npts)
    safe_sid = jnp.maximum(src_tab.sid, 0)                 # (ntiles, cap)
    sv = vals[:, safe_sid]                                 # (T, ntiles, cap)
    sv = jnp.transpose(sv, (1, 0, 2)) * src_tab.scale[:, None, :]
    return sv


def combine_rec_partials(rec_part: jnp.ndarray, rec_tab, nrec: int):
    """(ntx, nty, T, capr, nchan) partials -> (T, nrec, nchan) samples
    (segment sum over receiver ids; paper Fig. 3b gather).

    Shared by the single-device tile driver below and the sharded execution
    layer (`distributed/halo.py`), whose per-shard partials have the same
    (tiles..., T, cap, chan) layout — one tile per shard."""
    ntx, nty, T, capr, nchan = rec_part.shape
    ids = jnp.where(rec_tab.rid < 0, nrec, rec_tab.rid).reshape(-1)
    vals = rec_part.reshape(ntx * nty, T, capr, nchan)
    vals = jnp.transpose(vals, (0, 2, 1, 3)).reshape(-1, T, nchan)
    seg = jax.ops.segment_sum(vals, ids, num_segments=nrec + 1)
    return jnp.transpose(seg[:nrec], (1, 0, 2))            # (T, nrec, nchan)


def _jnp_window_tile(physics: phys.TBPhysics, sspec, T: int, h: int,
                     state_pads, param_pads, dom, s_coords, s_vals,
                     r_coords, r_w):
    """T in-window timesteps on one halo-padded window — the jnp oracle of
    the Pallas kernel's unrolled loop (`stencil_tb._tb_kernel`), sharing the
    same `physics.update` / mask / inject / record sequence.  `sspec` is
    anything exposing `dt`/`spacing`/`order` (a `TBKernelSpec` here, the
    sharded layer's `_StepSpec` in `distributed/halo.py`).

    Returns (cropped centre tuple, rec partials (T, capr, rec_channels)).
    """
    state = dict(zip(physics.state_fields, state_pads))
    params = dict(zip(physics.param_fields, param_pads))
    mask_fn = lambda a: a * dom  # noqa: E731
    sx, sy, sz = s_coords[:, 0], s_coords[:, 1], s_coords[:, 2]
    rx, ry, rz = r_coords[:, 0], r_coords[:, 1], r_coords[:, 2]
    recs = []
    for k in range(T):
        new = physics.update(state, params, sspec, mask_fn)
        for f in physics.evolved_fields:
            if f not in physics.premasked_fields:
                new[f] = new[f] * dom
        # fused grid-aligned injection (paper Listing 4); padding slots
        # carry val = 0 and scatter harmlessly onto window point (0, 0, 0)
        for f in physics.inject_fields:
            new[f] = new[f].at[sx, sy, sz].add(s_vals[k].astype(new[f].dtype))
        # per-step receiver partials (paper Fig. 3b gather, local entries)
        recs.append(jnp.stack(
            [(arr[rx, ry, rz] * r_w).astype(arr.dtype)
             for arr in physics.record(new)], axis=-1))
        state = new
    wx, wy = state_pads[0].shape[0], state_pads[0].shape[1]
    crop = (slice(h, wx - h), slice(h, wy - h), slice(None))
    return (tuple(state[f][crop] for f in physics.state_fields),
            jnp.stack(recs, axis=0))


def _jnp_time_tile(spec: ker.TBKernelSpec, physics: phys.TBPhysics,
                   state_pads, param_pads, s_coords, s_vals, r_coords, r_w):
    """jnp oracle of `stencil_tb.tb_time_tile`: the identical per-window
    trapezoid (window DMA -> T masked steps -> centre crop) looped in pure
    jnp, one window per (ti, tj) tile.  Same signature contract; returns
    (state tuple (nx, ny, nz), rec partials (ntx, nty, T, capr, chan))."""
    h = spec.halo
    tx, ty = spec.tile
    ntx, nty = spec.ntiles
    dom_pad = jnp.pad(jnp.ones((spec.nx, spec.ny, spec.nz), spec.dtype),
                      ((h, h), (h, h), (0, 0)))
    outs = [jnp.zeros((spec.nx, spec.ny, spec.nz), p.dtype)
            for p in state_pads]
    rec_rows = []
    for ti in range(ntx):
        row = []
        for tj in range(nty):
            k = ti * nty + tj
            slx = slice(ti * tx, ti * tx + tx + 2 * h)
            sly = slice(tj * ty, tj * ty + ty + 2 * h)
            wpads = tuple(p[slx, sly] for p in state_pads)
            wpar = tuple(p[slx, sly] for p in param_pads)
            out_w, rec = _jnp_window_tile(
                physics, spec, spec.T, h, wpads, wpar, dom_pad[slx, sly],
                s_coords[k], s_vals[k], r_coords[k], r_w[k])
            for i, centre in enumerate(out_w):
                outs[i] = outs[i].at[ti * tx:(ti + 1) * tx,
                                     tj * ty:(tj + 1) * ty, :].set(centre)
            row.append(rec)
        rec_rows.append(jnp.stack(row, axis=0))
    return tuple(outs), jnp.stack(rec_rows, axis=0)


def _run_time_tile(spec: ker.TBKernelSpec, physics: phys.TBPhysics,
                   state, param_pads, src_dcmp, src_tab, rec_tab, t0,
                   nrec: int, interpret: bool, executor: str = "pallas"):
    # `annotate` is a no-op unless --telemetry enabled a collector; under
    # tracing it names this region in the jaxpr/HLO and the recorded span
    # measures TRACE time (the compile-vs-execute split of DESIGN.md §7)
    with _spans.annotate("ops.tile_pass", T=spec.T, tile=spec.tile,
                         executor=executor):
        return _run_time_tile_impl(spec, physics, state, param_pads,
                                   src_dcmp, src_tab, rec_tab, t0, nrec,
                                   interpret, executor)


def _run_time_tile_impl(spec, physics, state, param_pads, src_dcmp,
                        src_tab, rec_tab, t0, nrec, interpret, executor):
    h = spec.halo
    ntx, nty = spec.ntiles
    ntiles = ntx * nty
    # fixed scope names for the device trace, entered whether or not
    # telemetry is on (unlike `annotate`'s)
    if src_tab is not None:
        s_coords = src_tab.coords
        with jax.named_scope("ops.src_vals"):
            s_vals = _src_vals_for_tile(src_dcmp, src_tab, t0, spec.T)
    else:
        s_coords, s_vals = _dummy_tables(ntiles, spec.T)
    s_vals = s_vals.astype(spec.dtype)
    if rec_tab is not None:
        r_coords, r_w = rec_tab.coords, rec_tab.weight
    else:
        r_coords = jnp.zeros((ntiles, 1, 3), jnp.int32)
        r_w = jnp.zeros((ntiles, 1), jnp.float32)
    r_w = r_w.astype(spec.dtype)

    with jax.named_scope("ops.state_pad"):
        state_pads = tuple(_pad_xy(f, h, "constant") for f in state)
    if executor == "pallas":
        new_state, rec_part = ker.tb_time_tile(
            spec, physics, state_pads, param_pads, s_coords, s_vals,
            r_coords, r_w, interpret=interpret)
    elif executor == "jnp":
        new_state, rec_part = _jnp_time_tile(
            spec, physics, state_pads, param_pads, s_coords, s_vals,
            r_coords, r_w)
    else:
        raise ValueError(f"unknown executor {executor!r}")
    if rec_tab is not None:
        with jax.named_scope("ops.rec_combine"):
            rec = combine_rec_partials(rec_part, rec_tab, nrec)
    else:
        rec = jnp.zeros((spec.T, 0, physics.rec_channels), spec.dtype)
    return new_state, rec


def make_spec(shape: Tuple[int, int, int], plan: TBPlan, order: int,
              dt: float, spacing: Tuple[float, float, float],
              src_cap: int, rec_cap: int, dtype=jnp.float32,
              physics: phys.TBPhysics = phys.ACOUSTIC) -> ker.TBKernelSpec:
    return ker.TBKernelSpec(
        nx=shape[0], ny=shape[1], nz=shape[2], tile=plan.tile, T=plan.T,
        order=order, dt=float(dt), spacing=tuple(float(s) for s in spacing),
        src_cap=src_cap, rec_cap=rec_cap, dtype=dtype,
        step_radius=physics.step_radius(order),
        rec_channels=physics.rec_channels)


def make_inner_spec(block: Tuple[int, int], nz: int,
                    inner_tile: Tuple[int, int], T: int, order: int,
                    dt: float, spacing: Tuple[float, float, float],
                    src_cap: int, rec_cap: int, dtype,
                    physics: phys.TBPhysics) -> ker.TBKernelSpec:
    """Kernel spec for the INNER trapezoid of one shard (DESIGN.md §4).

    The shard's (bx, by) block plays the role of the kernel's grid and the
    shard's exchanged deep halo plays the role of its zero padding; the
    kernel's own spatial grid is `block / inner_tile` tiles, each DMA'ing
    an `inner_tile + 2*T*r_step` window out of the exchanged block (at
    `(ti*tx, tj*ty)` plus the operand's origin in the shard's frame,
    `tb_time_tile(origins=...)`)."""
    bx, by = block
    tx, ty = inner_tile
    if bx % tx or by % ty:
        raise ValueError(f"inner tile {inner_tile} must divide the shard "
                         f"block {block}")
    return ker.TBKernelSpec(
        nx=bx, ny=by, nz=nz, tile=(tx, ty), T=T, order=order, dt=float(dt),
        spacing=tuple(float(s) for s in spacing), src_cap=src_cap,
        rec_cap=rec_cap, dtype=dtype, step_radius=physics.step_radius(order),
        rec_channels=physics.rec_channels)


def pass_inner_spec(geom: TBPassGeom, nz: int, order: int, dt: float,
                    spacing: Tuple[float, float, float], src_cap: int,
                    rec_cap: int, dtype,
                    physics: phys.TBPhysics) -> ker.TBKernelSpec:
    """Kernel spec for ONE pass of the time-nested inner schedule
    (DESIGN.md §4): the pass's kernel grid is the shard block plus the
    halo depth still valid AFTER the pass (`geom.d_out`, rounded up to the
    inner tile), its halo is the per-pass consumption `geom.T * r_step`,
    and the window DMA slices at the pass-local `(ti*tx, tj*ty)` origin
    (plus the operand's origin in its frame) — so the same `tb_time_tile`
    call advances a window that shrinks pass by pass, with the VMEM window
    sized by the INNER depth regardless of the exchange depth."""
    return make_inner_spec(geom.grid, nz, geom.tile, geom.T, order, dt,
                           spacing, src_cap, rec_cap, dtype, physics)


def tb_propagate_prepared(physics: phys.TBPhysics, nt: int,
                          spec: ker.TBKernelSpec,
                          rspec: Optional[ker.TBKernelSpec],
                          state: Tuple[jnp.ndarray, ...],
                          param_pads, rparam_pads,
                          src_dcmp: jnp.ndarray, src_tab, rec_tab,
                          rsrc_tab, rrec_tab, nrec: int,
                          interpret: Optional[bool] = None,
                          executor: str = "pallas"):
    """The traced core of `_tb_propagate`: scan over depth-T time tiles
    plus the shallower `nt % T` remainder tile, AFTER all host-side table
    binning.

    Every non-static argument is a jnp pytree — state tuple, padded
    params, the (nt, npts) `src_dcmp` wavelet table and the
    `TileSourceTable`/`TileReceiverTable` NamedTuples — so this function
    jits cleanly and, crucially, maps over a stacked shot axis: the
    survey engine (`survey/engine.py`) batches whole shot buckets
    through one trace of this function (`jax.vmap` with the jnp
    executor; the Pallas kernel takes no batch axis, so with it each
    shot runs in turn under `jax.lax.map`).  `spec`/`rspec` (None when
    `nt % spec.T == 0`), `nrec`, `interpret` and `executor`
    ("pallas" | "jnp") are static.

    Returns (final state tuple, recs (nt, nrec, rec_channels)); recs are
    all-zero shaped (nt, 0, chan) when no receiver tables were bound.
    """
    n_main = nt // spec.T
    rem = nt - n_main * spec.T
    if (rem > 0) != (rspec is not None):
        raise ValueError(f"nt={nt} with T={spec.T} needs "
                         f"{'a' if rem else 'no'} remainder spec")

    def tile_body(carry, tile_idx):
        t0 = tile_idx * spec.T
        new, rec = _run_time_tile(spec, physics, carry, param_pads,
                                  src_dcmp, src_tab, rec_tab, t0, nrec,
                                  interpret, executor)
        return new, rec

    carry = tuple(state)
    recs_main = None
    if n_main > 0:
        carry, recs_main = jax.lax.scan(tile_body, carry,
                                        jnp.arange(n_main))
        recs_main = recs_main.reshape(n_main * spec.T, -1,
                                      physics.rec_channels)

    if rem > 0:
        carry, rec_rem = _run_time_tile(
            rspec, physics, carry, rparam_pads, src_dcmp, rsrc_tab,
            rrec_tab, jnp.asarray(n_main * spec.T), nrec, interpret,
            executor)
        recs = (jnp.concatenate([recs_main, rec_rem], axis=0)
                if recs_main is not None else rec_rem)
    else:
        recs = recs_main
    return carry, recs


def _device_tables(tab):
    """A table as the jitted propagate takes it: its device arrays, without
    the host-side `nnz` the kernel never reads (which would otherwise be
    copied to the device on every call)."""
    return None if tab is None else tab._replace(nnz=None)


def _prepare(physics: phys.TBPhysics, nt: int,
             state: Tuple[jnp.ndarray, ...],
             params: Dict[str, jnp.ndarray],
             g: Optional[src_mod.GriddedSources],
             receivers: Optional[src_mod.GriddedReceivers],
             plan: TBPlan, order: int, dt,
             spacing: Tuple[float, float, float]):
    """The host half of `_tb_propagate`: bins the per-tile tables (span
    `ops.tables`, with the slot-fill and update counters) and sizes the
    kernel specs' caps from them.  Returns the static arguments
    (physics, nt, spec, rspec, nrec) and the array arguments (state,
    params, src_dcmp, tables) of `_tb_propagate_jit`."""
    shape = state[0].shape
    dtype = state[0].dtype
    dt = float(dt)
    if g is not None and g.nt < nt:
        raise ValueError(f"source wavelets cover {g.nt} steps < nt={nt}")

    def specced(src_cap, rec_cap, T=plan.T):
        p = dataclasses.replace(plan, T=T)
        return make_spec(shape, p, order, dt, spacing, src_cap, rec_cap,
                         dtype=dtype, physics=physics)

    # tables depend only on tile/halo/dt (not the caps), so build them once
    # and size the spec's static caps from what came back
    with _spans.span("ops.tables", physics=physics.name, nt=nt,
                     T=plan.T) as sp:
        spec = specced(1, 1)
        src_tab, rec_tab = build_tables(spec, g, receivers, params, physics)
        src_cap = src_tab.cap if src_tab is not None else 1
        rec_cap = rec_tab.coords.shape[1] if rec_tab is not None else 1
        spec = specced(src_cap, rec_cap)

        nrec = receivers.num if receivers is not None else 0
        src_dcmp = (g.src_dcmp if g is not None
                    else jnp.zeros((max(nt, 1), 1), dtype))

        rem = nt % spec.T
        rspec = rsrc_tab = rrec_tab = None
        if rem > 0:
            # remainder tables must be rebuilt: halo depth changes with T
            # (and with it the entries per tile, so caps as well)
            rsrc_tab, rrec_tab = build_tables(specced(1, 1, T=rem), g,
                                              receivers, params, physics)
            rspec = specced(
                rsrc_tab.cap if rsrc_tab is not None else 1,
                rrec_tab.coords.shape[1] if rrec_tab is not None else 1,
                T=rem)
        if _spans.active():
            sp.set(**slot_fill(spec, nt, (src_tab, rec_tab),
                               (rsrc_tab, rrec_tab)),
                   **update_counts(spec, nt))
    tables = tuple(map(_device_tables, (src_tab, rec_tab, rsrc_tab,
                                        rrec_tab)))
    return ((physics, nt, spec, rspec, nrec),
            (tuple(state), tuple(params[f] for f in physics.param_fields),
             src_dcmp, tables))


# `traced` is set by `_tb_propagate_traced`'s Python body, which runs only
# while the jit traces it, in the calling thread: never on a call its cache
# serves
_tracing = threading.local()


def _tb_propagate_traced(physics, nt, spec, rspec, nrec, interpret,
                         executor, state, params, src_dcmp, tables):
    _tracing.traced = True
    param_pads = tuple(_pad_xy(p, spec.halo, "edge") for p in params)
    rparam_pads = (tuple(_pad_xy(p, rspec.halo, "edge") for p in params)
                   if rspec is not None else None)
    return tb_propagate_prepared(physics, nt, spec, rspec, state, param_pads,
                                 rparam_pads, src_dcmp, *tables, nrec,
                                 interpret=interpret, executor=executor)


# the one-chip propagate, compiled once per plan (the specs), nt, nrec,
# executor and the shapes and dtypes of its arrays; no argument is donated
# (callers reuse their state arrays)
_tb_propagate_jit = jax.jit(_tb_propagate_traced,
                            static_argnums=(0, 1, 2, 3, 4, 5, 6))


def _tb_propagate(physics: phys.TBPhysics, nt: int,
                  state: Tuple[jnp.ndarray, ...],
                  params: Dict[str, jnp.ndarray],
                  g: Optional[src_mod.GriddedSources],
                  receivers: Optional[src_mod.GriddedReceivers],
                  plan: TBPlan, order: int, dt,
                  spacing: Tuple[float, float, float],
                  interpret: Optional[bool] = None, executor: str = "pallas"):
    """Propagate nt timesteps of `physics` with the temporally-blocked kernel.

    Semantics identical to the reference propagator in `core/propagators/`
    (tested): trapezoidal time tiles of depth plan.T, remainder tile of
    depth nt % T.  `state` is ordered as physics.state_fields; `params`
    maps physics.param_fields to (nx, ny, nz) arrays.

    The host-side table binning (`_prepare`) runs eagerly on every call;
    the traced half — the param pads and `tb_propagate_prepared` — is one
    jitted program, traced, lowered and compiled once per plan, nt,
    executor and array shapes and dtypes, so a warm call goes straight to
    the compiled program.  With the default `executor="pallas"` each time
    tile is one `pallas_call`; `executor="jnp"` runs the identical window
    schedule in pure jnp.

    Returns (final state tuple, rec (nt, nrec, rec_channels) | None).
    """
    static, args = _prepare(physics, nt, state, params, g, receivers, plan,
                            order, dt, spacing)
    spec = static[2]
    with _spans.span("ops.propagate", physics=physics.name, nt=nt,
                     T=spec.T, executor=executor) as sp:
        # `traced`: this call traced (and lowered, compiled or loaded) the
        # jitted program; a call its cache serves only enqueues it
        with _spans.span("ops.dispatch", count_compiles=True) as dsp:
            _tracing.traced = False
            carry, recs = _tb_propagate_jit(*static, interpret, executor,
                                            *args)
            dsp.set(traced=_tracing.traced)
        sp.sync((carry, recs))
    if receivers is None:
        recs = None
    return carry, recs


# ---------------------------------------------------------------------------
# Physics entry points
# ---------------------------------------------------------------------------

def acoustic_tb_propagate(nt: int, u0, u1, m, damp,
                          g: Optional[src_mod.GriddedSources],
                          receivers: Optional[src_mod.GriddedReceivers],
                          plan: TBPlan, order: int, dt,
                          spacing: Tuple[float, float, float],
                          interpret: Optional[bool] = None,
                          executor: str = "pallas"):
    """Acoustic TB propagation.  Returns ((u_prev, u), rec (nt, nrec) | None).

    Semantics identical to `kernels.ref.acoustic_reference` (tested)."""
    (u0n, u1n), recs = _tb_propagate(
        phys.ACOUSTIC, nt, (u0, u1), {"m": m, "damp": damp}, g, receivers,
        plan, order, dt, spacing, interpret=interpret, executor=executor)
    if recs is not None:
        recs = recs[..., 0]
    return (u0n, u1n), recs


def tti_tb_propagate(nt: int, state, params, g, receivers,
                     plan: TBPlan, order: int, dt,
                     spacing: Tuple[float, float, float],
                     interpret: Optional[bool] = None,
                     executor: str = "pallas"):
    """TTI TB propagation.

    `state` is a `propagators.tti.TTIState`; `params` a `TTIParams`.
    Returns (TTIState, rec (nt, nrec) | None) matching
    `kernels.ref.tti_reference` (tested)."""
    from repro.core.propagators import tti as tt
    st_tuple = tuple(getattr(state, f) for f in phys.TTI.state_fields)
    pdict = {f: getattr(params, f) for f in phys.TTI.param_fields}
    final, recs = _tb_propagate(phys.TTI, nt, st_tuple, pdict, g, receivers,
                                plan, order, dt, spacing, interpret=interpret,
                                executor=executor)
    if recs is not None:
        recs = recs[..., 0]
    return tt.TTIState(**dict(zip(phys.TTI.state_fields, final))), recs


def elastic_tb_propagate(nt: int, state, params, g, receivers,
                         plan: TBPlan, order: int, dt,
                         spacing: Tuple[float, float, float],
                         interpret: Optional[bool] = None,
                         executor: str = "pallas"):
    """Elastic TB propagation.

    `state` is a `propagators.elastic.ElasticState`; `params` an
    `ElasticParams`.  Returns (ElasticState, rec (nt, nrec, 2) | None) —
    channels are (vz, pressure proxy), matching
    `kernels.ref.elastic_reference` (tested)."""
    from repro.core.propagators import elastic as el
    st_tuple = tuple(getattr(state, f) for f in phys.ELASTIC.state_fields)
    pdict = {f: getattr(params, f) for f in phys.ELASTIC.param_fields}
    final, recs = _tb_propagate(phys.ELASTIC, nt, st_tuple, pdict, g,
                                receivers, plan, order, dt, spacing,
                                interpret=interpret, executor=executor)
    return el.ElasticState(**dict(zip(phys.ELASTIC.state_fields, final))), \
        recs


def acoustic_sb_propagate(nt: int, u0, u1, m, damp, g, receivers,
                          tile: Tuple[int, int], order: int, dt,
                          spacing, interpret: Optional[bool] = None):
    """The paper's baseline: spatially-blocked only (T = 1)."""
    plan = TBPlan(tile=tile, T=1, radius=order // 2)
    return acoustic_tb_propagate(nt, u0, u1, m, damp, g, receivers, plan,
                                 order, dt, spacing, interpret=interpret)
