"""Sharded multi-physics temporally-blocked execution layer (DESIGN.md §4).

The paper's enabling transformation (grid-aligned sources) composes directly
with distribution: after alignment, injection is a *local* operation on
whichever shard owns (or halos) the affected points, so a time tile of depth
T needs exactly ONE neighbor exchange of depth H = T*r_step — temporal
blocking applied to communication.  Redundant rim compute on each device
buys a T-fold reduction in exchange count, the multi-chip analogue of the
VMEM trapezoid in `kernels/stencil_tb.py`; the two trapezoids nest as ONE
hierarchical plan (`DistTBPlan` carrying an inner `core.TBPlan`, searched
jointly by `core.temporal_blocking.plan_hierarchy`):

    outer trapezoid   shard block + deep exchanged halo, advanced T steps
                      between `lax.ppermute` rounds (this module).  The
                      exchange is PER-FIELD deep: fields the update only
                      reads pointwise at the rim (u_prev/p_prev/r_prev,
                      the elastic velocities) ship a provably shallower
                      strip (`TBPhysics.field_halo_depths`), zero-padded
                      back to the uniform window — fewer exchange bytes
                      with bit-identical valid centres.
    inner trapezoid   the per-shard schedule over the exchanged block,
                      spatially tiled by `inner_plan.tile`: either the
                      Pallas TB kernel (`stencil_tb.tb_time_tile`,
                      `inner="pallas"`, one kernel grid of block/tile
                      windows per tile — the shard's `dom_pad` and tile
                      offsets compose inside the kernel's window DMA) or
                      its jnp oracle (`inner="jnp"`), which loops the SAME
                      per-window schedule in pure jnp.

The two TIME depths are decoupled (time-nesting, DESIGN.md §4): the inner
`TBPlan.T` may be any depth up to the outer exchange depth `T`, in which
case `ceil(T / inner.T)` inner passes consume ONE deep exchange, each pass
advancing the block plus the still-remaining halo (windows shrink by
`inner.T * r_step` per pass — `core.temporal_blocking.nested_pass_geometry`)
— so a very deep, latency-amortizing exchange no longer drags the kernel's
VMEM window up with it.  Each pass gets its own source/receiver binning
(tile origins shift with the remaining depth); the pass grid is rounded up
to the inner tile with a zero-padded garbage band the trapezoid crops.
`inner.T == T` is the flat single-pass schedule.

With `overlap=True` the deep exchange is double-buffered against compute:
the first in-tile step splits into an interior update of the un-exchanged
local block (data-independent of the ppermute, so XLA's latency-hiding
scheduler can run the exchange underneath it) plus four rim strips of
width `H + 2*r_step` recomputed once the halo lands; steps 2..T then run
through the inner executor on the stitched state at depth `H - r_step`.
The strips are the overlap's price — `plan_hierarchy` decides when paying
it beats serializing the exchange.

Everything physics-specific comes from the *same* `tb_physics.TBPhysics`
step specs that `kernels/ops._tb_propagate` uses, so one driver advances
acoustic (2 state fields), TTI (4) and elastic (9) — there is no
per-physics distributed stencil loop to keep in sync.

Source/receiver handling is the paper's §II machinery sharded by owner,
bound at the INNER tile granularity with one binning PER PASS
(`_pass_source_tables` / `_pass_receiver_tables` — the pass grids are
per-shard and overlap across shards, so they bin directly into the
(px, py, tiles, cap, ...) layout): every affected point is duplicated
into any window that contains it (paper Fig. 4b) and every receiver
gather entry lands once, in the owning shard's owning tile; each shard
records *partial* per-step receiver samples which the driver segment-sums
by receiver id (`ops.combine_rec_partials`) — so receiver traces are
per-step at any T, and `nt % T != 0` runs a shallower remainder tile
exactly like the single-device driver, nested passes included.

Mesh layout: grid x -> "data" axis, grid y -> "model" axis.  Exchanges are
`lax.ppermute` shifts; missing neighbors (domain boundary) produce zeros =
the Dirichlet convention shared by the reference and the Pallas kernel, and
out-of-domain cells are re-masked every in-block step (param fields carry
their physics' `param_fills` there so updates stay finite).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import numpy as np

from repro.core import sources as src_mod
from repro.core import tables as tables_mod
from repro.core.temporal_blocking import (HierPlan, TBPassGeom, TBPlan,
                                          nested_pass_geometry)
from repro.kernels import ops as ops_mod
from repro.kernels import tb_physics as phys
from repro.telemetry import spans as _spans


def _shift_from_low(x, h: int, axis_name: str, dim: int):
    """Every device sends its LAST h slices to the next device (axis order);
    device 0's halo comes back as zeros (Dirichlet)."""
    n = jax.lax.axis_size(axis_name)
    sl = [slice(None)] * x.ndim
    sl[dim] = slice(x.shape[dim] - h, None)
    piece = x[tuple(sl)]
    if n == 1:
        return jnp.zeros_like(piece)
    return jax.lax.ppermute(piece, axis_name,
                            perm=[(i, i + 1) for i in range(n - 1)])


def _shift_from_high(x, h: int, axis_name: str, dim: int):
    n = jax.lax.axis_size(axis_name)
    sl = [slice(None)] * x.ndim
    sl[dim] = slice(0, h)
    piece = x[tuple(sl)]
    if n == 1:
        return jnp.zeros_like(piece)
    return jax.lax.ppermute(piece, axis_name,
                            perm=[(i + 1, i) for i in range(n)
                                  if i + 1 <= n - 1])


def halo_exchange(x, h: int, axis_name: str, dim: int, shift_fns=None):
    """Pad the local block with depth-h halos from both neighbors.

    `shift_fns` (default: the ppermute pair above) injects the two
    neighbor-strip providers `(from_low, from_high)` — tests and oracles
    substitute collective-free simulators so the concat/zero-band algebra
    is exercised with real neighbor data on one device."""
    from_low, from_high = shift_fns or (_shift_from_low, _shift_from_high)
    lo = from_low(x, h, axis_name, dim)
    hi = from_high(x, h, axis_name, dim)
    return jnp.concatenate([lo, x, hi], axis=dim)


def halo_exchange_2d(x, h: int, ax_x: str, ax_y: str, shift_fns=None):
    """x then y (the second exchange carries the x-halo -> corners filled)."""
    x = halo_exchange(x, h, ax_x, 0, shift_fns=shift_fns)
    return halo_exchange(x, h, ax_y, 1, shift_fns=shift_fns)


def exchange_to_depth(x, depth: int, h: int, ax_x: str, ax_y: str,
                      shift_fns=None):
    """Exchange a depth-`depth` halo, then zero-pad out to the uniform
    window depth `h` — the per-field deep exchange (DESIGN.md §4).  Cells
    in the zero band are only ever read into values the trapezoid discards
    (`TBPhysics.halo_lags` is derived from exactly that dependency cone);
    `depth == 0` skips the ppermute rounds entirely."""
    if depth > 0:
        x = halo_exchange_2d(x, depth, ax_x, ax_y, shift_fns=shift_fns)
    if h > depth:
        pad = h - depth
        x = jnp.pad(x, ((pad, pad), (pad, pad), (0, 0)))
    return x


class _StepSpec(NamedTuple):
    """The slice of `TBKernelSpec` a `TBPhysics.update` actually reads."""

    dt: float
    spacing: Tuple[float, float, float]
    order: int


class DistTBPlan(NamedTuple):
    """Static setup for the sharded temporally-blocked propagator.

    `inner_plan` is the inner level of the two-level hierarchy: its tile
    spatially tiles the shard block inside the per-shard schedule (both
    executors), and its T is the INNER time depth — any depth up to the
    outer exchange depth `T`.  When `inner_plan.T < T`, the executor runs
    `ceil(T / inner_plan.T)` inner passes per deep exchange, each
    consuming `inner_plan.T * r_step` of the remaining halo so the
    advanced window shrinks pass by pass (time-nesting); the VMEM window
    is sized by the inner depth while the exchange amortizes at `T`.
    `None` means one flat pass with one tile covering the block.  Build
    from the joint autotuner with `dist_plan_from_hier`.
    """

    mesh: Mesh
    grid_shape: Tuple[int, int, int]
    physics: phys.TBPhysics = phys.ACOUSTIC
    order: int = 4
    T: int = 2
    dt: float = 1e-3
    spacing: Tuple[float, float, float] = (10.0, 10.0, 10.0)
    ax_x: str = "data"
    ax_y: str = "model"
    inner: str = "jnp"          # per-shard executor: "jnp" | "pallas"
    inner_plan: Optional[TBPlan] = None
    overlap: bool = False       # overlapped (split-first-step) exchange
    per_field_halo: bool = True  # per-field exchange depths (halo_lags)

    @property
    def r_step(self) -> int:
        """Per-timestep halo consumption (order//2 acoustic, order TTI/el)."""
        return self.physics.step_radius(self.order)

    @property
    def halo(self) -> int:
        return self.T * self.r_step

    @property
    def pgrid(self) -> Tuple[int, int]:
        return (self.mesh.shape[self.ax_x], self.mesh.shape[self.ax_y])

    @property
    def block(self) -> Tuple[int, int]:
        """Per-shard local block (bx, by)."""
        px, py = self.pgrid
        return (self.grid_shape[0] // px, self.grid_shape[1] // py)

    @property
    def inner_tile(self) -> Tuple[int, int]:
        """Spatial tile of the inner trapezoid (the whole block if no
        inner plan was set)."""
        return self.inner_plan.tile if self.inner_plan is not None \
            else self.block

    @property
    def inner_T(self) -> int:
        """Inner (per-pass) time depth; equals the exchange depth `T`
        for the flat single-pass schedule."""
        return self.inner_plan.T if self.inner_plan is not None else self.T

    def field_depths(self, T_depth: int) -> Tuple[int, ...]:
        """Per-state-field exchange depth for a depth-`T_depth` tile."""
        if not self.per_field_halo:
            h = T_depth * self.r_step
            return (h,) * len(self.physics.state_fields)
        return self.physics.field_halo_depths(T_depth, self.order)

    def validate(self):
        nx, ny, _ = self.grid_shape
        px, py = self.pgrid
        if nx % px or ny % py:
            raise ValueError(
                f"grid ({nx}, {ny}) must divide by the ({px}, {py}) mesh")
        bx, by = self.block
        if self.halo > min(bx, by):
            raise ValueError(
                f"halo depth T*r_step={self.halo} exceeds local block "
                f"({bx}, {by}); single-hop neighbor exchange requires "
                f"T*r_step <= block — lower T or use a coarser decomposition")
        if self.inner not in ("jnp", "pallas"):
            raise ValueError(f"unknown inner schedule {self.inner!r}")
        if self.inner_plan is not None:
            itx, ity = self.inner_plan.tile
            if bx % itx or by % ity:
                raise ValueError(
                    f"inner tile {self.inner_plan.tile} must divide the "
                    f"shard block ({bx}, {by})")
            if not 1 <= self.inner_plan.T <= self.T:
                raise ValueError(
                    f"inner plan depth T={self.inner_plan.T} must lie in "
                    f"[1, outer T={self.T}]: ceil(T / inner_T) inner "
                    f"passes consume one deep exchange (time-nested "
                    f"schedule)")


def dist_plan_from_hier(mesh: Mesh, grid_shape: Tuple[int, int, int],
                        physics: phys.TBPhysics, order: int,
                        hier: HierPlan, dt: float,
                        spacing: Tuple[float, float, float],
                        inner: str = "pallas", **kwargs) -> DistTBPlan:
    """Turn a jointly-autotuned `core.temporal_blocking.HierPlan` into the
    executable `DistTBPlan` (outer T and exchange overlap from the outer
    level, spatial tile from the inner level)."""
    return DistTBPlan(mesh=mesh, grid_shape=grid_shape, physics=physics,
                      order=order, T=hier.T, dt=dt, spacing=spacing,
                      inner=inner, inner_plan=hier.inner,
                      overlap=hier.overlap, **kwargs)


def _local_domain_mask(plan: DistTBPlan, h: int, shape_local, dtype):
    """1.0 inside the global domain for the depth-h halo-padded local block."""
    nx, ny, _ = plan.grid_shape
    px = jax.lax.axis_index(plan.ax_x)
    py = jax.lax.axis_index(plan.ax_y)
    bx = shape_local[0] - 2 * h
    by = shape_local[1] - 2 * h
    gx = px * bx - h + jax.lax.broadcasted_iota(jnp.int32, shape_local, 0)
    gy = py * by - h + jax.lax.broadcasted_iota(jnp.int32, shape_local, 1)
    ok = (gx >= 0) & (gx < nx) & (gy >= 0) & (gy < ny)
    return ok.astype(dtype)


# ---------------------------------------------------------------------------
# Per-shard inner trapezoids
# ---------------------------------------------------------------------------

# The jnp oracle of one halo-padded window (shared with the single-device
# driver — it moved to `kernels/ops` so the survey engine's jnp executor
# and this sharded layer run literally the same function).
_jnp_window_tile = ops_mod._jnp_window_tile


def _run_pass(plan: DistTBPlan, geom: TBPassGeom, state_pads, param_pads,
              dom_pad, h_full: int, s_coords, s_vals, r_coords, r_w,
              interpret: bool):
    """Advance ONE inner pass of the time-nested schedule (DESIGN.md §4).

    The incoming state is the shard block padded to the remaining halo
    depth `geom.d_in`; the pass advances `geom.T` steps over the region
    that stays valid afterwards (`block + 2*geom.d_out`, rounded up to the
    inner tile with a zero-padded garbage band the crop discards) and
    returns the state cropped to depth `geom.d_out` — the next pass's
    input, landing exactly on the block at the last pass.  `param_pads` /
    `dom_pad` stay at the full exchange depth `h_full` and are sliced to
    the pass window here (params' round-up band carries `param_fills` so
    updates stay finite in the garbage region).

    Tables are per pass-local tile: s_coords (ntiles, cap, 3) window-local,
    s_vals (ntiles, geom.T, cap), r_coords/r_w likewise.  Returns
    (state tuple at depth d_out, rec partials (ntiles, geom.T, capr, chan)).
    """
    physics = plan.physics
    bx, by = plan.block
    nz = state_pads[0].shape[2]
    tx, ty = geom.tile
    cx, cy = geom.grid
    hp = geom.halo
    keep = (bx + 2 * geom.d_out, by + 2 * geom.d_out)
    ex, ey = cx - keep[0], cy - keep[1]
    fills = dict(physics.param_fills)

    def fit(a, crop, fill):
        if crop:
            a = a[crop:a.shape[0] - crop, crop:a.shape[1] - crop]
        if ex or ey:
            a = jnp.pad(a, ((0, ex), (0, ey), (0, 0)),
                        constant_values=jnp.asarray(fill, a.dtype))
        return a

    crop_p = h_full - geom.d_in
    spads = tuple(fit(a, 0, 0.0) for a in state_pads)
    ppads = tuple(fit(a, crop_p, fills.get(f, 0.0))
                  for f, a in zip(physics.param_fields, param_pads))
    dom = fit(dom_pad, crop_p, 0.0)
    ntx, nty = geom.ntiles
    if plan.inner == "pallas":
        # One pallas_call whose grid tiles the pass window; the shard's
        # dom_pad rides along as one more HBM window and is sliced at the
        # same per-tile window origin as the fields (stencil_tb).
        from repro.kernels import stencil_tb as ker
        spec = ops_mod.pass_inner_spec(
            geom, nz, plan.order, float(plan.dt),
            tuple(float(s) for s in plan.spacing), s_coords.shape[1],
            r_coords.shape[1], spads[0].dtype, physics)
        new, rec = ker.tb_time_tile(
            spec, physics, spads, ppads, s_coords, s_vals,
            r_coords, r_w, dom_pad=dom, interpret=interpret)
    else:
        # jnp oracle: the SAME per-window schedule as the kernel grid,
        # looped in pure jnp (ntx*nty windows, each with its own halo)
        sspec = _StepSpec(float(plan.dt),
                          tuple(float(s) for s in plan.spacing), plan.order)
        outs = [jnp.zeros((cx, cy, nz), p.dtype) for p in spads]
        rec_rows = []
        for ti in range(ntx):
            row = []
            for tj in range(nty):
                k = ti * nty + tj
                slx = slice(ti * tx, ti * tx + tx + 2 * hp)
                sly = slice(tj * ty, tj * ty + ty + 2 * hp)
                wpads = tuple(p[slx, sly] for p in spads)
                wpar = tuple(p[slx, sly] for p in ppads)
                out_w, rec = _jnp_window_tile(
                    physics, sspec, geom.T, hp, wpads, wpar, dom[slx, sly],
                    s_coords[k], s_vals[k], r_coords[k], r_w[k])
                for i, centre in enumerate(out_w):
                    outs[i] = outs[i].at[ti * tx:(ti + 1) * tx,
                                         tj * ty:(tj + 1) * ty, :].set(centre)
                row.append(rec)
            rec_rows.append(jnp.stack(row, axis=0))
        new, rec = tuple(outs), jnp.stack(rec_rows, axis=0)
    new = tuple(a[:keep[0], :keep[1]] for a in new)
    rec = rec.reshape(ntx * nty, geom.T, rec.shape[-2], rec.shape[-1])
    return new, rec


def _split_first_step(plan: DistTBPlan, sspec: _StepSpec, h: int,
                      state_blocks, state_pads, param_pads, dom,
                      s_coords, s_vals0, r_coords, r_w):
    """The overlapped first step of a deep tile (DESIGN.md §4).

    The exchanged halo is only needed within `h + r_step` of the window
    edge at step 1, so the step splits into:

      interior   `physics.update` on the zero-padded LOCAL block — no data
                 dependency on the ppermute, so XLA can run the exchange
                 underneath it; valid at >= h + r_step from the window edge.
      rim strips four band updates of width `h + 2*r_step` sliced from the
                 exchanged window, each valid (after an r_step crop at cut
                 edges) over the rim the interior cannot cover.

    Stitching writes the strips over the interior result; the assembled
    state carries the standard trapezoid contract (garbage only within
    r_step of the window edge).  Injection and receiver partials then run
    exactly as in `_jnp_window_tile`'s k = 0, on SHARD-level tables.

    Returns (stitched padded state tuple, rec partials (1, capr, chan)).
    """
    physics = plan.physics
    r = plan.r_step
    sd = dict(zip(physics.state_fields, state_pads))
    pd = dict(zip(physics.param_fields, param_pads))
    wx, wy = state_pads[0].shape[0], state_pads[0].shape[1]
    bx = wx - 2 * h

    def upd(slx, sly):
        st_ = {f: a[slx, sly] for f, a in sd.items()}
        pr_ = {f: a[slx, sly] for f, a in pd.items()}
        dm = dom[slx, sly]
        return physics.update(st_, pr_, sspec, lambda a: a * dm)

    # interior: independent of the exchange (zero-padded local block)
    interior = {f: jnp.pad(b, ((h, h), (h, h), (0, 0)))
                for f, b in zip(physics.state_fields, state_blocks)}
    out = physics.update(interior, pd, sspec, lambda a: a * dom)

    band = h + 2 * r
    xlo = upd(slice(0, band), slice(None))
    xhi = upd(slice(wx - band, wx), slice(None))
    for f in out:
        out[f] = out[f].at[:h + r].set(xlo[f][:h + r])
        out[f] = out[f].at[wx - h - r:].set(xhi[f][r:])
    if bx > 2 * r:  # middle x range exists: cover its y rims
        ylo = upd(slice(h, wx - h), slice(0, band))
        yhi = upd(slice(h, wx - h), slice(wy - band, wy))
        for f in out:
            out[f] = out[f].at[h + r:wx - h - r, :h + r].set(
                ylo[f][r:bx - r, :h + r])
            out[f] = out[f].at[h + r:wx - h - r, wy - h - r:].set(
                yhi[f][r:bx - r, r:])

    # post-step sequence of _jnp_window_tile, k = 0
    for f in physics.evolved_fields:
        if f not in physics.premasked_fields:
            out[f] = out[f] * dom
    sx, sy, sz = s_coords[:, 0], s_coords[:, 1], s_coords[:, 2]
    for f in physics.inject_fields:
        out[f] = out[f].at[sx, sy, sz].add(s_vals0.astype(out[f].dtype))
    rx, ry, rz = r_coords[:, 0], r_coords[:, 1], r_coords[:, 2]
    rec = jnp.stack([(arr[rx, ry, rz] * r_w).astype(arr.dtype)
                     for arr in physics.record(out)], axis=-1)
    return (tuple(out[f] for f in physics.state_fields), rec[None])


# ---------------------------------------------------------------------------
# Host-side per-pass table binning
# ---------------------------------------------------------------------------

def _shard_axis_ranges(v, b, n_shard, geom, axis):
    """(shard, tile) pairs along ONE axis whose window [shard*b + tile*t
    - d - hp, ... + t + 2*hp) (or centre, for depth-1 passes) contains
    coordinate v — `tables.axis_tile_range` applied at shard granularity
    (a shard "window" spans its whole shifted tile grid), then again at
    tile granularity inside each covering shard.  O(pairs)."""
    t = geom.tile[axis]
    n_tile = geom.ntiles[axis]
    hp, d = geom.halo, geom.d_out
    pad = 0 if geom.include_halo else hp     # centre binning: shrink by hp
    span = t + 2 * (hp - pad)
    s_lo, s_hi = tables_mod.axis_tile_range(
        v, -(d + hp - pad), b, n_shard, (n_tile - 1) * t + span)
    out = []
    for s in range(s_lo, s_hi + 1):
        lo0 = s * b - d - hp + pad           # shard's tile-0 window lo
        k_lo, k_hi = tables_mod.axis_tile_range(v, lo0, t, n_tile, span)
        for k in range(k_lo, k_hi + 1):
            out.append((s, k))
    return out


def _pass_source_tables(plan: DistTBPlan, g, geom: TBPassGeom,
                        cap: Optional[int] = None):
    """Sharded (px, py, ntiles, ...) source tables for one inner pass.

    The pass's tile grid is per-shard and shifted by the remaining depth
    (`geom.d_out`) off the shard origin, so (unlike the flat schedule)
    it is NOT a partition of the global grid: the extended windows of
    neighbouring shards overlap and every affected point is duplicated
    into every (shard, tile) window that contains it — the sharded
    generalization of `sources.tile_source_tables(include_halo=True)`
    (paper Fig. 4b).  Depth-1 passes bin by tile centre instead (the
    injection only has to cover what the crop keeps).

    `cap` bounds entries per (shard, tile); None auto-sizes, a too-small
    cap raises the unified `tables.overflow_message` error naming the
    (shard, tile) and the required cap.

    Returns (coords (px, py, ntl, cap, 3) window-local int32,
             sid    (px, py, ntl, cap) int32, -1 padding,
             mask   (px, py, ntl, cap) float32 1/0 validity — the physical
             injection scale is gathered in-graph from sid).
    """
    px, py = plan.pgrid
    ntx, nty = geom.ntiles
    ntl = ntx * nty
    if g is None:
        return (jnp.zeros((px, py, ntl, 1, 3), jnp.int32),
                jnp.full((px, py, ntl, 1), -1, jnp.int32),
                jnp.zeros((px, py, ntl, 1), jnp.float32))
    bx, by = plan.block
    tx, ty = geom.tile
    hp = geom.halo
    d = geom.d_out
    pts = np.asarray(g.points)

    pairs = []  # (flat (shard, tile) id, point_idx)
    for p in range(pts.shape[0]):
        x, y = int(pts[p, 0]), int(pts[p, 1])
        for sx, ti in _shard_axis_ranges(x, bx, px, geom, 0):
            for sy, tj in _shard_axis_ranges(y, by, py, geom, 1):
                pairs.append(((sx * py + sy) * ntl + ti * nty + tj, p))

    def tile_name(flat):
        s, t = divmod(flat, ntl)
        return f"(shard {divmod(s, py)}, tile {t})"

    _, slot, cap = tables_mod.pack_slots(pairs, px * py * ntl, cap,
                                         "pass source table", tile_name)
    coords = np.zeros((px, py, ntl, cap, 3), np.int32)
    sid = np.full((px, py, ntl, cap), -1, np.int32)
    mask = np.zeros((px, py, ntl, cap), np.float32)
    for (flat, p), k in zip(pairs, slot):
        (sx, sy), t = divmod(flat // ntl, py), flat % ntl
        ti, tj = t // nty, t % nty
        ox = sx * bx + ti * tx - d - hp
        oy = sy * by + tj * ty - d - hp
        coords[sx, sy, t, k] = (pts[p, 0] - ox, pts[p, 1] - oy, pts[p, 2])
        sid[sx, sy, t, k] = p
        mask[sx, sy, t, k] = 1.0
    return jnp.asarray(coords), jnp.asarray(sid), jnp.asarray(mask)


def _pass_receiver_tables(plan: DistTBPlan, receivers, geom: TBPassGeom,
                          cap: Optional[int] = None):
    """Sharded receiver gather entries for one inner pass.

    Each (receiver, grid point) pair is recorded exactly once per step:
    by the shard that OWNS the point and the pass tile whose centre
    contains it (owned points sit deep enough inside every pass window to
    be valid at every in-pass step).  `cap` follows the unified contract
    (`tables.pack_slots`): None auto-sizes, a too-small cap raises naming
    the (shard, tile) and the required cap.  Returns (coords, weight) as
    sharded jnp arrays plus the host-side rid table `_combine_pass`
    segment-sums partials with.
    """
    px, py = plan.pgrid
    ntx, nty = geom.ntiles
    ntl = ntx * nty
    if receivers is None:
        return (jnp.zeros((px, py, ntl, 1, 3), jnp.int32),
                jnp.zeros((px, py, ntl, 1), jnp.float32),
                np.full((px, py, ntl, 1), -1, np.int32))
    idx = np.asarray(receivers.indices).reshape(-1, 3)
    w = np.asarray(receivers.weights, np.float64).reshape(-1)
    rids = np.repeat(np.arange(receivers.num, dtype=np.int32),
                     receivers.indices.shape[1])
    keep = w != 0.0
    idx, w, rids = idx[keep], w[keep], rids[keep]
    bx, by = plan.block
    tx, ty = geom.tile
    hp = geom.halo
    d = geom.d_out
    sx = idx[:, 0] // bx
    sy = idx[:, 1] // by
    cxl = idx[:, 0] - sx * bx + d        # pass-grid-local x in [d, bx + d)
    cyl = idx[:, 1] - sy * by + d
    ti, tj = cxl // tx, cyl // ty
    t = ti * nty + tj
    flat = (sx * py + sy) * ntl + t

    def tile_name(fl):
        s, tt = divmod(fl, ntl)
        return f"(shard {divmod(s, py)}, tile {tt})"

    _, slot, cap = tables_mod.pack_slots(
        list(zip(flat.tolist(), range(idx.shape[0]))), px * py * ntl, cap,
        "pass receiver table", tile_name)
    coords = np.zeros((px, py, ntl, cap, 3), np.int32)
    weight = np.zeros((px, py, ntl, cap), np.float32)
    rid = np.full((px, py, ntl, cap), -1, np.int32)
    for p in range(idx.shape[0]):
        k = slot[p]
        coords[sx[p], sy[p], t[p], k] = (cxl[p] - ti[p] * tx + hp,
                                         cyl[p] - tj[p] * ty + hp,
                                         idx[p, 2])
        weight[sx[p], sy[p], t[p], k] = w[p]
        rid[sx[p], sy[p], t[p], k] = rids[p]
    return jnp.asarray(coords), jnp.asarray(weight), rid


class _RidTab(NamedTuple):
    """The slice of a receiver table `ops.combine_rec_partials` reads."""

    rid: jnp.ndarray


def _combine_pass(parts, rid, nrec: int):
    """(px, py, ntl, T, capr, chan) shard partials + host rid table ->
    (T, nrec, chan) per-step samples (segment sum over receiver ids)."""
    px, py, ntl, T, capr, chan = parts.shape
    flat = parts.reshape(px * py * ntl, 1, T, capr, chan)
    tab = _RidTab(rid=jnp.asarray(rid.reshape(px * py * ntl, capr)))
    return ops_mod.combine_rec_partials(flat, tab, nrec)


# ---------------------------------------------------------------------------
# Sharded driver
# ---------------------------------------------------------------------------

def _depth_setup(plan: DistTBPlan, T_depth: int,
                 g: Optional[src_mod.GriddedSources],
                 receivers: Optional[src_mod.GriddedReceivers],
                 params: Dict[str, jnp.ndarray], interpret: bool,
                 prepped=None):
    """Build the shard_map'd tile function, its sharded tables / padded
    params, and the receiver-partial combiner for one time-tile depth
    (main T or the nt % T remainder).

    The host-built tables depend only on geometry (g's affected points,
    block, inner tile, halo) — never on `params` — so this whole setup
    traces cleanly under jit; the param-dependent injection scale is
    gathered in-graph by the tile function (table `scale` column = 1/0
    validity mask).

    `prepped` (optional) is the `(param_pads, dom_pad, h_from)` triple a
    DEEPER depth setup already exchanged: the remainder tile's halo is
    strictly shallower than the main tiles' (`rem < T`), so its padded
    params and domain mask are a collective-free per-shard centre crop of
    the main ones — the remainder pays ZERO param ppermute rounds
    (ROADMAP: the remainder's serialized setup exchange).

    Returns (run_tile, combine, (param_pads, dom_pad, h)) with
      run_tile(state, src_win, scale_vec) -> (new state, partials pytree)
      combine(partials) -> (T_depth, nrec, rec_channels) per-step samples.
    """
    physics = plan.physics
    ns = len(physics.state_fields)
    npar = len(physics.param_fields)
    px, py = plan.pgrid
    bx, by = plan.block
    r = plan.r_step
    h = T_depth * r
    overlap = plan.overlap
    T_rest = T_depth - 1 if overlap else T_depth  # steps the inner exec runs
    depths = plan.field_depths(T_depth)
    nrec = receivers.num if receivers is not None else 0
    nchan = physics.rec_channels
    spec3 = P(plan.ax_x, plan.ax_y, None)

    # --- the time-nested pass schedule: T_rest steps in inner-depth chunks
    # over pass-by-pass-shrinking windows (flat = one pass) ------------------
    geoms = nested_pass_geometry((bx, by), plan.inner_tile, T_rest,
                                 min(plan.inner_T, max(T_rest, 1)), r)

    # --- host-side owner-sharded source/receiver tables, one binning per
    # pass (the tile origins shift with the remaining depth d_out) -----------
    extra = []
    pass_rids = []
    for geom in geoms:
        sc, sid, smask = _pass_source_tables(plan, g, geom)
        rc, rw, rid = _pass_receiver_tables(plan, receivers, geom)
        pass_rids.append(rid)
        extra += [sc, sid, smask, rc, rw]
    o_rid = None
    if overlap:
        # shard-level tables for the split first step (window = the whole
        # exchanged block, one "tile" per shard)
        og = TBPassGeom(T=1, t0=0, d_in=h, d_out=0, halo=h, grid=(bx, by),
                        tile=(bx, by), ntiles=(1, 1),
                        include_halo=T_depth > 1)
        o_sc, o_sid, o_smask = _pass_source_tables(plan, g, og)
        o_rc, o_rw, o_rid = _pass_receiver_tables(plan, receivers, og)
        extra += [o_sc, o_sid, o_smask, o_rc, o_rw]
    extra_specs = [P(plan.ax_x, plan.ax_y, *(None,) * (a.ndim - 2))
                   for a in extra]

    # --- time-invariant param halos (exchanged once per depth) --------------
    fills = dict(physics.param_fills)

    with _spans.span("halo.setup_exchange", depth=h,
                     reused=prepped is not None):
        if prepped is not None and prepped[2] >= h:
            # reuse a deeper setup's exchanged pads: per-shard centre crop
            # (the depth-h mask/halo band IS the centre of the depth-h_from
            # one), no ppermute at all
            d = prepped[2] - h

            @functools.partial(jax.shard_map, mesh=plan.mesh,
                               in_specs=(spec3,) * (npar + 1),
                               out_specs=(spec3,) * (npar + 1))
            def reslice(*ps):
                if d == 0:
                    return ps
                return tuple(p[d:-d, d:-d] for p in ps)

            resliced = reslice(*prepped[0], prepped[1])
            param_pads, dom_pad = resliced[:npar], resliced[npar]
        else:
            @functools.partial(jax.shard_map, mesh=plan.mesh,
                               in_specs=(spec3,) * npar,
                               out_specs=(spec3,) * (npar + 1))
            def prepare(*ps):
                pads = [halo_exchange_2d(p, h, plan.ax_x, plan.ax_y)
                        for p in ps]
                dom = _local_domain_mask(plan, h, pads[0].shape,
                                         pads[0].dtype)
                out = []
                for f, pad in zip(physics.param_fields, pads):
                    fill = fills.get(f, 0.0)
                    if fill:
                        pad = jnp.where(dom > 0, pad,
                                        jnp.asarray(fill, pad.dtype))
                    out.append(pad)
                return (*out, dom)

            prepared = prepare(*[params[f] for f in physics.param_fields])
            param_pads, dom_pad = prepared[:npar], prepared[npar]

    # --- one outer-trapezoid tile: deep exchange + T local steps ------------
    sspec = _StepSpec(float(plan.dt), tuple(float(s) for s in plan.spacing),
                      plan.order)
    in_specs = ((spec3,) * ns + (spec3,) * npar + (spec3,)
                + tuple(extra_specs) + (P(None, None), P(None)))
    out_specs = (spec3,) * ns
    if overlap:
        out_specs += (P(plan.ax_x, plan.ax_y, None, None, None, None),)
    out_specs += (P(plan.ax_x, plan.ax_y, None, None, None, None),) \
        * len(geoms)

    def _gather_vals(win, sid, smask, scale_vec, dtype):
        """(T, npts) decomposed wavelets -> per-tile (tiles..., T, cap)
        injection values, scale gathered in-graph."""
        safe = jnp.maximum(sid, 0)
        sv = win[:, safe] * (scale_vec[safe] * smask)[None]
        ndim = sv.ndim  # (T, *tiles, cap)
        return jnp.transpose(sv, tuple(range(1, ndim - 1)) + (0, ndim - 1)
                             ).astype(dtype)

    # check_vma=False: the varying-axes checker has no rule for pallas_call
    # (the inner="pallas" path); every output is explicitly sharded anyway.
    @functools.partial(jax.shard_map, mesh=plan.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def tile(*args):
        sblocks = args[:ns]
        ppads = args[ns:ns + npar]
        dom = args[ns + npar]
        rest = list(args[ns + npar + 1:])
        ptabs = []
        for _ in geoms:
            ptabs.append([a[0, 0] for a in rest[:5]])
            rest = rest[5:]
        if overlap:
            osc, osid, osmask, orc, orw = [a[0, 0, 0] for a in rest[:5]]
            rest = rest[5:]
        src_win, scale_vec = rest
        dtype = sblocks[0].dtype
        # ONE deep exchange per depth-T tile (the whole point), per-field
        # depths zero-padded to the uniform window
        with _spans.annotate("halo.exchange", depth=h):
            spads = tuple(exchange_to_depth(b, d, h, plan.ax_x, plan.ax_y)
                          for b, d in zip(sblocks, depths))
        rec_outs = []
        off = 0
        if overlap:
            with _spans.annotate("halo.split_first_step", depth=h):
                sv0 = (src_win[0][jnp.maximum(osid, 0)]
                       * (scale_vec[jnp.maximum(osid, 0)]
                          * osmask)).astype(dtype)
                state1, rec1 = _split_first_step(
                    plan, sspec, h, sblocks, spads, ppads, dom, osc, sv0,
                    orc, orw)
            rec_outs.append(rec1[None, None, None])
            # depth h - r = T_rest * r: exactly the first pass's d_in
            state = tuple(a[r:-r, r:-r] for a in state1)
            off = 1
        else:
            state = spads
        for ip, (geom, tabs) in enumerate(zip(geoms, ptabs)):
            isc, isid, ismask, irc, irw = tabs
            with _spans.annotate("halo.pass", idx=ip, T=geom.T,
                                 d_out=geom.d_out):
                sv = _gather_vals(
                    src_win[off + geom.t0:off + geom.t0 + geom.T],
                    isid, ismask, scale_vec, dtype)
                state, parts = _run_pass(plan, geom, state, ppads, dom, h,
                                         isc, sv, irc, irw, interpret)
            rec_outs.append(parts[None, None])
        return (*state, *rec_outs)

    def run_tile(state, src_win, scale_vec):
        outs = tile(*state, *param_pads, dom_pad, *extra, src_win, scale_vec)
        return tuple(outs[:ns]), tuple(outs[ns:])

    def combine(partials):
        """Shard partials -> (T_depth, nrec, nchan) per-step samples."""
        if receivers is None:
            return jnp.zeros((T_depth, 0, nchan), jnp.float32)
        recs = []
        idx = 0
        if overlap:
            recs.append(_combine_pass(partials[0], o_rid, nrec))
            idx = 1
        for geom, rid in zip(geoms, pass_rids):
            recs.append(_combine_pass(partials[idx], rid, nrec))
            idx += 1
        return recs[0] if len(recs) == 1 else jnp.concatenate(recs, axis=0)

    return run_tile, combine, (param_pads, dom_pad, h)


def sharded_tb_propagate(plan: DistTBPlan, nt: int,
                         state: Tuple[jnp.ndarray, ...],
                         params: Dict[str, jnp.ndarray],
                         g: Optional[src_mod.GriddedSources] = None,
                         receivers: Optional[src_mod.GriddedReceivers] = None,
                         *, interpret: Optional[bool] = None):
    """Temporally-blocked sharded propagation of any registered physics.

    Semantics identical to the matching `kernels.ref.*_reference` (tested):
    `state` is ordered as `plan.physics.state_fields`, `params` maps
    `param_fields` to GLOBAL (nx, ny, nz) arrays (sharded or not — jit
    handles layout via the shard_map specs).  `nt` need not divide by
    `plan.T`; the remainder runs as a shallower tile with its own
    (smaller) exchange depth, mirroring `kernels/ops._tb_propagate`.
    The schedule — inner spatial tiling, inner time depth (time-nested
    passes when `inner_plan.T < T`), per-field exchange depths,
    overlapped exchange — comes from the plan and never changes results,
    only data movement (tested across all combinations).

    Returns (final state tuple, rec (nt, nrec, rec_channels) | None) with
    per-step receiver samples at any T (each shard records masked partials,
    segment-summed by receiver id across shards).

    jit-compatible in `state`/`params` (sharded or not — the shard_map
    specs handle layout): the host-side table build depends only on `g`
    and the static plan, and the param-dependent injection scale is
    gathered in-graph.
    """
    physics = plan.physics
    plan.validate()
    state = tuple(state)
    if len(state) != len(physics.state_fields):
        raise ValueError(f"{physics.name} carries "
                         f"{len(physics.state_fields)} state fields, "
                         f"got {len(state)}")
    nchan = physics.rec_channels
    dtype = state[0].dtype

    if g is not None:
        if g.nt < nt:
            raise ValueError(f"source wavelets cover {g.nt} steps < nt={nt}")
        src_dcmp = g.src_dcmp
        scale_vec = jnp.asarray(
            physics.inject_scale(params, g, float(plan.dt)),
            jnp.float32)
    else:
        src_dcmp = jnp.zeros((max(nt, 1), 1), dtype)
        scale_vec = jnp.zeros((1,), jnp.float32)

    def src_window(t0, T_depth):
        return jax.lax.dynamic_slice(src_dcmp, (t0, 0),
                                     (T_depth, src_dcmp.shape[1]))

    n_main = nt // plan.T
    rem = nt - n_main * plan.T

    recs_main = None
    main_pads = None
    if n_main > 0:
        with _spans.span("halo.setup", depth=plan.T):
            run_tile, combine, main_pads = _depth_setup(plan, plan.T, g,
                                                        receivers, params,
                                                        interpret)

        def body(carry, tile_idx):
            new, parts = run_tile(carry, src_window(tile_idx * plan.T,
                                                    plan.T), scale_vec)
            return new, combine(parts)

        state, recs_main = jax.lax.scan(body, state, jnp.arange(n_main))
        recs_main = recs_main.reshape(n_main * plan.T, -1, nchan)

    if rem > 0:
        # the remainder tile nests the same way: passes of the SAME inner
        # depth (clamped when the remainder is shallower than one pass);
        # its shallower param/domain pads are cropped out of the main
        # tiles' deep-exchanged ones (no second param ppermute round)
        rplan = plan._replace(
            T=rem, inner_plan=(dataclasses.replace(
                plan.inner_plan, T=min(plan.inner_plan.T, rem))
                if plan.inner_plan is not None else None))
        with _spans.span("halo.remainder", depth=rem):
            run_rem, combine_rem, _ = _depth_setup(rplan, rem, g, receivers,
                                                   params, interpret,
                                                   prepped=main_pads)
            state, parts = run_rem(state, src_window(n_main * plan.T, rem),
                                   scale_vec)
            rec_rem = combine_rem(parts)
        recs = (jnp.concatenate([recs_main, rec_rem], axis=0)
                if recs_main is not None else rec_rem)
    else:
        recs = recs_main

    return state, (recs if receivers is not None else None)
