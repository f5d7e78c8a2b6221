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
                      spatially tiled by `inner_plan.tile`: the Pallas TB
                      kernel (`stencil_tb.tb_time_tile`), one kernel grid
                      of block/tile windows per pass, DMA'd in place out
                      of the shard's frames, its domain mask an iota
                      predicate over the shard's domain box.

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
through the kernel on the stitched state at depth `H - r_step`.
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

Memory (DESIGN.md §4): a shard keeps each exchanged field in ONE buffer,
its frame (`_Frame`) — the block zero-padded to the exchange depth, the
received strips written into it in place — and every pass reads its
windows from the frames at an origin, so no per-tile pad, crop or mask
array exists; the params are exchanged into their frames once per
propagate.  With the state donated by the entry point
(`sharded_propagate`), a 512x512x1024 acoustic block fits one v5e at
1024^3 on a 2x2 mesh (`tests/test_tpu_compile.py`).

Mesh layout: grid x -> "data" axis, grid y -> "model" axis.  Exchanges are
`lax.ppermute` shifts; missing neighbors (domain boundary) produce zeros =
the Dirichlet convention shared by the reference and the Pallas kernel, and
out-of-domain cells are re-masked every in-block step (param fields carry
their physics' `param_fills` there so updates stay finite).
"""
from __future__ import annotations

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
from repro.kernels import stencil_tb as ker
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


def exchange_to_depth(x, depth: int, h, ax_x: str, ax_y: str,
                      shift_fns=None, frame: Optional[Tuple[int, int]] = None):
    """The local block with a depth-`depth` halo from its neighbours, in a
    zero window of depth `h` — the per-field deep exchange (DESIGN.md §4).
    Cells in the zero band are only ever read into values the trapezoid
    discards (`TBPhysics.halo_lags` is derived from exactly that
    dependency cone); `depth == 0` skips the ppermute rounds entirely.

    The window is ONE buffer: the block zero-padded to it, the received
    strips then written in place (x first, then y from the x-extended
    rows, which fills the corners).  `h` may be an (x, y) pair, and
    `frame` (default (bx + 2h, by + 2h)) makes the buffer larger on the
    high side, so a caller can read windows from it in place (`_Frame`).
    `shift_fns` (default: the ppermute pair above) injects the two
    neighbor-strip providers `(from_low, from_high)` — tests and oracles
    substitute collective-free simulators so the strip algebra is
    exercised with real neighbor data on one device."""
    from_low, from_high = shift_fns or (_shift_from_low, _shift_from_high)
    hx, hy = (h, h) if isinstance(h, int) else h
    bx, by = x.shape[0], x.shape[1]
    fx, fy = frame or (bx + 2 * hx, by + 2 * hy)
    out = jnp.pad(x, ((hx, fx - hx - bx), (hy, fy - hy - by), (0, 0)))
    if depth > 0:
        d = depth
        put = jax.lax.dynamic_update_slice
        out = put(out, from_low(x, d, ax_x, 0), (hx - d, hy, 0))
        out = put(out, from_high(x, d, ax_x, 0), (hx + bx, hy, 0))
        rows = out[hx - d:hx + bx + d, hy:hy + by]
        out = put(out, from_low(rows, d, ax_y, 1), (hx - d, hy - d, 0))
        out = put(out, from_high(rows, d, ax_y, 1), (hx - d, hy + by, 0))
    return out


def halo_exchange_2d(x, h: int, ax_x: str, ax_y: str, shift_fns=None):
    """The full-depth exchange: the block padded with depth-h halos from
    all eight neighbours (x then y; the second round carries the x-halo,
    so the corners fill)."""
    return exchange_to_depth(x, h, h, ax_x, ax_y, shift_fns=shift_fns)


class _StepSpec(NamedTuple):
    """The slice of `TBKernelSpec` a `TBPhysics.update` actually reads."""

    dt: float
    spacing: Tuple[float, float, float]
    order: int


class DistTBPlan(NamedTuple):
    """Static setup for the sharded temporally-blocked propagator.

    `inner_plan` is the inner level of the two-level hierarchy: its tile
    spatially tiles the shard block inside the per-shard schedule, and
    its T is the INNER time depth — any depth up to the outer exchange
    depth `T`.  When `inner_plan.T < T`, the kernel runs
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
    inner_plan: Optional[TBPlan] = None
    overlap: bool = False       # overlapped (split-first-step) exchange

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
                        **kwargs) -> DistTBPlan:
    """Turn a jointly-autotuned `core.temporal_blocking.HierPlan` into the
    executable `DistTBPlan` (outer T and exchange overlap from the outer
    level, spatial tile from the inner level)."""
    return DistTBPlan(mesh=mesh, grid_shape=grid_shape, physics=physics,
                      order=order, T=hier.T, dt=dt, spacing=spacing,
                      inner_plan=hier.inner, overlap=hier.overlap,
                      **kwargs)


def _geoms(plan: DistTBPlan, T_depth: int):
    """The inner passes of one depth-`T_depth` tile: the steps the kernel
    runs (all but the split first step with overlap) in chunks of
    the inner depth, clamped to the tile (the `nt % T` remainder)."""
    T_rest = T_depth - 1 if plan.overlap else T_depth
    inner_T = min(plan.inner_T, T_depth, max(T_rest, 1))
    return nested_pass_geometry(plan.block, plan.inner_tile, T_rest,
                                inner_T, plan.r_step)


def _pass_spec(plan: DistTBPlan, geom: TBPassGeom, nz: int, src_cap: int,
               rec_cap: int, dtype):
    return ops_mod.pass_inner_spec(
        geom, nz, plan.order, float(plan.dt),
        tuple(float(s) for s in plan.spacing), src_cap, rec_cap, dtype,
        plan.physics)


class _Frame(NamedTuple):
    """Where a shard keeps an exchanged field (DESIGN.md §4): one (fx, fy,
    nz) buffer whose index `depth + i` is block-local i, per axis.  A
    pass reads its kernel windows from it in place, at origin `depth -
    d_in`; the y depth puts a state frame's first read on the 8-row
    tiling a window DMA needs, and the high-side margin holds every
    window (y rounded up to 8 rows)."""

    depth: Tuple[int, int]
    shape: Tuple[int, int]


def _aligned(lo: int, d_in: int) -> int:
    """The least depth >= lo at which a read of input depth d_in starts
    on an 8-row boundary."""
    return lo + (d_in - lo) % 8


def _fit(plan: DistTBPlan, depth: Tuple[int, int], reads, nz: int
         ) -> _Frame:
    """The frame at `depth` that holds its (bx + 2*depth) window and the
    kernel windows of the passes in `reads`."""
    bx, by = plan.block
    fx, fy = bx + 2 * depth[0], by + 2 * depth[1]
    for geom in reads:
        wx, wy, _ = _pass_spec(plan, geom, nz, 1, 1, jnp.float32).window
        ox, oy = depth[0] - geom.d_in, depth[1] - geom.d_in
        fx = max(fx, ox + geom.grid[0] - geom.tile[0] + wx)
        # an unaligned read DMAs from the row below, 8 rows more
        fy = max(fy, oy - oy % 8 + geom.grid[1] - geom.tile[1] + wy
                 + (8 if oy % 8 else 0))
    return _Frame(depth, (fx, fy))


def _frames(plan: DistTBPlan, nt: int, nz: int):
    """(param frame, {tile depth: state frame}).  The params are exchanged
    once at the main tiles' depth and read by every pass, aligned for the
    main tiles' first pass; each tile depth exchanges its state into a
    frame aligned for its own first pass."""
    h = plan.halo
    tiles = {d: _geoms(plan, d) for d in _tile_depths(plan, nt)}
    main = next(iter(tiles.values()))
    pdepth = (h, _aligned(h, main[0].d_in) if main else h)
    params = _fit(plan, pdepth, [g for gs in tiles.values() for g in gs],
                  nz)
    states = {}
    for d, geoms in tiles.items():
        hd = d * plan.r_step
        sdepth = (hd, _aligned(hd, geoms[0].d_in) if geoms else hd)
        states[d] = _fit(plan, sdepth, geoms[:1], nz)
    return params, states


def _tile_depths(plan: DistTBPlan, nt: int):
    """The tile depths a propagate runs: T, then the nt % T remainder."""
    return sorted({min(nt, plan.T), nt % plan.T} - {0}, reverse=True)


def _dom_bounds(plan: DistTBPlan, offset: Tuple[int, int]):
    """(x_lo, x_hi, y_lo, y_hi): this shard's part of the global domain in
    local coordinates whose index `offset` is the block's first cell."""
    nx, ny, _ = plan.grid_shape
    bx, by = plan.block
    sx = jax.lax.axis_index(plan.ax_x) * bx
    sy = jax.lax.axis_index(plan.ax_y) * by
    return (offset[0] - sx, nx + offset[0] - sx, offset[1] - sy,
            ny + offset[1] - sy)


def _dom_mask(bounds, shape, start, dtype):
    """1 inside `bounds`, 0 outside, over an array of `shape` whose first
    cell sits at local coordinates `start` — an iota predicate XLA fuses
    into its consumer, never a grid-sized buffer."""
    x_lo, x_hi, y_lo, y_hi = bounds
    gx = start[0] + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    gy = start[1] + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return ((gx >= x_lo) & (gx < x_hi) & (gy >= y_lo)
            & (gy < y_hi)).astype(dtype)


# ---------------------------------------------------------------------------
# Per-shard inner trapezoids
# ---------------------------------------------------------------------------

def _reach(a, origin: Tuple[int, int], need: Tuple[int, int], fill: float):
    """`a` padded on the high side (with `fill`) until [origin, origin +
    need) fits in x and y; frames already hold every window, so this only
    grows a pass's output that the next nested pass reads."""
    ex = max(origin[0] + need[0] - a.shape[0], 0)
    ey = max(origin[1] + need[1] - a.shape[1], 0)
    if ex or ey:
        a = jnp.pad(a, ((0, ex), (0, ey), (0, 0)),
                    constant_values=jnp.asarray(fill, a.dtype))
    return a


def _run_pass(plan: DistTBPlan, geom: TBPassGeom, state,
              s_depth: Tuple[int, int], param_frames,
              p_depth: Tuple[int, int], s_coords, s_vals, r_coords, r_w,
              counts, interpret: bool):
    """Advance ONE inner pass of the time-nested schedule (DESIGN.md §4).

    `state` holds the block at (x, y) depth `s_depth` (a frame, or the
    previous pass's output) and is valid to depth `geom.d_in`; the pass
    advances `geom.T` steps over the region that stays valid afterwards
    (`block + 2*geom.d_out`, rounded up to the inner tile: the round-up
    band the crop discards reads cells no kept value depends on) and
    returns it at depth `geom.d_out` — the next pass's input, landing
    exactly on the block at the last pass.  Every operand is read in
    place, at origin `depth - geom.d_in`; the domain mask is the shard's
    box in pass-grid coordinates.

    Tables are per pass-local tile: s_coords (ntiles, cap, 3) window-local,
    s_vals (ntiles, geom.T, cap), r_coords/r_w likewise, and counts
    (ntiles, 2) the live (source, receiver) entries of each tile.  Returns
    (state tuple at depth d_out, rec partials (ntiles, geom.T, capr, chan)).
    """
    physics = plan.physics
    bx, by = plan.block
    nz = state[0].shape[2]
    tx, ty = geom.tile
    cx, cy = geom.grid
    keep = (bx + 2 * geom.d_out, by + 2 * geom.d_out)
    fills = dict(physics.param_fills)
    so = (s_depth[0] - geom.d_in, s_depth[1] - geom.d_in)
    po = (p_depth[0] - geom.d_in, p_depth[1] - geom.d_in)
    bounds = _dom_bounds(plan, (geom.d_out, geom.d_out))
    ntx, nty = geom.ntiles
    # One pallas_call whose grid tiles the pass window, its windows DMA'd
    # straight out of the frames
    spec = _pass_spec(plan, geom, nz, s_coords.shape[1], r_coords.shape[1],
                      state[0].dtype)
    need = (cx - tx + spec.window[0], cy - ty + spec.window[1])
    spads = tuple(_reach(a, so, need, 0.0) for a in state)
    # an unaligned param read DMAs from the row below, 8 rows more
    pneed = (need[0], need[1] + (8 if po[1] % 8 else 0))
    pfrom = (po[0], po[1] - po[1] % 8)
    ppads = tuple(_reach(a, pfrom, pneed, fills.get(f, 0.0))
                  for f, a in zip(physics.param_fields, param_frames))
    new, rec = ker.tb_time_tile(
        spec, physics, spads, ppads, s_coords, s_vals, r_coords, r_w,
        counts, origins=(so,) * len(spads) + (po,) * len(ppads),
        dom_box=jnp.stack(bounds).astype(jnp.int32), interpret=interpret)
    new = tuple(a[:keep[0], :keep[1]] for a in new)
    rec = rec.reshape(ntx * nty, geom.T, rec.shape[-2], rec.shape[-1])
    return new, rec


def _split_first_step(plan: DistTBPlan, sspec: _StepSpec, h: int,
                      state_blocks, frames, s_depth: Tuple[int, int],
                      param_frames, p_depth: Tuple[int, int],
                      s_coords, s_vals0, r_coords, r_w):
    """The overlapped first step of a deep tile (DESIGN.md §4).

    The exchanged halo is only needed within `h + r_step` of the window
    edge at step 1, so the step splits into:

      interior   `physics.update` on the LOCAL block alone (inside the
                 domain, zero beyond it) — no data dependency on the
                 ppermute, so XLA can run the exchange underneath it;
                 valid at >= r_step from the block edge.
      rim strips four band updates of width `h + 2*r_step` read from the
                 exchanged frames, each valid (after an r_step crop at cut
                 edges) over the rim the interior cannot cover.

    The window is the depth-h part of the frames (state frames at (x, y)
    depth `s_depth`, params at `p_depth`, both >= h); each evolved
    field's new frame holds the interior with the strips written over
    its rim, and a field the update carries unchanged (a previous
    time level) keeps its source's exchanged frame.  The result carries
    the standard trapezoid contract (garbage only within r_step of the
    window edge).  Injection and receiver partials then run as in the
    kernel's first in-window step, on SHARD-level tables (window-local).

    Returns (new frames tuple, rec partials (1, capr, chan)).
    """
    physics = plan.physics
    r = plan.r_step
    bx, by = plan.block
    nz = frames[0].shape[2]
    dtype = frames[0].dtype
    ox, oy = s_depth[0] - h, s_depth[1] - h      # the window's origins
    px, py = p_depth[0] - h, p_depth[1] - h
    wx, wy = bx + 2 * h, by + 2 * h
    sd = dict(zip(physics.state_fields, frames))
    pd = dict(zip(physics.param_fields, param_frames))
    bounds = _dom_bounds(plan, s_depth)

    def upd(x0, x1, y0, y1):
        """The update over window rows [x0, x1) x cols [y0, y1), with its
        domain mask."""
        ss = (slice(ox + x0, ox + x1), slice(oy + y0, oy + y1))
        ps = (slice(px + x0, px + x1), slice(py + y0, py + y1))
        dm = _dom_mask(bounds, (x1 - x0, y1 - y0, nz), (ox + x0, oy + y0),
                       dtype)
        new = physics.update({f: a[ss] for f, a in sd.items()},
                             {f: a[ps] for f, a in pd.items()}, sspec,
                             lambda a: a * dm)
        return new, dm

    # interior: independent of the exchange (the block holds no
    # out-of-domain cell, so its mask is the identity)
    blocks = dict(zip(physics.state_fields, state_blocks))
    inner = physics.update(
        blocks, {f: a[p_depth[0]:p_depth[0] + bx, p_depth[1]:p_depth[1] + by]
                 for f, a in pd.items()}, sspec, lambda a: a)
    carried = {f: src for f, a in inner.items()
               for src, b in blocks.items() if a is b}

    band = h + 2 * r
    # (window rows, window cols) of each strip, and the part of it kept
    strips = [((0, band), (0, wy), (slice(0, h + r), slice(None))),
              ((wx - band, wx), (0, wy), (slice(r, None), slice(None)))]
    if bx > 2 * r:  # middle x range exists: cover its y rims
        strips += [((h, wx - h), (0, band), (slice(r, bx - r),
                                              slice(0, h + r))),
                   ((h, wx - h), (wy - band, wy), (slice(r, bx - r),
                                                   slice(r, None)))]
    evolved = [f for f in physics.state_fields if f not in carried]
    fx, fy = frames[0].shape[:2]
    out = {f: jnp.pad(inner[f], ((s_depth[0], fx - s_depth[0] - bx),
                                 (s_depth[1], fy - s_depth[1] - by),
                                 (0, 0)))
           for f in evolved}
    for (x0, x1), (y0, y1), keep in strips:
        new, dm = upd(x0, x1, y0, y1)
        at = (ox + x0 + (keep[0].start or 0),
              oy + y0 + (keep[1].start or 0), 0)
        for f in evolved:
            v = new[f][keep]
            # the kernel's post-step mask (the interior is in-domain: its
            # mask is 1)
            if f in physics.evolved_fields and \
                    f not in physics.premasked_fields:
                v = v * dm[keep]
            out[f] = jax.lax.dynamic_update_slice(out[f], v, at)
    for f, src in carried.items():
        out[f] = sd[src]

    sx, sy, sz = s_coords[:, 0] + ox, s_coords[:, 1] + oy, s_coords[:, 2]
    for f in physics.inject_fields:
        out[f] = out[f].at[sx, sy, sz].add(s_vals0.astype(out[f].dtype))
    rx, ry, rz = r_coords[:, 0] + ox, r_coords[:, 1] + oy, r_coords[:, 2]
    samples = physics.record({f: a[rx, ry, rz] for f, a in out.items()})
    rec = jnp.stack([(v * r_w).astype(v.dtype) for v in samples], axis=-1)
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
             injection scale is gathered in-graph from sid,
             nnz    (px, py, ntl) host int32 live entries, slots 0..n-1).
    """
    px, py = plan.pgrid
    ntx, nty = geom.ntiles
    ntl = ntx * nty
    if g is None:
        return (jnp.zeros((px, py, ntl, 1, 3), jnp.int32),
                jnp.full((px, py, ntl, 1), -1, jnp.int32),
                jnp.zeros((px, py, ntl, 1), jnp.float32),
                np.zeros((px, py, ntl), np.int32))
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

    fill, slot, cap = tables_mod.pack_slots(pairs, px * py * ntl, cap,
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
    return (jnp.asarray(coords), jnp.asarray(sid), jnp.asarray(mask),
            fill.reshape(px, py, ntl))


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
    segment-sums partials with, and the (px, py, ntl) host int32 live
    entries of each (shard, tile).
    """
    px, py = plan.pgrid
    ntx, nty = geom.ntiles
    ntl = ntx * nty
    if receivers is None:
        return (jnp.zeros((px, py, ntl, 1, 3), jnp.int32),
                jnp.zeros((px, py, ntl, 1), jnp.float32),
                np.full((px, py, ntl, 1), -1, np.int32),
                np.zeros((px, py, ntl), np.int32))
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

    fill, slot, cap = tables_mod.pack_slots(
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
    return (jnp.asarray(coords), jnp.asarray(weight), rid,
            fill.reshape(px, py, ntl))


# ---------------------------------------------------------------------------
# Sharded driver
# ---------------------------------------------------------------------------

def _depth_tables(plan: DistTBPlan, T_depth: int,
                  g: Optional[src_mod.GriddedSources],
                  receivers: Optional[src_mod.GriddedReceivers]):
    """Host-side owner-sharded source/receiver tables for one time-tile
    depth (main T or the nt % T remainder): per inner pass (the tile
    origins shift with the remaining depth d_out) `(coords, sid, mask,
    rcoords, rweight, counts, rid)`, with `counts` (px, py, ntl, 2) the
    live (source, receiver) entries that bound the kernel's slot loops,
    and with overlap one more set for the split first step (window = the
    whole exchanged block, one "tile" per shard).  They depend only on
    geometry (g's affected points, block, inner tile, halo), never on
    `params`: the param-dependent injection scale is gathered in-graph."""
    bx, by = plan.block
    out = []
    geoms = list(_geoms(plan, T_depth))
    if plan.overlap:
        geoms.insert(0, TBPassGeom(
            T=1, t0=0, d_in=T_depth * plan.r_step, d_out=0,
            halo=T_depth * plan.r_step, grid=(bx, by), tile=(bx, by),
            ntiles=(1, 1), include_halo=T_depth > 1))
    for geom in geoms:
        sc, sid, smask, snnz = _pass_source_tables(plan, g, geom)
        rc, rw, rid, rnnz = _pass_receiver_tables(plan, receivers, geom)
        out.append((sc, sid, smask, rc, rw, np.stack([snnz, rnnz], -1),
                    jnp.asarray(rid)))
    return tuple(out)


def pass_slot_iters(plan: DistTBPlan, nt: int, tables) -> Dict[str, int]:
    """Span attributes for the kernel's sparse slot loops over a sharded
    propagate, from `_host_tables`' tables: `slot_iters`, the iterations
    its inject and record loops run in every pass — each (shard, tile)'s
    live entries, in each slab of each step (`stencil_tb.slab_steps`) —
    and `slot_iters_at_cap`, the same over every slot of the caps.  The
    split first step runs outside the kernel and counts none."""
    nz = plan.grid_shape[2]
    out = {"slot_iters": 0, "slot_iters_at_cap": 0}
    for T_depth, tabs in tables.items():
        tiles = nt // plan.T if T_depth == plan.T else 1
        for geom, tab in zip(_geoms(plan, T_depth), tabs[plan.overlap:]):
            slabs = tiles * ker.slab_steps(
                _pass_spec(plan, geom, nz, 1, 1, jnp.float32))
            caps = tab[0].shape[3] + tab[3].shape[3]
            out["slot_iters"] += slabs * int(np.sum(tab[5]))
            out["slot_iters_at_cap"] += slabs * tab[5].size // 2 * caps
    return out


def _host_tables(plan: DistTBPlan, nt: int, g, receivers, itemsize: int):
    """Every depth's tables, under the `halo.tables` span with the
    layer's counters (`sharded_counts`)."""
    with _spans.span("halo.tables", physics=plan.physics.name, nt=nt,
                     T=plan.T) as sp:
        tables = {d: _depth_tables(plan, d, g, receivers)
                  for d in _tile_depths(plan, nt)}
        if _spans.active():
            sp.set(**sharded_counts(plan, nt, itemsize),
                   **pass_slot_iters(plan, nt, tables))
    return tables


def _tile(plan: DistTBPlan, frame: _Frame, pframe: _Frame, T_depth: int,
          interpret: bool):
    """The shard_map'd function of one depth-`T_depth` outer-trapezoid
    tile: ONE deep exchange of the state blocks into their `frame`s, then
    T_depth local steps (the split first step with overlap, then the
    inner passes), reading the params in their `pframe`s.

    tile(*state blocks, *param frames, *tables, src_win, scale_vec)
      -> (*new state blocks, *rec partials, one per pass)."""
    physics = plan.physics
    ns = len(physics.state_fields)
    npar = len(physics.param_fields)
    bx, by = plan.block
    r = plan.r_step
    h = T_depth * r
    depths = plan.field_depths(T_depth)
    geoms = _geoms(plan, T_depth)
    ntab = len(geoms) + plan.overlap
    spec3 = P(plan.ax_x, plan.ax_y, None)
    spec6 = P(plan.ax_x, plan.ax_y, None, None, None, None)
    tab_specs = (P(plan.ax_x, plan.ax_y, None, None, None),
                 P(plan.ax_x, plan.ax_y, None, None),
                 P(plan.ax_x, plan.ax_y, None, None),
                 P(plan.ax_x, plan.ax_y, None, None, None),
                 P(plan.ax_x, plan.ax_y, None, None),
                 P(plan.ax_x, plan.ax_y, None, None)) * ntab
    in_specs = ((spec3,) * (ns + npar) + tab_specs + (P(None, None),
                                                      P(None)))
    out_specs = (spec3,) * ns + (spec6,) * ntab
    sspec = _StepSpec(float(plan.dt), tuple(float(s) for s in plan.spacing),
                      plan.order)

    def gather_vals(win, sid, smask, scale_vec, dtype):
        """(T, npts) decomposed wavelets -> per-tile (tiles..., T, cap)
        injection values, scale gathered in-graph."""
        safe = jnp.maximum(sid, 0)
        sv = win[:, safe] * (scale_vec[safe] * smask)[None]
        ndim = sv.ndim  # (T, *tiles, cap)
        return jnp.transpose(sv, tuple(range(1, ndim - 1)) + (0, ndim - 1)
                             ).astype(dtype)

    # check_vma=False: the varying-axes checker has no rule for pallas_call;
    # every output is explicitly sharded anyway.
    @functools.partial(jax.shard_map, mesh=plan.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def tile(*args):
        sblocks = args[:ns]
        ppads = args[ns:ns + npar]
        rest = args[ns + npar:]
        tabs = [[a[0, 0] for a in rest[6 * i:6 * i + 6]]
                for i in range(ntab)]
        src_win, scale_vec = rest[6 * ntab:]
        dtype = sblocks[0].dtype
        # ONE deep exchange per depth-T tile (the whole point), per-field
        # depths, each into its frame
        with _spans.annotate("halo.exchange", depth=h):
            state = tuple(exchange_to_depth(b, d, frame.depth, plan.ax_x,
                                            plan.ax_y, frame=frame.shape)
                          for b, d in zip(sblocks, depths))
        s_depth = frame.depth
        rec_outs = []
        off = 0
        if plan.overlap:
            osc, osid, osmask, orc, orw, _ = (a[0] for a in tabs.pop(0))
            with _spans.annotate("halo.split_first_step", depth=h):
                safe = jnp.maximum(osid, 0)
                sv0 = (src_win[0][safe] * (scale_vec[safe] * osmask)
                       ).astype(dtype)
                state, rec1 = _split_first_step(
                    plan, sspec, h, sblocks, state, s_depth, ppads,
                    pframe.depth, osc, sv0, orc, orw)
            rec_outs.append(rec1[None, None, None])
            off = 1
        for ip, (geom, (isc, isid, ismask, irc, irw, icnt)) in enumerate(
                zip(geoms, tabs)):
            with _spans.annotate("halo.pass", idx=ip, T=geom.T,
                                 d_out=geom.d_out):
                sv = gather_vals(
                    src_win[off + geom.t0:off + geom.t0 + geom.T],
                    isid, ismask, scale_vec, dtype)
                state, parts = _run_pass(plan, geom, state, s_depth, ppads,
                                         pframe.depth, isc, sv, irc, irw,
                                         icnt, interpret)
            s_depth = (geom.d_out, geom.d_out)
            rec_outs.append(parts[None, None])
        state = tuple(a[s_depth[0]:s_depth[0] + bx,
                        s_depth[1]:s_depth[1] + by] for a in state)
        return (*state, *rec_outs)

    return tile


def _param_frames(plan: DistTBPlan, frame: _Frame, params):
    """The time-invariant param fields in their frames: exchanged once per
    propagate at the main tiles' depth (the remainder tile reads the same
    frames, deeper in), with each physics' `param_fills` outside the
    domain and past the exchanged window, where the update must stay
    finite."""
    physics = plan.physics
    spec3 = P(plan.ax_x, plan.ax_y, None)
    npar = len(physics.param_fields)
    fills = dict(physics.param_fills)
    D = plan.halo
    dx, dy = frame.depth
    bx, by = plan.block

    @functools.partial(jax.shard_map, mesh=plan.mesh,
                       in_specs=(spec3,) * npar, out_specs=(spec3,) * npar)
    def prepare(*ps):
        out = []
        for f, p in zip(physics.param_fields, ps):
            pad = exchange_to_depth(p, D, frame.depth, plan.ax_x,
                                    plan.ax_y, frame=frame.shape)
            fill = fills.get(f, 0.0)
            if fill:
                # the domain's part of the exchanged window
                lo, hi, ylo, yhi = _dom_bounds(plan, frame.depth)
                inside = (jnp.maximum(lo, dx - D),
                          jnp.minimum(hi, dx + bx + D),
                          jnp.maximum(ylo, dy - D),
                          jnp.minimum(yhi, dy + by + D))
                ok = _dom_mask(inside, pad.shape, (0, 0), jnp.bool_)
                pad = jnp.where(ok, pad, jnp.asarray(fill, pad.dtype))
            out.append(pad)
        return tuple(out)

    with _spans.annotate("halo.setup_exchange", depth=D):
        return prepare(*[params[f] for f in physics.param_fields])


class _RidTab(NamedTuple):
    """The slice of a receiver table `ops.combine_rec_partials` reads."""

    rid: jnp.ndarray


def _combine_pass(parts, rid, nrec: int):
    """(px, py, ntl, T, capr, chan) shard partials + rid table ->
    (T, nrec, chan) per-step samples (segment sum over receiver ids)."""
    px, py, ntl, T, capr, chan = parts.shape
    flat = parts.reshape(px * py * ntl, 1, T, capr, chan)
    tab = _RidTab(rid=rid.reshape(px * py * ntl, capr))
    return ops_mod.combine_rec_partials(flat, tab, nrec)


def _propagate(plan: DistTBPlan, nt: int, state, params, g, tables,
               nrec: int, interpret: Optional[bool]):
    """The traced sharded propagate, after all host-side binning: a scan
    over the depth-T tiles plus the `nt % T` remainder tile, all reading
    the same param frames.  Returns (state tuple, recs (nt, nrec, chan))."""
    physics = plan.physics
    ns = len(physics.state_fields)
    nchan = physics.rec_channels
    dtype = state[0].dtype
    pframe, frames = _frames(plan, nt, state[0].shape[2])
    if g is not None:
        src_dcmp = g.src_dcmp
        scale_vec = jnp.asarray(
            physics.inject_scale(params, g, float(plan.dt)), jnp.float32)
    else:
        src_dcmp = jnp.zeros((max(nt, 1), 1), dtype)
        scale_vec = jnp.zeros((1,), jnp.float32)
    ppads = _param_frames(plan, pframe, params)

    def run(T_depth, state, t0):
        tabs = tables[T_depth]
        flat = [a for t in tabs for a in t[:6]]
        win = jax.lax.dynamic_slice(src_dcmp, (t0, 0),
                                    (T_depth, src_dcmp.shape[1]))
        outs = _tile(plan, frames[T_depth], pframe, T_depth, interpret)(
            *state, *ppads, *flat, win, scale_vec)
        if nrec == 0:
            return tuple(outs[:ns]), jnp.zeros((T_depth, 0, nchan), dtype)
        recs = [_combine_pass(p, t[6], nrec) for p, t in zip(outs[ns:], tabs)]
        return tuple(outs[:ns]), (recs[0] if len(recs) == 1
                                  else jnp.concatenate(recs, axis=0))

    n_main = nt // plan.T
    rem = nt - n_main * plan.T
    recs = []
    if n_main > 0:
        def body(carry, k):
            return run(plan.T, carry, k * plan.T)

        state, rec = jax.lax.scan(body, tuple(state), jnp.arange(n_main))
        recs.append(rec.reshape(n_main * plan.T, -1, nchan))
    if rem > 0:
        # the remainder tile nests the same way: passes of the SAME inner
        # depth (clamped when the remainder is shallower than one pass)
        state, rec = run(rem, state, n_main * plan.T)
        recs.append(rec)
    return state, (recs[0] if len(recs) == 1 else
                   jnp.concatenate(recs, axis=0))


def _check(plan: DistTBPlan, nt: int, state, g):
    plan.validate()
    physics = plan.physics
    if len(state) != len(physics.state_fields):
        raise ValueError(f"{physics.name} carries "
                         f"{len(physics.state_fields)} state fields, "
                         f"got {len(state)}")
    if g is not None and g.nt < nt:
        raise ValueError(f"source wavelets cover {g.nt} steps < nt={nt}")


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
    passes when `inner_plan.T < T`), overlapped exchange — comes from
    the plan and never changes results,
    only data movement (tested across all combinations).

    Returns (final state tuple, rec (nt, nrec, rec_channels) | None) with
    per-step receiver samples at any T (each shard records masked partials,
    segment-summed by receiver id across shards).

    Traceable: under the caller's jit the host-side table build (geometry
    only) runs at trace time and the param-dependent injection scale is
    gathered in-graph.  `sharded_propagate` is the entry point that owns
    the jit and the state's donation.
    """
    state = tuple(state)
    _check(plan, nt, state, g)
    tables = _host_tables(plan, nt, g, receivers,
                          jnp.dtype(state[0].dtype).itemsize)
    nrec = receivers.num if receivers is not None else 0
    state, recs = _propagate(plan, nt, state, params, g, tables, nrec,
                             interpret)
    return state, (recs if receivers is not None else None)


_propagate_jit = jax.jit(_propagate, static_argnums=(0, 1, 6, 7),
                         donate_argnums=(2,))


def sharded_lower(plan: DistTBPlan, nt: int, state, params,
                  g: Optional[src_mod.GriddedSources] = None,
                  receivers: Optional[src_mod.GriddedReceivers] = None,
                  *, interpret: Optional[bool] = None):
    """The program `sharded_propagate` runs, lowered for `state` and
    `params` (arrays or `jax.ShapeDtypeStruct`s with their shardings) and
    not run: dry runs and compile checks (`.compile().memory_analysis()`
    is the per-device footprint)."""
    state = tuple(state)
    _check(plan, nt, state, g)
    tables = _host_tables(plan, nt, g, receivers,
                          jnp.dtype(state[0].dtype).itemsize)
    nrec = receivers.num if receivers is not None else 0
    return _propagate_jit.lower(plan, nt, state, dict(params), g, tables,
                                nrec, interpret)


def sharded_propagate(plan: DistTBPlan, nt: int,
                      state: Tuple[jnp.ndarray, ...],
                      params: Dict[str, jnp.ndarray],
                      g: Optional[src_mod.GriddedSources] = None,
                      receivers: Optional[src_mod.GriddedReceivers] = None,
                      *, interpret: Optional[bool] = None):
    """The sharded layer's entry point, as `ops.acoustic_tb_propagate` is
    the one-chip one: host binning of the source and receiver tables,
    then one jitted propagate (compiled once per plan, nt and table
    shapes; the tables are its arguments, not constants) that DONATES
    `state`, so the final state takes the initial state's memory.  Pass
    state arrays nothing else holds, one buffer each.

    `state` and `params` are global (nx, ny, nz) arrays, best already
    sharded `P(plan.ax_x, plan.ax_y, None)` over `plan.mesh`; build the
    plan with `sharded_plan`.  Returns (final state tuple, rec (nt, nrec,
    rec_channels) | None), as `sharded_tb_propagate`.

    Spans: `halo.propagate` (synced on the result) holds `halo.dispatch`
    (`count_compiles`: the binning, and the jit's trace, lowering,
    compile or load and enqueue), which holds `halo.tables` with the
    layer's counters (`sharded_counts`)."""
    state = tuple(state)
    _check(plan, nt, state, g)
    nrec = receivers.num if receivers is not None else 0
    with _spans.span("halo.propagate", physics=plan.physics.name, nt=nt,
                     T=plan.T) as sp:
        with _spans.span("halo.dispatch", count_compiles=True):
            tables = _host_tables(plan, nt, g, receivers,
                                  jnp.dtype(state[0].dtype).itemsize)
            out = _propagate_jit(plan, nt, state, dict(params), g, tables,
                                 nrec, interpret)
        sp.sync(out)
    state, recs = out
    return state, (recs if receivers is not None else None)


def sharded_plan(mesh: Mesh, physics: phys.TBPhysics,
                 grid_shape: Tuple[int, int, int], order: int, dt: float,
                 spacing: Tuple[float, float, float],
                 ax_x: str = "data", ax_y: str = "model",
                 **planner) -> DistTBPlan:
    """The plan `sharded_propagate` runs on `mesh`: the joint autotuner's
    (`plan_hierarchy`) outer depth, inner tile and depth, and overlap for
    one shard's block; `planner` goes to the autotuner."""
    from repro.core.temporal_blocking import plan_hierarchy

    block = (grid_shape[0] // mesh.shape[ax_x],
             grid_shape[1] // mesh.shape[ax_y])
    hier, _ = plan_hierarchy(physics.name, grid_shape[2], order, block,
                             **planner)
    return dist_plan_from_hier(mesh, grid_shape, physics, order, hier, dt,
                               spacing, ax_x=ax_x, ax_y=ax_y)


def sharded_counts(plan: DistTBPlan, nt: int, itemsize: int = 4
                   ) -> Dict[str, object]:
    """Span attributes of the sharded layer's work, from the static plan.

    Per tile depth (the depth-T tiles, then the `nt % T` remainder):
    `update_points`, the points every shard's split first step (interior
    block and rim strips) and inner passes compute over all tiles and
    steps — the deep-halo rims plus the kernel's trapezoid
    (`stencil_tb.update_points`) — and `useful_points`, the global grid's
    points times the steps served.
    Per propagate: `exchange_bytes`, the bytes the shards send one another
    (state strips every tile, params once), and `exchanges`, the
    ppermute rounds that move them."""
    physics = plan.physics
    nx, ny, nz = plan.grid_shape
    px, py = plan.pgrid
    bx, by = plan.block
    r = plan.r_step

    def sent(d):
        """(bytes, ppermutes) of one field's depth-d exchange, all shards."""
        if d == 0:
            return 0, 0
        nbytes = (2 * (px - 1) * py * d * by
                  + 2 * px * (py - 1) * d * (bx + 2 * d)) * nz * itemsize
        return nbytes, 2 * (px > 1) + 2 * (py > 1)

    out = {"update_points": [], "useful_points": []}
    nbytes, rounds = 0, 0
    for d in (plan.halo,) * len(physics.param_fields):
        b, k = sent(d)
        nbytes, rounds = nbytes + b, rounds + k
    rem = nt % plan.T
    for T_depth, tiles in ((plan.T, nt // plan.T), (rem, 1 if rem else 0)):
        if tiles == 0:
            continue
        h = T_depth * r
        pts = 0
        if plan.overlap:
            band = h + 2 * r
            pts += (bx * by + 2 * band * (by + 2 * h)
                    + (2 * bx * band if bx > 2 * r else 0)) * nz
        for geom in _geoms(plan, T_depth):
            pts += ker.update_points(_pass_spec(plan, geom, nz, 1, 1,
                                                jnp.float32))
        out["update_points"].append(tiles * px * py * pts)
        out["useful_points"].append(nx * ny * nz * tiles * T_depth)
        for d in plan.field_depths(T_depth):
            b, k = sent(d)
            nbytes, rounds = nbytes + tiles * b, rounds + tiles * k
    out["exchange_bytes"] = nbytes
    out["exchanges"] = rounds
    return out
