"""Pallas TPU kernel: multi-field temporally-blocked stencil driver with
fused grid-aligned source injection and receiver interpolation.

This is the TPU-native realization of the paper's scheme (DESIGN.md §2),
generalized over physics: the same trapezoidal VMEM schedule advances the
isotropic acoustic (1 evolved field), TTI pseudo-acoustic (coupled p/r) and
isotropic elastic (9-field velocity-stress) propagators — the paper's full
§III evaluation matrix.  Everything physics-specific is a
`tb_physics.TBPhysics` step spec; this module owns only the schedule:

- The paper makes temporal blocking *legal* by aligning sparse off-the-grid
  operators to the grid (SM/SID/src_dcmp).  We consume exactly those
  structures, re-laid-out as per-(x,y)-tile tables
  (`sources.tile_source_tables`).
- The paper's wavefront schedule exploited Xeon L3 residency; here a spatial
  tile plus a `T*r_step`-deep halo is DMA'd HBM->VMEM once (one window per
  state/param field), advanced `T` timesteps entirely in VMEM
  (trapezoidal/overlapped time tiling), with the injection applied to the
  physics' inject fields at each in-VMEM step, and only the valid centre
  written back.  HBM traffic drops ~T-fold at the cost of redundant rim
  compute (`TBPlan.overlap_factor`).  `r_step` is the per-step halo
  consumption — order//2 for the acoustic Laplacian, order for elastic and
  TTI whose step chains two derivative passes (DESIGN.md §2).

Kernel layout
  grid = (ntx, nty) spatial tiles; one `pallas_call` per *time tile* of
  depth T (the outer `t_tile` loop of the paper's Listing 6 lives in
  `ops._tb_propagate`).

  inputs (ANY/HBM, manually DMA'd):   state fields then param fields,
                                      each padded by H = T*r_step
  inputs (SMEM, one block per tile):  flattened source/receiver tables,
                                      then the tile's live (source,
                                      receiver) counts, which bound its
                                      slot loops
  outputs (blocked):                  per-state-field centre regions;
                                      receiver partials, one
                                      (T*rec_channels, capr) vector per
                                      tile

Schedule (`step_slabs`): in-VMEM step k advances only the x-planes the
steps after it read, [r(k+1), wx - r(k+1)) — the trapezoid shrinks by
r = r_step planes a side per step — as a loop over x-slabs of b = 8
planes.  A slab applies the physics update to its b planes plus r on
each side (x is the untiled major dimension, so the slab is a cheap ref
slice) and keeps the middle b, so Mosaic's code and temporaries scale
with a slab, not the window.  y stays window-wide.

TPU notes: the z (minor) dimension is kept whole and should be a multiple
of 128; tiles (tx, ty) should be multiples of 8 (the window's y extent is
rounded up to 8 rows).  Scatter/gather of the sparse points is realized with
broadcasted-iota masks over the slab holding the point (predicated vector
ops — the VPU-friendly analogue of the paper's z-column nnz loop, see
DESIGN.md §2 table).  Parity runs in interpret mode on CPU
(tests/test_kernel_stencil_tb.py, tests/test_kernel_multiphysics.py) and
natively on the chip (chip_smoke.py); tests/test_tpu_compile.py compiles
it for a described v5e at 512^3.  `kernel_cost` below feeds the roofline
model.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import stencil as st
from repro.core.temporal_blocking import SLAB, VMEM_BUDGET
from repro.kernels import tb_physics as phys
from repro.kernels.platform import resolve_interpret


@dataclasses.dataclass(frozen=True)
class TBKernelSpec:
    """Static configuration of one temporally-blocked kernel call."""

    nx: int
    ny: int
    nz: int
    tile: Tuple[int, int]
    T: int                      # time-tile depth
    order: int                  # space order (radius = order // 2)
    dt: float
    spacing: Tuple[float, float, float]
    src_cap: int                # max sources per tile (padded)
    rec_cap: int                # max receiver gather entries per tile
    dtype: jnp.dtype = jnp.float32
    step_radius: Optional[int] = None   # per-step halo; None -> order // 2
    rec_channels: int = 1

    @property
    def radius(self) -> int:
        return self.order // 2

    @property
    def halo(self) -> int:
        r = self.radius if self.step_radius is None else self.step_radius
        return self.T * r

    @property
    def window(self) -> Tuple[int, int, int]:
        """VMEM window of one tile: the tile plus its halo, with y (the
        sublane dimension) rounded up to the 8-row tiling so the window
        DMA is aligned; the extra rows lie past the halo and never reach
        the centre."""
        wy = self.tile[1] + 2 * self.halo
        return (self.tile[0] + 2 * self.halo, -(-wy // 8) * 8, self.nz)

    @property
    def ntiles(self) -> Tuple[int, int]:
        tx, ty = self.tile
        if self.nx % tx or self.ny % ty:
            raise ValueError(
                f"grid ({self.nx},{self.ny}) must divide by tile {self.tile}")
        return (self.nx // tx, self.ny // ty)

    def vmem_bytes(self, nwindows: int = 6) -> int:
        """Resident bytes of `nwindows` window-sized VMEM buffers (a pair
        per state field and one per param field; 6 = the acoustic kernel's
        two (u_prev, u) sets plus m and damp)."""
        wx, wy, wz = self.window
        return wx * wy * wz * jnp.dtype(self.dtype).itemsize * nwindows


def step_slabs(spec: TBKernelSpec, k):
    """The x-schedule of in-VMEM step `k` (a Python int, or the kernel's
    traced step): `(lo, hi, b, nslab)`.

    Step k updates window planes [lo, hi) = [r(k+1), wx - r(k+1)), with
    r = step radius — the depth-T trapezoid, which is all the steps after
    it read — in `nslab` slabs of `b` planes (b is the same every step).
    Slab s keeps planes [x0, x0 + b) with x0 = lo + s*b, except that
    the last slab is shifted back to end at hi, and a range narrower than
    b is computed by one slab held inside [r, wx - r), where its reads
    stay in the window.  The kernel, `kernel_cost` and the
    `update_points` counter all count from here."""
    wx = spec.window[0]
    r = spec.halo // spec.T
    b = min(SLAB, wx - 2 * r)
    lo = r * (k + 1)
    hi = wx - lo
    return lo, hi, b, (hi - lo + b - 1) // b


def slab_steps(spec: TBKernelSpec) -> int:
    """Slabs one tile's call runs, over its T steps: the times the slot
    loops run, each for the tile's live entries."""
    return sum(step_slabs(spec, k)[3] for k in range(spec.T))


def update_points(spec: TBKernelSpec) -> int:
    """Points one call's updates keep: over tiles and steps, slabs x b x
    wy x nz.  A plane the shifted last slab keeps again counts twice.  (A
    slab's update also reads r_step planes a side, whose values it drops;
    on the TPU the kernel's time follows the kept planes, PERF.md §5.)"""
    ntx, nty = spec.ntiles
    _, wy, wz = spec.window
    b = step_slabs(spec, 0)[2]
    return ntx * nty * slab_steps(spec) * b * wy * wz


def _domain_mask(spec: TBKernelSpec, ti, tj, x0, nplanes: int, box=None):
    """1.0 inside the physical domain, 0.0 in the halo padding, over window
    planes [x0, x0 + nplanes) — enforces the Dirichlet boundary at every
    in-VMEM step (matches the oracle's zero-fill convention).  The domain
    is [0, nx) x [0, ny) of the kernel grid, or, with `box`, the
    [box[0], box[1]) x [box[2], box[3]) read from SMEM (a shard of a
    decomposed grid, whose domain edges depend on its offset)."""
    _, wy, wz = spec.window
    tx, ty = spec.tile
    h = spec.halo
    shape = (nplanes, wy, wz)
    gx = ti * tx - h + x0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    gy = tj * ty - h + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if box is None:
        ok = (gx >= 0) & (gx < spec.nx) & (gy >= 0) & (gy < spec.ny)
    else:
        ok = (gx >= box[0]) & (gx < box[1]) & (gy >= box[2]) & (gy < box[3])
    return ok.astype(spec.dtype)


def _point_mask(shape, x, y, z):
    """One-hot (broadcasted-iota) mask selecting window point (x, y, z)."""
    ix = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    iy = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    iz = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    return (ix == x) & (iy == y) & (iz == z)


def _tb_kernel(spec: TBKernelSpec, physics: phys.TBPhysics,
               origins: Tuple[Tuple[int, int], ...], has_box: bool, *refs):
    """Generic multi-field TB kernel body.

    Ref layout (positional, in pallas_call order):
      inputs:  n_state + n_param HBM refs, then this tile's src_coords,
               src_vals, rec_coords, rec_w and counts rows, flattened
               (1, 1, n) blocks in SMEM (+ the (4,) domain box in SMEM
               when `has_box`)
      outputs: n_state centre refs, then rec partials (1, T*chan, capr)
      scratch: one VMEM window per HBM ref — a pair of them per state
               field, between which the step loop ping-pongs — then a DMA
               semaphore array

    Tile (ti, tj)'s window starts at (ti*tx, tj*ty) of an HBM operand,
    offset by that operand's static `origins` entry.  A param window whose
    y origin is off the 8-row tiling is DMA'd from the tiling row below
    it (8 rows taller) and read at that row offset.

    Each in-VMEM step runs as a loop over the x-slabs `step_slabs` gives
    it: the physics update is applied to `b + 2*r_step` window planes and
    its middle `b` planes are kept.  The update is local with radius
    r_step, so the kept planes are exactly what a whole-window update
    computes there.  Step k's slabs cover [r_step*(k+1), wx -
    r_step*(k+1)), which is all that step k+1 reads; planes outside it
    keep stale values that nothing the centre depends on reads.

    `has_box` and `origins` are how the sharded execution layer reuses
    this kernel unchanged (DESIGN.md §4): on a single device the domain
    is the spec's grid, but on a shard of a decomposed grid it depends on
    the shard's global offset, so the caller supplies its edges; and the
    shard's operands are frames larger than the kernel grid's padding,
    read in place at an offset.
    """
    ns = len(physics.state_fields)
    nw = physics.num_windows
    hbm = refs[:nw]
    src_coords_ref, src_vals_ref, rec_coords_ref, rec_w_ref, counts_ref = \
        refs[nw:nw + 5]
    nin = nw + 5 + has_box
    box = ([refs[nw + 5][i] for i in range(4)] if has_box else None)
    out_refs = refs[nin:nin + ns]
    rec_out_ref = refs[nin + ns]
    wins = refs[nin + ns + 1:nin + ns + 1 + nw]
    sems = refs[nin + ns + 1 + nw]

    ti = pl.program_id(0)
    tj = pl.program_id(1)
    tx, ty = spec.tile
    wx, wy, wz = spec.window
    h = spec.halo
    r = spec.halo // spec.T
    nch = physics.rec_channels

    # ---- DMA one window per field HBM -> VMEM ------------------------------
    # (state windows land in slot 0 of their (2, wx, wy, wz) pair)
    shifts = [oy % 8 for _, oy in origins]

    def win(i):
        ox, oy = origins[i]
        oy -= shifts[i]
        return hbm[i].at[pl.ds(ti * tx + ox if ox else ti * tx, wx),
                         pl.ds(tj * ty + oy if oy else tj * ty,
                               wins[i].shape[-2]), :]

    copies = [pltpu.make_async_copy(
        win(i), wins[i].at[0] if i < ns else wins[i], sems.at[i])
        for i in range(nw)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    states, par_refs = wins[:ns], wins[ns:]
    rec_out_ref[...] = jnp.zeros(rec_out_ref.shape, rec_out_ref.dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, (spec.T * nch, spec.rec_cap),
                                    0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (spec.T * nch, spec.rec_cap),
                                     1)
    inj = [physics.state_fields.index(f) for f in physics.inject_fields]
    # the tile's live slots are the prefix 0..n-1 of its table rows
    # (`tables.pack_slots`): the slot loops run those alone
    n_src = jnp.minimum(counts_ref[0, 0, 0], spec.src_cap)
    n_rec = jnp.minimum(counts_ref[0, 0, 1], spec.rec_cap)

    # ---- T in-VMEM timesteps: step k reads slot k % 2, writes the other ----
    def step(k, carry):
        cur = [st.at[k % 2] for st in states]
        nxt = [st.at[1 - k % 2] for st in states]
        lo_k, hi_k, b, nslab = step_slabs(spec, k)

        def slab(s, carry):
            lo = lo_k + s * b                    # first plane this slab owns
            # first plane it computes (`step_slabs`)
            x0 = jnp.maximum(jnp.minimum(lo, hi_k - b), r)
            rd = pl.ds(x0 - r, b + 2 * r)
            st_in = {f: cur[i][rd] for i, f in enumerate(physics.state_fields)}
            pr_in = {f: (par_refs[i][rd, pl.ds(shifts[ns + i], wy)]
                         if shifts[ns + i] else par_refs[i][rd])
                     for i, f in enumerate(physics.param_fields)}
            dom = _domain_mask(spec, ti, tj, x0 - r, b + 2 * r, box)
            new = physics.update(st_in, pr_in, spec, lambda a: a * dom)
            # (computed, not sliced out of `dom`: Mosaic's strided-slice
            # rule mis-handles the iota mask's lane-replicated layout)
            dom_c = _domain_mask(spec, ti, tj, x0, b, box)
            for i, f in enumerate(physics.state_fields):
                v = new[f][r:r + b]
                if f in physics.evolved_fields and \
                        f not in physics.premasked_fields:
                    v = v * dom_c
                nxt[i][pl.ds(x0, b)] = v.astype(spec.dtype)

            # fused grid-aligned source injection (paper Listing 4/5 ->
            # masked vector adds) over the tile's live sources
            def inject(p, c):
                x = src_coords_ref[0, 0, 3 * p] - x0

                @pl.when((x >= 0) & (x < b))
                def _():
                    val = src_vals_ref[0, 0, k * spec.src_cap + p]
                    mask = _point_mask((b, wy, wz), x,
                                       src_coords_ref[0, 0, 3 * p + 1],
                                       src_coords_ref[0, 0, 3 * p + 2])
                    add = jnp.where(mask, val, 0.0).astype(spec.dtype)
                    for i in inj:
                        nxt[i][pl.ds(x0, b)] = nxt[i][pl.ds(x0, b)] + add
                return c

            jax.lax.fori_loop(0, n_src, inject, 0)

            # fused receiver interpolation partials (paper Fig. 3b); a
            # plane recomputed by the shifted last slab is owned by the
            # slab before it, so every entry is sampled exactly once
            rec_arrays = physics.record(
                {f: nxt[i][pl.ds(x0, b)]
                 for i, f in enumerate(physics.state_fields)})

            def record(p, c):
                x = rec_coords_ref[0, 0, 3 * p] - x0

                @pl.when((x >= lo - x0) & (x < b))
                def _():
                    w = rec_w_ref[0, 0, p]
                    mask = _point_mask((b, wy, wz), x,
                                       rec_coords_ref[0, 0, 3 * p + 1],
                                       rec_coords_ref[0, 0, 3 * p + 2])
                    acc = rec_out_ref[0]
                    for ch, arr in enumerate(rec_arrays):
                        smp = jnp.sum(jnp.where(mask, arr, 0.0), axis=0)
                        smp = jnp.sum(jnp.sum(smp, axis=0, keepdims=True),
                                      axis=1, keepdims=True)
                        acc = jnp.where((rows == k * nch + ch) & (lanes == p),
                                        (w * smp).astype(spec.dtype), acc)
                    rec_out_ref[0] = acc
                return c

            jax.lax.fori_loop(0, n_rec, record, 0)
            return carry

        jax.lax.fori_loop(0, nslab, slab, 0)
        return carry

    jax.lax.fori_loop(0, spec.T, step, 0)

    # ---- write back the valid centre ---------------------------------------
    for i in range(ns):
        out_refs[i][...] = states[i][spec.T % 2, h:h + tx, h:h + ty, :]


def tb_time_tile(spec: TBKernelSpec, physics: phys.TBPhysics,
                 state_pads, param_pads,
                 src_coords, src_vals, rec_coords, rec_w, counts,
                 *, origins=None, dom_box=None,
                 interpret: Optional[bool] = None):
    """One depth-T time tile over the whole grid (one pallas_call).

    Args:
      state_pads: one (nx + 2H, ny + 2H, nz) array per physics.state_fields
                  (zero-padded).
      param_pads: one padded array per physics.param_fields (edge-padded).
      src_coords: (ntiles, cap, 3) window-local int32.
      src_vals:   (ntiles, T, cap) f32, scale folded in, 0 on padding.
      rec_coords: (ntiles, capr, 3); rec_w: (ntiles, capr).
      counts:     (ntiles, 2) int32: each tile's live (source, receiver)
                  entries, which fill slots 0..n-1 of its rows
                  (`TileSourceTable.nnz`, `TileReceiverTable.nnz`).  The
                  kernel's inject and record loops run that many
                  iterations, so empty slots cost nothing; a slot past
                  the count is never read.
      origins:    optional static (ox, oy) per state then param operand:
                  where that operand's (nx + 2H, ny + 2H) padded grid
                  starts inside it, so a larger array is read in place
                  (the sharded execution layer's frames, distributed/
                  halo.py); None is (0, 0) for every operand.  A state
                  operand's oy must lie on the 8-row tiling.
      dom_box:    optional (4,) int32 [x_lo, x_hi, y_lo, y_hi]: the
                  physical domain in kernel-grid coordinates, overriding
                  [0, nx) x [0, ny) — used when this kernel runs on one
                  shard of a decomposed grid, where "inside the physical
                  domain" depends on the shard offset.  A traced value,
                  read from SMEM; the mask stays an iota predicate, so no
                  grid-sized mask exists in HBM or VMEM.  The time-nested
                  schedule issues one call PER PASS with the spec's
                  grid/halo parameterized by the remaining exchange depth
                  (`ops.pass_inner_spec`: grid = block + 2*d_out rounded
                  up to the tile, halo = inner_T * r_step).
      interpret:  None picks by backend (`platform.resolve_interpret`).

    Each tile's rows of the four tables and its counts reach SMEM as
    flattened blocks: the kernel reads them one scalar at a time, and
    SMEM pads only a block's minor dimension.  Natively the call takes
    no batch axis: Mosaic lowers none on the manually DMA'd (ANY)
    operands, so a caller with many shots loops over them
    (`jax.lax.map`, as the survey engine does) instead of `jax.vmap`.
    Returns (new_states tuple, rec_partials) with fields (nx, ny, nz) and
    rec_partials (ntx, nty, T, capr, rec_channels).
    """
    ntx, nty = spec.ntiles
    tables = (src_coords.shape, src_vals.shape, rec_coords.shape, rec_w.shape,
              counts.shape)
    want = ((ntx * nty, spec.src_cap, 3), (ntx * nty, spec.T, spec.src_cap),
            (ntx * nty, spec.rec_cap, 3), (ntx * nty, spec.rec_cap),
            (ntx * nty, 2))
    if tables != want:
        raise ValueError(f"tile tables {tables} do not match the spec's "
                         f"{want}")
    tables = (src_coords, src_vals, rec_coords, rec_w, counts)
    ns = len(physics.state_fields)
    nw = physics.num_windows
    wx, wy, wz = spec.window
    nch = physics.rec_channels
    origins = tuple(origins) if origins is not None else ((0, 0),) * nw
    if any(oy % 8 for _, oy in origins[:ns]):
        raise ValueError(f"state origins {origins[:ns]} must start on the "
                         f"8-row tiling in y")
    # a param window off the tiling is read from the row below, 8 taller
    rows_y = [wy + (8 if oy % 8 else 0) for _, oy in origins]
    has_box = dom_box is not None
    kern = functools.partial(_tb_kernel, spec, physics, origins, has_box)
    # the last tile row's window reaches past the halo when y is rounded
    # up to the tiling; edge values there stay finite and off the centre
    hbm = []
    for a, (ox, oy), ry in zip((*state_pads, *param_pads), origins, rows_y):
        if ox + (ntx - 1) * spec.tile[0] + wx > a.shape[0]:
            raise ValueError(f"operand {a.shape} at origin {(ox, oy)} "
                             f"does not hold the kernel grid's windows")
        extra = oy - oy % 8 + (nty - 1) * spec.tile[1] + ry - a.shape[1]
        if extra > 0:
            a = jnp.pad(a, ((0, 0), (0, extra), (0, 0)), mode="edge")
        hbm.append(a)
    tile_idx = lambda i, j: (i * nty + j, 0, 0)  # noqa: E731
    # one flattened row per tile, so the SMEM block pads only its length
    rows = [t.astype(dt).reshape(ntx * nty, 1, -1)
            for t, dt in zip(tables, (jnp.int32, jnp.float32, jnp.int32,
                                      jnp.float32, jnp.int32))]

    rec_shape = (ntx * nty, spec.T * nch, spec.rec_cap)
    boxes = [jnp.asarray(dom_box, jnp.int32)] if has_box else []
    outs = pl.pallas_call(
        kern,
        grid=(ntx, nty),
        in_specs=(
            [pl.BlockSpec(memory_space=pl.ANY)] * nw
            + [pl.BlockSpec((1, 1, t.shape[2]), tile_idx,
                            memory_space=pltpu.SMEM) for t in rows]
            + [pl.BlockSpec(memory_space=pltpu.SMEM)] * has_box
        ),
        out_specs=(
            [pl.BlockSpec((spec.tile[0], spec.tile[1], spec.nz),
                          lambda i, j: (i, j, 0))] * ns
            + [pl.BlockSpec((1,) + rec_shape[1:], tile_idx)]
        ),
        out_shape=(
            [jax.ShapeDtypeStruct((spec.nx, spec.ny, spec.nz),
                                  spec.dtype)] * ns
            + [jax.ShapeDtypeStruct(rec_shape, spec.dtype)]
        ),
        scratch_shapes=(
            [pltpu.VMEM((2, wx, wy, wz), spec.dtype)] * ns
            + [pltpu.VMEM((wx, ry, wz), spec.dtype) for ry in rows_y[ns:]]
            + [pltpu.SemaphoreType.DMA((nw,))]
        ),
        # the budget the autotuner planned this tile against
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET),
        interpret=resolve_interpret(interpret),
        # a stable name for the kernel's events on the device trace
        name="tb_time_tile",
    )(*hbm, *rows, *boxes)
    rec = outs[ns].reshape(ntx, nty, spec.T, nch, spec.rec_cap)
    return tuple(outs[:ns]), jnp.swapaxes(rec, 3, 4)


def kernel_cost(spec: TBKernelSpec,
                physics: phys.TBPhysics = phys.ACOUSTIC) -> dict:
    """Analytic per-call cost of the kernel (feeds §Roofline / benchmarks).

    Reads one window per state+param field, writes back the centre of every
    state field.  Stencil flops count what the steps compute:
    `update_points` (the `step_slabs` schedule) at the physics' flops per
    point; sparse-term flops are the masked vector adds of the fused
    injection/interpolation over the one slab that holds each entry.
    `vmem_bytes` is the resident windows, a pair per state field.
    """
    ntx, nty = spec.ntiles
    wx, wy, wz = spec.window
    b = step_slabs(spec, 0)[2]
    if physics.name == "acoustic":
        stencil_flops = st.stencil_flops_per_point(spec.order, 3) + 9
    else:
        from repro.core.propagators import elastic, tti
        mod = {"elastic": elastic, "tti": tti}[physics.name]
        stencil_flops = mod.model_flops_per_step((1, 1, 1), spec.order)
    window_pts = wx * wy * wz
    stencil_pts = update_points(spec)
    sparse_flops = (len(physics.inject_fields) * spec.src_cap
                    + 2 * physics.rec_channels * spec.rec_cap) * b * wy * wz
    flops = (stencil_pts * stencil_flops
             + ntx * nty * spec.T * sparse_flops)
    itemsize = jnp.dtype(spec.dtype).itemsize
    nw = physics.num_windows
    ns = len(physics.state_fields)
    hbm_read = ntx * nty * window_pts * nw * itemsize
    hbm_write = spec.nx * spec.ny * spec.nz * ns * itemsize
    return {"flops": float(flops),
            "stencil_points": stencil_pts,
            "hbm_bytes": float(hbm_read + hbm_write),
            "useful_flops": float(spec.nx * spec.ny * spec.nz * spec.T
                                  * stencil_flops),
            "vmem_bytes": spec.vmem_bytes(nw + ns)}
