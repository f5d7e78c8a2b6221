"""Temporal blocking schedules (paper §II.B, adapted to TPU — DESIGN.md §2).

Three layers:

1. `TimeTileSchedule` — splits the nt-step time loop into depth-T tiles
   (the outer `t_tile` loop of the paper's Listing 6).
2. `tiled_propagate` — a generic driver that runs any per-timestep `step_fn`
   tile-by-tile (scan over tiles, unrolled/fori inner loop).  On a single
   device this is mathematically identical to the naive scan — the paper's
   correctness contract — while giving the compiler the tile structure the
   Pallas kernel and the distributed deep-halo exchange exploit.
3. Analytical HBM-traffic/overlap models for the trapezoidal VMEM schedule —
   the TPU replacement for the paper's cache-aware roofline reasoning, used
   by the autotuner (`benchmarks/table1_autotune.py`) and §Roofline — plus
   the interconnect term of the sharded outer trapezoid (exchange bytes and
   latency per depth-T tile, DESIGN.md §4), which makes `plan_for_physics`
   mesh-aware via `mesh_block`/`link_bw`/`link_latency`.  With a mesh
   block the sweep is the JOINT two-level search (`plan_hierarchy` →
   `HierPlan`): inner Pallas tile (VMEM window) x outer exchange depth
   (per-field exchange bytes/latency) x overlapped-vs-serialized exchange.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class TBPassGeom(NamedTuple):
    """Geometry of ONE inner pass of the time-nested schedule (DESIGN.md §4).

    A depth-`T_outer` exchange tile no longer has to be consumed by one
    inner kernel call: the inner executor runs passes of depth <= inner T,
    each consuming `T * r` of the remaining exchanged halo, so the VMEM
    window is sized by the INNER depth while the exchange is amortized at
    the OUTER depth.  Pass p advances the block-plus-remaining-halo region
    (block + 2*d_out per side after the pass) and its kernel grid is that
    region rounded up to the inner tile (`grid`); the round-up band reads
    zero-padded garbage that the trapezoid crops.

    T:        timesteps this pass advances (= inner T, shallower on the
              last pass when inner T does not divide the step count).
    t0:       step offset of the pass within the inner-executor segment.
    d_in:     halo depth of the incoming state (= d_out + T*r).
    d_out:    halo depth still valid after the pass (0 on the last pass).
    halo:     per-pass window overhang (T*r).
    grid:     kernel grid = block + 2*d_out rounded up to the tile.
    tile:     spatial tile of the pass (the inner Pallas tile).
    ntiles:   grid / tile.
    include_halo: whether source tables must duplicate points into every
              window containing them (T > 1: intermediate in-pass steps
              read injected halo values — paper Fig. 4b).
    """

    T: int
    t0: int
    d_in: int
    d_out: int
    halo: int
    grid: Tuple[int, int]
    tile: Tuple[int, int]
    ntiles: Tuple[int, int]
    include_halo: bool


def nested_pass_geometry(block: Tuple[int, int], tile: Tuple[int, int],
                         T_steps: int, inner_T: int, r: int
                         ) -> List[TBPassGeom]:
    """Split `T_steps` in-tile steps into inner passes of depth <= inner_T.

    The pass depths telescope through the exchanged halo: pass p enters at
    depth `d_in = (T_steps - t0) * r` and leaves `d_out = d_in - T*r`
    valid, so the last pass lands exactly on the shard block.  `inner_T ==
    T_steps` reproduces the flat single-pass schedule.  `inner_T` need not
    divide `T_steps` (the remainder tile of `nt % T_outer` reuses the same
    chunking); the final pass just runs shallower.
    """
    if T_steps < 0 or inner_T < 1:
        raise ValueError(f"need T_steps >= 0 and inner_T >= 1, got "
                         f"({T_steps}, {inner_T})")
    bx, by = block
    tx, ty = tile
    geoms = []
    done = 0
    while done < T_steps:
        Tp = min(inner_T, T_steps - done)
        d_out = (T_steps - done - Tp) * r
        cx = -(-(bx + 2 * d_out) // tx) * tx
        cy = -(-(by + 2 * d_out) // ty) * ty
        geoms.append(TBPassGeom(
            T=Tp, t0=done, d_in=d_out + Tp * r, d_out=d_out, halo=Tp * r,
            grid=(cx, cy), tile=(tx, ty), ntiles=(cx // tx, cy // ty),
            include_halo=Tp > 1))
        done += Tp
    return geoms


@dataclasses.dataclass(frozen=True)
class TimeTileSchedule:
    """nt timesteps split into ceil(nt/T) tiles of depth <= T."""

    nt: int
    T: int

    def __post_init__(self):
        if self.T < 1:
            raise ValueError("time tile depth must be >= 1")

    @property
    def num_tiles(self) -> int:
        return -(-self.nt // self.T)

    @property
    def padded_nt(self) -> int:
        return self.num_tiles * self.T

    def tile_starts(self) -> np.ndarray:
        return np.arange(self.num_tiles) * self.T


def tiled_propagate(step_fn: Callable, nt: int, T: int, state,
                    per_step_out: Callable = None):
    """Run `state = step_fn(state, t)` for t in [0, nt) in depth-T time tiles.

    `per_step_out(state, t)` optionally collects a per-timestep output (e.g.
    receiver samples); outputs for padded steps (t >= nt) are masked to zero
    and the state update is suppressed, so results are independent of T.
    Returns (final_state, outs) with outs stacked over the padded time axis
    and then truncated to nt.
    """
    sched = TimeTileSchedule(nt, T)

    def one_step(carry, t):
        nxt = step_fn(carry, t)
        valid = t < nt
        nxt = jax.tree_util.tree_map(
            lambda a, b: jnp.where(valid, a, b), nxt, carry)
        if per_step_out is not None:
            out = per_step_out(nxt, t)
            out = jax.tree_util.tree_map(
                lambda o: jnp.where(valid, o, jnp.zeros_like(o)), out)
        else:
            out = ()
        return nxt, out

    def one_tile(carry, tile_idx):
        t0 = tile_idx * T
        ts = t0 + jnp.arange(T)
        carry, outs = jax.lax.scan(one_step, carry, ts)
        return carry, outs

    final, outs = jax.lax.scan(one_tile, state, jnp.arange(sched.num_tiles))
    if per_step_out is not None:
        outs = jax.tree_util.tree_map(
            lambda o: o.reshape((sched.padded_nt,) + o.shape[2:])[:nt], outs)
    else:
        outs = None
    return final, outs


# ---------------------------------------------------------------------------
# Trapezoidal VMEM time-tiling cost model (DESIGN.md §2)
# ---------------------------------------------------------------------------

# Scoped-VMEM budget of one TB kernel call on a TPU v5e (128 MiB physical
# VMEM per core); the autotuner plans against it and the kernel hands it to
# Mosaic as `vmem_limit_bytes`, so a plan that fits here compiles there.
VMEM_BUDGET = 96 * 2 ** 20
# x-planes the kernel's in-VMEM step advances per slab (`stencil_tb`)
SLAB = 8


@dataclasses.dataclass(frozen=True)
class TBPlan:
    """A (tile_x, tile_y, T) choice for the Pallas TB kernel."""

    tile: Tuple[int, int]
    T: int
    radius: int

    def to_dict(self) -> dict:
        """JSON-safe form (the survey plan cache's on-disk format)."""
        return {"tile": [int(t) for t in self.tile], "T": int(self.T),
                "radius": int(self.radius)}

    @classmethod
    def from_dict(cls, d: dict) -> "TBPlan":
        return cls(tile=tuple(int(t) for t in d["tile"]), T=int(d["T"]),
                   radius=int(d["radius"]))

    @property
    def halo(self) -> int:
        return self.T * self.radius

    def window(self, nz: int) -> Tuple[int, int, int]:
        tx, ty = self.tile
        return (tx + 2 * self.halo, ty + 2 * self.halo, nz)

    def overlap_factor(self) -> float:
        """Redundant-compute multiplier of the trapezoid: window area over
        tile area, averaged over the T steps actually computed.

        Step k computes the window shrunk by k*r per side (we only need
        values valid for the final centre), so compute per point-step is
        sum_k prod_d (tile_d + 2*(T-k)*r) / (T * prod_d tile_d)."""
        tx, ty = self.tile
        r = self.radius
        tot = 0.0
        for k in range(self.T):
            m = (self.T - k) * r
            tot += (tx + 2 * m) * (ty + 2 * m)
        return tot / (self.T * tx * ty)

    def vmem_bytes(self, nz: int, fields: int, dtype_bytes: int = 4) -> int:
        """Resident bytes: `fields` window-sized buffers.

        `fields` is deliberately required: the historical default of 5 was
        the acoustic kernel's window count (u0, u1, m, damp, scratch) and
        silently mis-budgeted TTI (11 windows) and elastic (14).  Callers
        take the count from `PHYSICS_COSTS[physics].fields`."""
        wx, wy, wz = self.window(nz)
        return wx * wy * wz * dtype_bytes * fields

    def kernel_vmem_bytes(self, nz: int, fields: int, out_fields: int,
                          slab_fields: int, dtype_bytes: int = 4) -> int:
        """Scoped VMEM the Pallas kernel (`stencil_tb._tb_kernel`) needs
        for one tile: `fields` resident windows, the double-buffered
        centre blocks of the `out_fields` written back, and the live
        temporaries of one x-slab update (`SLAB` + 2*radius planes),
        budgeted at one slab-sized array per field it reads
        (`slab_fields`) plus three, plus one per two points of radius.
        That last term bounds what Mosaic allocated for the autotuned
        plans at 512^3 on a v5e, which grows with the radius: about 4.4
        slab-sized arrays for acoustic SO-4, 9.4 for elastic SO-4, and
        12.5, 13.3 and 15.4 for TTI at SO-4, 8 and 12 (radius 4, 8,
        12)."""
        wx, wy, _ = self.window(nz)
        wy = -(-wy // 8) * 8            # the kernel's window is 8-row aligned
        tx, ty = self.tile
        slab = min(SLAB + 2 * self.radius, wx)
        temps = slab_fields + 3 + self.radius // 2
        planes = (fields * wx * wy + 2 * out_fields * tx * ty
                  + temps * slab * wy)
        return planes * nz * dtype_bytes

    def hbm_bytes_per_point_step(self, nz: int, read_fields: int = 4,
                                 write_fields: int = 1,
                                 dtype_bytes: int = 4) -> float:
        """HBM bytes moved per grid-point-timestep: the window is read and
        the centre written once per T steps."""
        tx, ty = self.tile
        wx, wy, _ = self.window(nz)
        read = wx * wy * nz * read_fields * dtype_bytes
        write = tx * ty * nz * write_fields * dtype_bytes
        return (read + write) / (tx * ty * nz * self.T)

    # --- time-nested pricing (inner T | outer T, DESIGN.md §4) --------------

    def nested_compute_multiplier(self, block: Tuple[int, int],
                                  outer_T: int) -> float:
        """Redundant-compute multiplier of the time-nested schedule: this
        plan's depth-T passes consume a depth-`outer_T*radius` exchanged
        halo (`nested_pass_geometry`), so each pass pays its own trapezoid
        overlap AND the still-valid outer rim it must keep advancing
        (shrinking by T*radius per pass).  `outer_T == self.T` with a
        block-dividing tile collapses to `overlap_factor()` — the flat
        schedule."""
        bx, by = block
        tot = 0.0
        for p in nested_pass_geometry(block, self.tile, outer_T, self.T,
                                      self.radius):
            inner = TBPlan(self.tile, p.T, self.radius)
            tot += inner.overlap_factor() * p.grid[0] * p.grid[1] * p.T
        return tot / (bx * by * outer_T)

    def nested_hbm_bytes_per_point_step(self, block: Tuple[int, int],
                                        outer_T: int, nz: int,
                                        read_fields: int = 4,
                                        write_fields: int = 1,
                                        dtype_bytes: int = 4) -> float:
        """HBM traffic of the time-nested schedule per block-point-step:
        every pass re-reads its windows and writes back its (still rim-
        extended) centre, so traffic is the per-pass flat traffic scaled
        by the pass grid and averaged over the outer depth."""
        bx, by = block
        tot = 0.0
        for p in nested_pass_geometry(block, self.tile, outer_T, self.T,
                                      self.radius):
            inner = TBPlan(self.tile, p.T, self.radius)
            tot += inner.hbm_bytes_per_point_step(
                nz, read_fields=read_fields, write_fields=write_fields,
                dtype_bytes=dtype_bytes) * p.grid[0] * p.grid[1] * p.T
        return tot / (bx * by * outer_T)

    # --- interconnect terms (the outer trapezoid of DESIGN.md §4) -----------

    def exchange_bytes_per_tile(self, block: Tuple[int, int], nz: int,
                                fields: int = 1,
                                dtype_bytes: int = 4,
                                depths: Tuple[int, ...] = None) -> int:
        """Bytes a shard with local block (bx, by) sends per depth-T time
        tile: the x exchange moves two (d, by, nz) strips, the y exchange
        two (bx + 2d, d, nz) strips of the already-x-padded block (corners
        ride the second hop), per exchanged field.

        `depths` (optional) gives a per-field exchange depth instead of the
        uniform `halo` — the elastic/TTI per-field-halo saving (DESIGN.md
        §4): fields only read pointwise at the rim ship a shallower strip
        (`TBPhysics.field_halo_depths`); `fields` is ignored when given."""
        bx, by = block
        if depths is None:
            depths = (self.halo,) * fields
        return sum(2 * d * nz * (by + bx + 2 * d) * dtype_bytes
                   for d in depths)

    def exchange_seconds_per_point_step(self, block: Tuple[int, int],
                                        nz: int, fields: int,
                                        link_bw: float,
                                        link_latency: float,
                                        dtype_bytes: int = 4,
                                        depths: Tuple[int, ...] = None
                                        ) -> float:
        """Interconnect time per grid-point-timestep of one shard: one deep
        exchange (4 ppermute shifts per field: 2 axes x 2 directions)
        amortized over the T steps it buys — the multi-chip analogue of
        `hbm_bytes_per_point_step`.  Deeper T trades a linear growth in rim
        bytes against a 1/T drop in per-exchange latency.  With per-field
        `depths`, zero-depth fields skip their ppermute rounds entirely."""
        bx, by = block
        byts = self.exchange_bytes_per_tile(block, nz, fields, dtype_bytes,
                                            depths=depths)
        n_exchanged = (fields if depths is None
                       else sum(1 for d in depths if d > 0))
        coll = 4 * n_exchanged * link_latency
        return (byts / link_bw + coll) / (bx * by * nz * self.T)

    def split_step_overhead_per_point_step(self, block: Tuple[int, int],
                                           nz: int, r_step: int,
                                           flops_per_point: float,
                                           peak_flops: float) -> float:
        """Extra redundant compute of the overlapped exchange (DESIGN.md
        §4): the first in-tile step is split into an interior update (runs
        while the ppermute is in flight) plus four rim strips of width
        `halo + 2*r_step` recomputed once the halo lands.  The strips are
        the overlap's price; this returns their cost per point-step."""
        bx, by = block
        h = self.halo
        band = h + 2 * r_step
        strip_pts = 2 * band * ((bx + 2 * h) + (by + 2 * h)) * nz
        return strip_pts * flops_per_point / (peak_flops * bx * by * nz
                                              * self.T)


class SweepLog(dict):
    """The autotune sweep log: a plain {key: entry} dict plus `best_key`,
    the key the sweep's own strict-< argmin selected — so downstream
    consumers (`plan_hierarchy`) never re-derive the winner with their
    own, potentially divergent, tie-breaking."""

    best_key = None


def autotune_plan(nz: int, radius: int, vmem_budget: int = VMEM_BUDGET,
                  tiles=(16, 32, 64, 128, 256), depths=(1, 2, 4, 8, 16),
                  fields: int = 6, dtype_bytes: int = 4,
                  flops_per_point: float = 40.0,
                  read_fields: int = 4, write_fields: int = 1,
                  peak_flops: float = 197e12, hbm_bw: float = 819e9,
                  mesh_block: Tuple[int, int] = None,
                  link_bw: float = 45e9, link_latency: float = 1.5e-6,
                  exchange_fields: int = None,
                  exchange_lags: Tuple[int, ...] = None,
                  sweep_overlap: bool = False,
                  outer_depths: Tuple[int, ...] = None,
                  ) -> Tuple[TBPlan, dict]:
    """Pick (tile, T[, outer T, overlap]) minimizing modeled time per
    point-step under the VMEM cap — the TPU collapse of the paper's
    Table-I autotuning sweep, extended to the two-level sharded hierarchy
    (DESIGN.md §4).

    Single-device terms:
      compute      = overlap_factor * flops_per_point / peak_flops
      memory       = hbm_bytes_per_point_step / hbm_bw

    With `mesh_block` the sweep becomes the JOINT two-level search: the
    candidate tile is the *inner* Pallas tile (VMEM window priced at this
    level; tiles that don't divide the per-device block, or halos deeper
    than the block, are infeasible), while the exchange term prices the
    *outer* per-shard trapezoid (one depth-T*radius ppermute round per
    tile over blocks of (bx, by)):

      serialized   = max(compute, memory) + comm        (exchange blocks
                     the tile's compute — the non-overlapped schedule)
      overlapped   = max(max(compute, memory), comm) + split_overhead
                     (the first in-tile step splits into interior + rim
                     strips so the ppermute hides behind the interior;
                     the strips are redundant compute — only swept when
                     `sweep_overlap`)

    With `outer_depths` (requires `mesh_block`) the two TIME levels
    decouple: every candidate (tile, T) is the INNER plan (VMEM window and
    per-pass trapezoid priced at depth T) and every `T_out` in
    `outer_depths` with `T_out % T == 0` is a candidate EXCHANGE depth —
    `T_out / T` inner passes consume one depth-`T_out*radius` exchange
    over shrinking windows (`nested_pass_geometry`), so
    compute/memory use the nested multipliers while the exchange bytes
    and latency amortize over `T_out`.  Log keys become
    `(tx, ty, T, T_out)` and entries carry `outer_T`/`vmem_bytes`;
    `T_out == T` reproduces the flat joint sweep exactly.

    T=1 (no temporal blocking) is in the sweep, so kernels where TB cannot
    win (high space order: overlap growth beats traffic savings — the
    paper's SO-12 result) autotune back to the spatially-blocked schedule.
    A latency-dominated interconnect pushes toward deep T (fewer
    exchanges) while a bandwidth-starved one pushes back to shallow T (rim
    bytes grow with the exchange depth) — the multi-chip analogue of the
    same trade; a tight VMEM budget under a latency-dominated link is
    where the NESTED plans win (deep outer amortization without the deep
    VMEM window).

    `exchange_fields` (default `write_fields`) is how many state fields
    cross the link per exchange; `exchange_lags` (optional, per exchanged
    field, in grid points) prices the per-field exchange depths
    `max(halo - lag, 0)` — fields only read pointwise at the rim ship a
    shallower strip.  `link_bw`/`link_latency` default to one ICI link
    (~45 GB/s).
    """
    exchange_fields = (write_fields if exchange_fields is None
                       else exchange_fields)
    if outer_depths is not None and mesh_block is None:
        raise ValueError("outer_depths (time-nested sweep) requires "
                         "mesh_block")
    best, best_cost, log = None, math.inf, SweepLog()
    for tx in tiles:
        for ty in tiles:
            for T in depths:
                plan = TBPlan((tx, ty), T, radius)
                # (+1 window: the sharded layer's domain mask)
                vmem = plan.kernel_vmem_bytes(
                    nz, fields + (mesh_block is not None), write_fields,
                    read_fields, dtype_bytes)
                if vmem > vmem_budget:
                    continue
                if mesh_block is not None and (
                        tx > mesh_block[0] or ty > mesh_block[1]
                        or mesh_block[0] % tx or mesh_block[1] % ty):
                    continue  # infeasible inner tile on the device block
                # candidate exchange depths: the inner depth itself (the
                # flat schedule, always in the sweep even when no entry
                # of `outer_depths` divides by T) plus every nestable
                # outer multiple
                outer_cands = ((T,) if outer_depths is None else
                               tuple(dict.fromkeys(
                                   (T,) + tuple(To for To in outer_depths
                                                if To % T == 0))))
                for T_out in outer_cands:
                    outer = TBPlan((tx, ty), T_out, radius)
                    if mesh_block is not None and \
                            outer.halo > min(mesh_block):
                        continue  # exchange deeper than the shard block
                    nested = outer_depths is not None
                    if nested:
                        comp = plan.nested_compute_multiplier(
                            mesh_block, T_out) * flops_per_point / peak_flops
                        mem = plan.nested_hbm_bytes_per_point_step(
                            mesh_block, T_out, nz, read_fields=read_fields,
                            write_fields=write_fields,
                            dtype_bytes=dtype_bytes) / hbm_bw
                    else:
                        comp = (plan.overlap_factor() * flops_per_point
                                / peak_flops)
                        mem = plan.hbm_bytes_per_point_step(
                            nz, read_fields=read_fields,
                            write_fields=write_fields,
                            dtype_bytes=dtype_bytes) / hbm_bw
                    entry = {"compute_s": comp, "memory_s": mem,
                             "overlap": plan.overlap_factor(),
                             "vmem_bytes": vmem}
                    cost = max(comp, mem)
                    if mesh_block is not None:
                        field_depths = None
                        if exchange_lags is not None:
                            field_depths = tuple(max(outer.halo - lag, 0)
                                                 for lag in exchange_lags)
                            entry["field_depths"] = field_depths
                        comm = outer.exchange_seconds_per_point_step(
                            mesh_block, nz, exchange_fields, link_bw,
                            link_latency, dtype_bytes=dtype_bytes,
                            depths=field_depths)
                        entry["comm_s"] = comm
                        entry["exchange_bytes"] = \
                            outer.exchange_bytes_per_tile(
                                mesh_block, nz, exchange_fields,
                                dtype_bytes, depths=field_depths)
                        serial = max(cost, 0.0) + comm
                        entry["overlap_exchange"] = False
                        if sweep_overlap:
                            split = outer.split_step_overhead_per_point_step(
                                mesh_block, nz, radius, flops_per_point,
                                peak_flops)
                            overlapped = max(cost, comm) + split
                            entry["split_s"] = split
                            if overlapped < serial:
                                entry["overlap_exchange"] = True
                                serial = overlapped
                        cost = serial
                    entry["cost_s"] = cost
                    if nested:
                        entry["outer_T"] = T_out
                        log[(tx, ty, T, T_out)] = entry
                    else:
                        log[(tx, ty, T)] = entry
                    if cost < best_cost:
                        best, best_cost = plan, cost
                        log.best_key = ((tx, ty, T, T_out) if nested
                                        else (tx, ty, T))
    if best is None:
        raise ValueError("no plan fits the VMEM budget"
                         + ("" if mesh_block is None
                            else " and per-device block"))
    return best, log


# ---------------------------------------------------------------------------
# Per-physics pricing (paper §III: the payoff scales with field count)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PhysicsCost:
    """Static per-physics quantities the TB cost model needs.

    state_fields:  carried wavefields (VMEM windows, written back by TB).
    param_fields:  read-only model windows (DMA'd, never written).
    evolved_fields: fields freshly computed per step — what a naive
                   spatially-blocked step writes to HBM (1 acoustic,
                   2 TTI, 9 elastic).
    radius_mult:   per-step halo growth in units of order//2 — 1 for the
                   acoustic Laplacian; 2 for elastic (stress reads the new
                   velocities) and TTI (two first-derivative passes).
    flops_per_point: order -> useful FLOPs per grid-point-timestep, taken
                   from the matching propagator's `model_flops_per_step`.
    halo_lag_units: per-state-field exchange-depth reduction in units of
                   order//2 — fields the update only reads pointwise at
                   the rim (previous-time-level copies; the elastic
                   velocities, whose fresh values feed the stress
                   derivatives before the rim garbage front reaches them)
                   provably ship a shallower halo strip: depth =
                   max(T*r_step - lag*(order//2), 0).  Mirrors
                   `kernels.tb_physics.TBPhysics.halo_lags`.

    These counts mirror `kernels.tb_physics.PHYSICS` (kept numeric here so
    core never imports kernels); a cross-check test in
    tests/test_tb_cost_model.py guards against drift.
    """

    name: str
    state_fields: int
    param_fields: int
    evolved_fields: int
    radius_mult: int
    flops_per_point: Callable[[int], float]
    halo_lag_units: Tuple[int, ...] = ()

    @property
    def fields(self) -> int:
        """VMEM-resident windows of the kernel (`stencil_tb._tb_kernel`):
        two sets of state windows (its step loop ping-pongs between them)
        plus one per param field — the acoustic 6 = 2 x (u_prev, u) + m,
        damp is the default of `autotune_plan`."""
        return 2 * self.state_fields + self.param_fields

    @property
    def read_fields(self) -> int:
        return self.state_fields + self.param_fields

    @property
    def write_fields(self) -> int:
        return self.state_fields

    def step_radius(self, order: int) -> int:
        return self.radius_mult * (order // 2)

    def exchange_lags(self, order: int) -> Tuple[int, ...]:
        """Per-state-field exchange-depth reductions in grid points."""
        lags = self.halo_lag_units or (0,) * self.state_fields
        return tuple(lag * (order // 2) for lag in lags)


def _flops(propagator: str):
    def f(order: int) -> float:
        from repro.core.propagators import acoustic, elastic, tti
        mod = {"acoustic": acoustic, "elastic": elastic, "tti": tti}
        return float(mod[propagator].model_flops_per_step((1, 1, 1), order))
    return f


PHYSICS_COSTS = {
    # halo_lag_units order matches the TBPhysics state_fields order:
    # acoustic (u_prev, u); tti (p, p_prev, r, r_prev);
    # elastic (vx, vy, vz, txx, tyy, tzz, txy, txz, tyz).
    "acoustic": PhysicsCost("acoustic", state_fields=2, param_fields=2,
                            evolved_fields=1, radius_mult=1,
                            flops_per_point=_flops("acoustic"),
                            halo_lag_units=(1, 0)),
    "tti": PhysicsCost("tti", state_fields=4, param_fields=6,
                       evolved_fields=2, radius_mult=2,
                       flops_per_point=_flops("tti"),
                       halo_lag_units=(0, 2, 0, 2)),
    "elastic": PhysicsCost("elastic", state_fields=9, param_fields=4,
                           evolved_fields=9, radius_mult=2,
                           flops_per_point=_flops("elastic"),
                           halo_lag_units=(1, 1, 1, 0, 0, 0, 0, 0, 0)),
}


def plan_for_physics(physics: str, nz: int, order: int, **kwargs
                     ) -> Tuple[TBPlan, dict]:
    """Autotune a (tile, T) plan priced for a specific physics.

    Fills `autotune_plan`'s field counts, per-step halo radius and FLOP
    density from `PHYSICS_COSTS[physics]`; kwargs (vmem_budget, tiles,
    depths, peak_flops, hbm_bw, mesh_block, link_bw, link_latency, ...)
    pass through and override.  The acoustic entry reproduces the
    historical defaults, and T=1 remains in the sweep so physics/order
    combinations where the trapezoid's overlap growth beats the traffic
    savings (the paper's SO-12 result) fall back to the spatially-blocked
    schedule.

    Passing `mesh_block=(bx, by)` (the per-device block of the sharded
    layer in `distributed/halo.py`) makes the sweep the joint two-level
    search of DESIGN.md §4: the candidate tile is the *inner* Pallas tile
    (must divide the block), the interconnect term prices the one
    deep exchange per tile with this physics' state-field count and
    per-field depths (`halo_lag_units` — what actually crosses the link),
    and `sweep_overlap=True` adds the overlapped-exchange schedule to the
    sweep.
    """
    pc = PHYSICS_COSTS[physics]
    args = dict(fields=pc.fields, read_fields=pc.read_fields,
                write_fields=pc.write_fields,
                exchange_fields=pc.state_fields,
                exchange_lags=pc.exchange_lags(order),
                flops_per_point=pc.flops_per_point(order))
    args.update(kwargs)
    return autotune_plan(nz, pc.step_radius(order), **args)


# ---------------------------------------------------------------------------
# Hierarchical two-level plan (outer shard trapezoid x inner Pallas tile)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HierPlan:
    """Joint two-level temporal-blocking plan for one shard (DESIGN.md §4).

    inner:         the Pallas-tile plan *inside* the per-device block —
                   `inner.T` is the INNER (VMEM) time depth: one kernel
                   pass advances the exchanged block `inner.T` steps.
    outer_T:       the exchange depth — a multiple of `inner.T`;
                   `outer_T / inner.T` inner passes consume one deep
                   exchange over pass-by-pass-shrinking windows
                   (`nested_pass_geometry`).  `outer_T == inner.T` is the
                   flat (non-nested) schedule.
    block:         the per-device (bx, by) block the outer trapezoid
                   exchanges around.
    overlap:       whether the first in-tile step runs as the split
                   interior/rim schedule so the deep ppermute hides behind
                   interior compute (pass 0 only).
    field_depths:  per-state-field exchange depths (grid points) — the
                   per-field-halo saving; uniform depth is `halo`.
    """

    inner: TBPlan
    outer_T: int
    block: Tuple[int, int]
    overlap: bool
    field_depths: Tuple[int, ...]

    def to_dict(self) -> dict:
        """JSON-safe form (the survey plan cache's on-disk format)."""
        return {"inner": self.inner.to_dict(), "outer_T": int(self.outer_T),
                "block": [int(b) for b in self.block],
                "overlap": bool(self.overlap),
                "field_depths": [int(d) for d in self.field_depths]}

    @classmethod
    def from_dict(cls, d: dict) -> "HierPlan":
        return cls(inner=TBPlan.from_dict(d["inner"]),
                   outer_T=int(d["outer_T"]),
                   block=tuple(int(b) for b in d["block"]),
                   overlap=bool(d["overlap"]),
                   field_depths=tuple(int(x) for x in d["field_depths"]))

    @property
    def T(self) -> int:
        """The exchange depth (what `DistTBPlan.T` executes)."""
        return self.outer_T

    @property
    def outer(self) -> TBPlan:
        """The outer trapezoid as a TBPlan (exchange-level pricing)."""
        return TBPlan(self.inner.tile, self.outer_T, self.inner.radius)

    @property
    def halo(self) -> int:
        """Exchange depth in grid points (outer_T * r_step)."""
        return self.outer.halo

    def vmem_bytes(self, nz: int, fields: int, dtype_bytes: int = 4) -> int:
        """Resident bytes of the INNER window — the whole point of
        nesting: sized by `inner.T`, not the exchange depth."""
        return self.inner.vmem_bytes(nz, fields, dtype_bytes)

    def exchange_bytes(self, nz: int, dtype_bytes: int = 4) -> int:
        """Bytes per deep exchange with the per-field depths."""
        return self.outer.exchange_bytes_per_tile(
            self.block, nz, dtype_bytes=dtype_bytes,
            depths=self.field_depths)

    def exchange_bytes_uniform(self, nz: int, dtype_bytes: int = 4) -> int:
        """The uniform-depth baseline the per-field scheme is priced
        against."""
        return self.outer.exchange_bytes_per_tile(
            self.block, nz, fields=len(self.field_depths),
            dtype_bytes=dtype_bytes)


def plan_hierarchy(physics: str, nz: int, order: int,
                   block: Tuple[int, int], **kwargs
                   ) -> Tuple[HierPlan, dict]:
    """Jointly autotune the outer exchange depth, inner (tile, T) and
    overlap choice for one per-device block — the hierarchical search the
    parameterised time-tiling literature (Kukreja et al., PAPERS.md) shows
    must not be done level-by-level.

    Thin wrapper over `plan_for_physics(..., mesh_block=block,
    sweep_overlap=True, outer_depths=depths)` that re-packages the winning
    sweep entry as a `HierPlan`; `distributed/halo.py` turns it into a
    `DistTBPlan` via `dist_plan_from_hier`.  The sweep is 4-dimensional
    (log keys `(tx, ty, inner_T, outer_T)`): the VMEM window and per-pass
    trapezoid are priced at the inner depth while the exchange amortizes
    at the outer depth, so very deep exchanges no longer drag the VMEM
    window up with them.
    """
    kwargs.setdefault("sweep_overlap", True)
    kwargs.setdefault("outer_depths", kwargs.get("depths", (1, 2, 4, 8, 16)))
    pc = PHYSICS_COSTS[physics]
    plan, log = plan_for_physics(physics, nz, order, mesh_block=block,
                                 **kwargs)
    # the sweep's own winner over the full 4-tuple key space
    # (autotune_plan's returned TBPlan only carries the inner level)
    key = log.best_key
    entry = log[key]
    tx, ty, inner_T = key[0], key[1], key[2]
    outer_T = entry.get("outer_T", inner_T)
    inner = TBPlan((tx, ty), inner_T, pc.step_radius(order))
    outer_halo = outer_T * pc.step_radius(order)
    depths = entry.get("field_depths",
                       tuple(max(outer_halo - lag, 0)
                             for lag in pc.exchange_lags(order)))
    return (HierPlan(inner=inner, outer_T=outer_T,
                     block=(int(block[0]), int(block[1])),
                     overlap=bool(entry.get("overlap_exchange", False)),
                     field_depths=tuple(depths)),
            log)
