"""Sparse "off-the-grid" sources & receivers, and the paper's precompute scheme.

This module is the faithful reproduction of Section II of the paper:

  1. inject each source into an empty grid to discover the affected points
     (Listing 2) — `affected_points` (we also expose the direct index-based
     computation, which is bit-identical and what production uses);
  2. build the binary source mask ``SM`` and unique-ID volume ``SID``
     (Fig. 5b/5c) — `GriddedSources.sm`, `GriddedSources.sid`, dense grids
     made on request from the affected points: the production paths read
     the points and never a grid-sized array;
  3. decompose the off-grid wavelets into per-affected-grid-point wavelets
     ``src_dcmp`` (Listing 3, Fig. 5d) — `GriddedSources.src_dcmp`;
  4. the fused, grid-aligned injection that makes temporal blocking legal
     (Listing 4) — `inject` / `dense_increment`;
  5. the reduced-iteration-space compression: ``nnz_mask`` over z-columns and
     the packed ``Sp_SID`` (Listing 5, Fig. 6) — `ZCompressed`;
  plus the TPU adaptation: per-tile source/receiver tables consumed by the
  Pallas temporally-blocked kernel (`tile_source_tables`,
  `tile_receiver_tables`) — tile-granular analogues of ``nnz_mask``.

Receivers are handled symmetrically (measurement interpolation, Fig. 3b):
interpolation weights are precomputed into a gather table so that reading a
receiver is a local, grid-aligned operation.

Everything here is host-side numpy precomputation producing jnp constants
whose size follows the affected points, never the grid; it runs once per
model setup, which is the paper's "negligible overhead" claim (span
`sources.precompute`).

Interpolation itself lives in `core/interp.py` as a precomputed per-axis
coefficient operator (`InterpCoeffs`, Devito's
``PrecomputedSparseFunction`` idiom): multilinear by default, radius-r
Kaiser-windowed sinc via an `InterpSpec`, with an explicit edge policy
(out-of-domain coordinates raise by default; ``edge="clip"`` clamps and
renormalizes weights to sum 1).  The tile binning/packing shared with the
sharded per-pass builders lives in `core/tables.py`; the builders here
are thin geometry adapters over that core.

Paper-artifact map (the same table lives in DESIGN.md §2):

    paper artifact                   implementing function
    -------------------------------  -------------------------------------
    Listing 1  (naive propagate)     core/propagators/*.propagate
    Listing 2  (affected points)     affected_points[_by_injection]
    Listing 3  (wavelet decompose)   precompute  (-> GriddedSources.src_dcmp)
    Listing 4  (fused injection)     inject / dense_increment
    Listing 5  (z-compressed loop)   z_compress / inject_zcompressed
    Listing 6  (time-tiled loop)     kernels/ops._tb_propagate + stencil_tb
    Fig. 5b/5c SM / SID              GriddedSources.sm / .sid
    Fig. 5d    src_dcmp              GriddedSources.src_dcmp
    Fig. 6     nnz_mask / Sp_SID     ZCompressed
    Fig. 3b    receiver interp       interpolate / tile_receiver_tables
    Fig. 4b    halo-source dep       tile_source_tables(include_halo=True)
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import interp as interp_mod
from repro.core import tables as tables_mod
from repro.core.grid import Grid
from repro.core.interp import LINEAR, InterpCoeffs, InterpSpec  # noqa: F401
from repro.telemetry import spans as _spans


# ---------------------------------------------------------------------------
# Source / receiver descriptions (off-the-grid)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparseOperator:
    """A set of sparsely located off-the-grid points (sources or receivers).

    coords: (num, ndim) float64 physical coordinates — *not* grid-aligned.
    """

    coords: np.ndarray

    def __post_init__(self):
        object.__setattr__(self, "coords",
                           np.atleast_2d(np.asarray(self.coords, np.float64)))

    @property
    def num(self) -> int:
        return self.coords.shape[0]


class InterpStencil(NamedTuple):
    """Expanded interpolation stencil for a set of off-grid points.

    indices: (num, footprint, ndim) int32 — neighbouring grid points (np in
      the paper's Listing 1; `map(s, i)` is `indices[s, i]`).  footprint is
      (2r)**ndim — 2**ndim for the default multilinear kernel.
    weights: (num, footprint) float64 — kernel weights, rows sum to 1
      (enforced: out-of-domain coordinates raise unless the spec opts into
      ``edge="clip"``, which renormalizes — see `core/interp.py`).
    """

    indices: np.ndarray
    weights: np.ndarray


def interp_stencil(op: SparseOperator, grid: Grid,
                   spec: InterpSpec = LINEAR) -> InterpStencil:
    """Interpolation stencil — paper Fig. 3, `f` in Listing 1.

    Thin wrapper: precomputes the per-axis coefficient operator
    (`interp.precompute_coeffs`) and expands it to tensor-product form.
    The default linear spec reproduces the original trilinear stencil
    bit-for-bit for in-domain points (pinned by parity tests)."""
    co = interp_mod.precompute_coeffs(op.coords, grid, spec)
    return InterpStencil(*co.expand())


# ---------------------------------------------------------------------------
# Step 1 (Listing 2): discover affected points by injecting into empty grid
# ---------------------------------------------------------------------------

def affected_points_by_injection(stencil: InterpStencil, grid: Grid,
                                 wavelet0: np.ndarray) -> np.ndarray:
    """The paper's Listing 2: scatter one timestep into an empty grid, then
    read off the non-zero coordinates.  `wavelet0` is src(t0, :) and must be
    non-zero for every source (paper assumption; `precompute` falls back to
    weight-based discovery otherwise, equivalent to injecting for more
    timesteps).  The empty grid is held sparsely (only the points written
    to), so the work follows the stencils, not the grid."""
    u = {}
    num, npts, _ = stencil.indices.shape
    for s in range(num):
        for i in range(npts):
            xs = tuple(int(v) for v in stencil.indices[s, i])
            u[xs] = u.get(xs, 0.0) + stencil.weights[s, i] * wavelet0[s]
    pts = sorted(p for p, v in u.items() if v != 0.0)
    return np.asarray(pts, np.int32).reshape(-1, grid.ndim)


def affected_points(stencil: InterpStencil) -> np.ndarray:
    """Index-based equivalent of Listing 2: unique grid points with non-zero
    interpolation weight, in lexicographic order (ascending unique IDs)."""
    flatidx = stencil.indices.reshape(-1, stencil.indices.shape[-1])
    flatw = stencil.weights.reshape(-1)
    pts = flatidx[flatw != 0.0]
    return np.unique(pts, axis=0).astype(np.int32)


# ---------------------------------------------------------------------------
# Steps 2-3: SM / SID masks and decomposed wavefields
# ---------------------------------------------------------------------------

class GriddedSources(NamedTuple):
    """Grid-aligned decomposition of an off-the-grid source set (Fig. 5d).

    After this structure exists, source injection is a *local* grid-aligned
    operation and temporal blocking is legal (paper §II.A).

    points:    (npts, ndim) int32 — coordinates of affected points, in
               ascending unique-ID (lexicographic) order.
    src_dcmp:  (nt, npts) float32 — per-affected-point wavelets (Listing 3):
               src_dcmp[t, sid] = sum_s w(s->point) * src[t, s].

    The paper's dense SM / SID volumes (Fig. 5b/5c) are `sm(shape)` /
    `sid(shape)`, host grids made from `points` on request (oracles and
    tests); nothing grid-sized is built or put on a device otherwise.
    """

    points: jnp.ndarray
    src_dcmp: jnp.ndarray

    @property
    def npts(self) -> int:
        return self.points.shape[0]

    @property
    def nt(self) -> int:
        return self.src_dcmp.shape[0]

    def sm(self, shape: Tuple[int, ...]) -> np.ndarray:
        """(shape) uint8 binary source mask (Fig. 5b)."""
        out = np.zeros(shape, np.uint8)
        out[tuple(np.asarray(self.points).T)] = 1
        return out

    def sid(self, shape: Tuple[int, ...]) -> np.ndarray:
        """(shape) int32 unique ascending ID per affected point, -1
        elsewhere (Fig. 5c; the paper uses an implicit 0 background — we
        use -1 so ID 0 is usable)."""
        out = np.full(shape, -1, np.int32)
        out[tuple(np.asarray(self.points).T)] = np.arange(self.npts,
                                                          dtype=np.int32)
        return out


def _point_ids(pts: np.ndarray, idx: np.ndarray,
               shape: Tuple[int, ...]) -> np.ndarray:
    """SID of each grid index in `idx` (n, ndim): its position in the
    lexicographically sorted `pts`, -1 where it is no affected point —
    the SID volume read at `idx`, without building the volume."""
    keys = np.ravel_multi_index(tuple(pts.T), shape)
    want = np.ravel_multi_index(tuple(idx.T), shape)
    pos = np.searchsorted(keys, want)
    hit = pos < keys.size
    hit[hit] = keys[pos[hit]] == want[hit]
    return np.where(hit, pos, -1)


def precompute(op: SparseOperator, grid: Grid, wavelets: np.ndarray,
               *, discover_by_injection: bool = False,
               dtype=jnp.float32,
               interp: InterpSpec = LINEAR) -> GriddedSources:
    """The paper's §II.A precompute pipeline (steps 1-3).

    Args:
      op: the off-grid source set.
      grid: the FD grid.
      wavelets: (nt, num_sources) source time signatures src(t, s).
      discover_by_injection: use the literal Listing-2 discovery (inject one
        timestep into an empty grid).  The default uses the index-based
        equivalent; both paths are tested to agree.
      interp: interpolation kernel + edge policy (`core/interp.InterpSpec`);
        default multilinear with out-of-domain coordinates raising.
    """
    wavelets = np.asarray(wavelets, np.float64)
    if wavelets.ndim != 2 or wavelets.shape[1] != op.num:
        raise ValueError(f"wavelets must be (nt, {op.num}), got {wavelets.shape}")
    with _spans.span("sources.precompute", nsrc=op.num) as sp:
        st = interp_stencil(op, grid, interp)

        if discover_by_injection:
            t0 = next((t for t in range(wavelets.shape[0])
                       if np.all(wavelets[t] != 0.0)), None)
            if t0 is None:
                pts = affected_points(st)
            else:
                pts = affected_points_by_injection(st, grid, wavelets[t0])
        else:
            pts = affected_points(st)

        npts = pts.shape[0]
        # Listing 3: decompose wavelets onto affected points.  A point
        # shared by several sources accumulates all their weighted wavelets
        # (the paper's "points being affected by more than one source").
        ids = _point_ids(pts, st.indices.reshape(-1, grid.ndim), grid.shape)
        w = st.weights.reshape(-1)                              # (num*2^d,)
        src_ids = np.repeat(np.arange(op.num), st.indices.shape[1])
        nt = wavelets.shape[0]
        # Accumulate weighted wavelets per affected point; np.add.at handles
        # repeated ids (several sources hitting the same grid point).
        src_dcmp = np.zeros((nt, npts), np.float64)
        contrib = wavelets[:, src_ids] * w[None, :]            # (nt, entries)
        np.add.at(src_dcmp.T, ids, contrib.T)
        # the host arrays this builds: no dense SM/SID grid any more
        sp.set(npts=npts, sm_bytes=0, sid_bytes=0,
               src_dcmp_bytes=src_dcmp.nbytes)
        return GriddedSources(
            points=jnp.asarray(pts),
            src_dcmp=jnp.asarray(src_dcmp, dtype=dtype),
        )


# ---------------------------------------------------------------------------
# Step 4 (Listing 4): fused grid-aligned injection
# ---------------------------------------------------------------------------

def inject(u: jnp.ndarray, g: GriddedSources, t: jnp.ndarray,
           scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Grid-aligned injection of timestep `t` (dynamic) into field `u`.

    u[p] += scale[p] * src_dcmp[t, SID[p]] for p in affected points.  This is
    the paper's Listing 4 semantics expressed as a scatter-add — legal at any
    point inside a space-time tile because all operands are grid-aligned.
    `scale` is the physical injection factor (dt^2/m at the points for the
    acoustic case), gathered at the affected points.
    """
    vals = jax.lax.dynamic_index_in_dim(g.src_dcmp, t, axis=0,
                                        keepdims=False)        # (npts,)
    if scale is not None:
        vals = vals * scale
    return u.at[tuple(g.points.T)].add(vals.astype(u.dtype))


def point_scale(field: jnp.ndarray, g: GriddedSources) -> jnp.ndarray:
    """Gather a per-grid-point factor (e.g. dt^2/m) at the affected points."""
    return field[tuple(g.points.T)]


def dense_increment(g: GriddedSources, t: jnp.ndarray,
                    shape: Tuple[int, ...], dtype=jnp.float32) -> jnp.ndarray:
    """Materialize the full-grid injection increment for timestep `t` —
    the SM/SID-masked read the fused loop in Listing 4 performs:
    ``SM[p] ? src_dcmp[t, SID[p]] : 0``.  Used by oracles and tests; the
    production paths use `inject` (scatter) or the per-tile tables."""
    vals = jax.lax.dynamic_index_in_dim(g.src_dcmp, t, 0, keepdims=False)
    safe_sid = jnp.maximum(jnp.asarray(g.sid(shape)), 0)
    inc = vals[safe_sid] * jnp.asarray(g.sm(shape)).astype(dtype)
    return inc.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Step 5 (Listing 5 / Fig. 6): reduced iteration space along z
# ---------------------------------------------------------------------------

class ZCompressed(NamedTuple):
    """The paper's nnz_mask / Sp_SID compression of SM/SID along z.

    nnz_mask: (nx, ny) int32 — number of affected z's per column (Fig. 6).
    sp_z:     (nx, ny, max_nnz) int32 — packed z indices (padded with -1).
    sp_sid:   (nx, ny, max_nnz) int32 — packed SIDs (padded with -1).
    """

    nnz_mask: jnp.ndarray
    sp_z: jnp.ndarray
    sp_sid: jnp.ndarray

    @property
    def max_nnz(self) -> int:
        return self.sp_z.shape[-1]


def z_compress(g: GriddedSources, shape: Tuple[int, int, int]
               ) -> ZCompressed:
    """Aggregate non-zeros along z, cutting off all-zero z-slices (§II.A.5)."""
    if len(shape) != 3:
        raise ValueError("z-compression is defined for 3-D grids")
    sm, sid = g.sm(shape), g.sid(shape)
    nx, ny, nz = shape
    nnz = sm.astype(np.int32).sum(axis=2)
    max_nnz = max(int(nnz.max()), 1)
    sp_z = np.full((nx, ny, max_nnz), -1, np.int32)
    sp_sid = np.full((nx, ny, max_nnz), -1, np.int32)
    xs, ys = np.nonzero(nnz)
    for x, y in zip(xs, ys):
        zz = np.nonzero(sm[x, y])[0]
        sp_z[x, y, :zz.size] = zz
        sp_sid[x, y, :zz.size] = sid[x, y, zz]
    return ZCompressed(jnp.asarray(nnz), jnp.asarray(sp_z), jnp.asarray(sp_sid))


def inject_zcompressed(u: jnp.ndarray, g: GriddedSources, zc: ZCompressed,
                       t: jnp.ndarray,
                       scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Listing-5 semantics: iterate only packed non-zero z entries.

    Vectorized over the packed slots; padding slots (sid == -1) contribute 0.
    Equivalent to `inject` — asserted by tests.
    """
    vals = jax.lax.dynamic_index_in_dim(g.src_dcmp, t, 0, keepdims=False)
    if scale is not None:
        vals = vals * scale
    nx, ny, k = zc.sp_sid.shape
    valid = zc.sp_sid >= 0
    safe_sid = jnp.maximum(zc.sp_sid, 0)
    inc = jnp.where(valid, vals[safe_sid], 0.0)            # (nx, ny, k)
    xg, yg = jnp.meshgrid(jnp.arange(nx), jnp.arange(ny), indexing="ij")
    xi = jnp.broadcast_to(xg[..., None], (nx, ny, k)).reshape(-1)
    yi = jnp.broadcast_to(yg[..., None], (nx, ny, k)).reshape(-1)
    zi = jnp.maximum(zc.sp_z, 0).reshape(-1)
    return u.at[xi, yi, zi].add(inc.reshape(-1).astype(u.dtype))


# ---------------------------------------------------------------------------
# Receivers (measurement interpolation, Fig. 3b)
# ---------------------------------------------------------------------------

class GriddedReceivers(NamedTuple):
    """Grid-aligned receiver gather table.

    indices: (nrec, footprint, ndim) int32; weights: (nrec, footprint)
    float32 — footprint is (2r)**ndim (8 for the default trilinear kernel).
    """

    indices: jnp.ndarray
    weights: jnp.ndarray

    @property
    def num(self) -> int:
        return self.indices.shape[0]


def precompute_receivers(op: SparseOperator, grid: Grid,
                         dtype=jnp.float32,
                         interp: InterpSpec = LINEAR) -> GriddedReceivers:
    with _spans.span("sources.precompute_receivers", nrec=op.num) as sp:
        st = interp_stencil(op, grid, interp)
        sp.set(npts=st.weights.size, indices_bytes=st.indices.nbytes,
               weights_bytes=st.weights.nbytes)
        return GriddedReceivers(jnp.asarray(st.indices),
                                jnp.asarray(st.weights, dtype=dtype))


def interpolate(u: jnp.ndarray, r: GriddedReceivers) -> jnp.ndarray:
    """d(t, r) = sum_i w_i * u[neigh_i] — one receiver sample per receiver."""
    nrec, k, ndim = r.indices.shape
    flat = r.indices.reshape(-1, ndim)
    vals = u[tuple(flat.T)].reshape(nrec, k)
    return jnp.sum(vals * r.weights.astype(u.dtype), axis=1)


# ---------------------------------------------------------------------------
# TPU adaptation: tile-granular tables for the Pallas TB kernel
# ---------------------------------------------------------------------------

class TileSourceTable(NamedTuple):
    """Per-(x,y)-tile source table (the tile-granular analogue of nnz_mask).

    For tile (i, j) covering centre region [i*tx:(i+1)*tx) x [j*ty:(j+1)*ty)
    (full z), entries are affected points inside the centre region with
    coordinates local to the tile's *window* origin (centre minus halo).

    nnz:    (n_tiles,) int32 — valid entries per tile, which fill slots
            0..nnz-1: the kernel's inject loop runs that many iterations
            a slab (`stencil_tb.tb_time_tile`'s `counts`).  A host
            (numpy) array, so counting the live slots from it syncs
            nothing; the propagate passes it to the device with the rest
            of the table.
    coords: (n_tiles, cap, 3) int32 — window-local (x, y, z), padded 0.
    sid:    (n_tiles, cap) int32 — SID per entry, padded -1.
    scale:  (n_tiles, cap) float32 — per-point physical factor, padded 0.
    """

    nnz: np.ndarray
    coords: jnp.ndarray
    sid: jnp.ndarray
    scale: jnp.ndarray

    @property
    def cap(self) -> int:
        return self.coords.shape[1]


def tile_source_tables(g: GriddedSources, grid_shape: Tuple[int, int, int],
                       tile: Tuple[int, int], halo: int,
                       scale: Optional[np.ndarray] = None,
                       cap: Optional[int] = None,
                       include_halo: bool = False) -> TileSourceTable:
    """Bin affected points into (x, y) tiles for the Pallas kernel.

    `halo` is the window overhang (T*r for a depth-T time tile), so local
    coords are point - (tile_origin - halo).

    With ``include_halo=False`` tiles partition the *centre* regions and each
    point belongs to exactly one tile (use for T = 1 or pure scatter).

    With ``include_halo=True`` every point is assigned to **every tile whose
    window (centre + halo) contains it** — required for temporal blocking:
    a source in a neighbouring tile's centre must also be injected into this
    tile's halo during intermediate in-VMEM steps, or its wavefront would be
    missing when it reaches the centre (exactly the paper's Fig. 4b data
    dependency).  Points are then deliberately duplicated across windows.
    """
    nx, ny, _ = grid_shape
    tx, ty = tile
    ntx = -(-nx // tx)
    nty = -(-ny // ty)
    pts = np.asarray(g.points)
    npts = pts.shape[0]
    scl = (np.ones(npts, np.float32) if scale is None
           else np.asarray(scale, np.float32))

    wg = tables_mod.WindowGrid(origin=(-halo, -halo), tile=(tx, ty),
                               ntiles=(ntx, nty), pad=halo)
    pairs = tables_mod.bin_points(pts[:, :2], wg,
                                  "window" if include_halo else "centre")
    fill, slot, cap = tables_mod.pack_slots(pairs, wg.n_tiles, cap,
                                            "source table")
    coords = np.zeros((wg.n_tiles, cap, 3), np.int32)
    sid_t = np.full((wg.n_tiles, cap), -1, np.int32)
    scale_t = np.zeros((wg.n_tiles, cap), np.float32)
    for (tt, p), k in zip(pairs, slot):
        ox, oy = wg.window_origin(tt // nty, tt % nty)
        coords[tt, k] = (pts[p, 0] - ox, pts[p, 1] - oy, pts[p, 2])
        sid_t[tt, k] = p
        scale_t[tt, k] = scl[p]
    return TileSourceTable(fill, jnp.asarray(coords),
                           jnp.asarray(sid_t), jnp.asarray(scale_t))


class TileReceiverTable(NamedTuple):
    """Per-tile receiver gather entries (point, receiver id, weight).

    A receiver's 2**ndim gather points may straddle tiles; each (receiver,
    point) pair is assigned to the owning tile and contributes a *partial*
    sample — the host segment-sums partials by receiver id afterwards.
    """

    nnz: np.ndarray         # (n_tiles,) as TileSourceTable's: bounds the
    #                         kernel's record loop
    coords: jnp.ndarray     # (n_tiles, cap, 3) window-local
    rid: jnp.ndarray        # (n_tiles, cap) receiver id, padded -1
    weight: jnp.ndarray     # (n_tiles, cap) float32


def tile_receiver_tables(r: GriddedReceivers, grid_shape: Tuple[int, int, int],
                         tile: Tuple[int, int], halo: int,
                         cap: Optional[int] = None) -> TileReceiverTable:
    nx, ny, _ = grid_shape
    tx, ty = tile
    nty = -(-ny // ty)
    ntx = -(-nx // tx)
    idx = np.asarray(r.indices).reshape(-1, 3)
    w = np.asarray(r.weights, np.float64).reshape(-1)
    rids = np.repeat(np.arange(r.num, dtype=np.int32), r.indices.shape[1])
    keep = w != 0.0
    idx, w, rids = idx[keep], w[keep], rids[keep]
    wg = tables_mod.WindowGrid(origin=(-halo, -halo), tile=(tx, ty),
                               ntiles=(ntx, nty), pad=halo)
    pairs = tables_mod.bin_points(idx[:, :2], wg, "centre")
    fill, slot, cap = tables_mod.pack_slots(pairs, wg.n_tiles, cap,
                                            "receiver table")
    coords = np.zeros((wg.n_tiles, cap, 3), np.int32)
    rid_t = np.full((wg.n_tiles, cap), -1, np.int32)
    w_t = np.zeros((wg.n_tiles, cap), np.float32)
    for (tt, p), k in zip(pairs, slot):
        ox, oy = wg.window_origin(tt // nty, tt % nty)
        coords[tt, k] = (idx[p, 0] - ox, idx[p, 1] - oy, idx[p, 2])
        rid_t[tt, k] = rids[p]
        w_t[tt, k] = w[p]
    return TileReceiverTable(fill, jnp.asarray(coords),
                             jnp.asarray(rid_t), jnp.asarray(w_t))


# ---------------------------------------------------------------------------
# Wavelets
# ---------------------------------------------------------------------------

def ricker_wavelet(nt: int, dt: float, f0: float, num: int = 1,
                   t0: Optional[float] = None) -> np.ndarray:
    """Ricker (Mexican-hat) wavelet, the standard seismic source signature.

    Returns (nt, num).  `t0` defaults to 1/f0 so the wavelet onset is
    non-zero at early timesteps (the paper's Listing-2 assumption).
    """
    t0 = 1.0 / f0 if t0 is None else t0
    t = np.arange(nt) * dt
    a = (np.pi * f0 * (t - t0)) ** 2
    w = (1.0 - 2.0 * a) * np.exp(-a)
    return np.tile(w[:, None], (1, num)).astype(np.float64)
