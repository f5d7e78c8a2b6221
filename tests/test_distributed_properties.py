"""Property-based tests for the distributed TB layer (ISSUE 4).

Three groups, all runnable without devices (the subprocess parity suite in
`test_distributed.py` covers the ppermute wiring on a real 8-device mesh):

* `exchange_to_depth` roundtrip — the per-field shallow-strip + zero-pad
  exchange equals the full-depth exchange with the outer band zeroed, for
  random (depth, h, block, domain-edge) configurations.  The production
  `halo_exchange`/`halo_exchange_2d`/`exchange_to_depth` code runs
  unmodified; only the two neighbor-strip providers are injected
  (`shift_fns`) with a collective-free simulator fed from a 3x3 block
  neighborhood — including the x-then-y ordering subtlety that the y
  strips come from the neighbor's already-x-padded block.

* `TBPhysics.field_halo_depths` / `DistTBPlan.field_depths` — per-field
  exchange depths never exceed the uniform depth, some field always ships
  full depth (the dependency cone binds), and depths are monotone in T.

* `nested_pass_geometry` — the time-nested pass schedule telescopes
  exactly through the exchanged halo (d_in/d_out chain, step counts, tile
  round-up bounds).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_stub import given, hst, settings

from repro.core.temporal_blocking import nested_pass_geometry
from repro.distributed import halo as H
from repro.kernels import tb_physics as phys


# ---------------------------------------------------------------------------
# exchange_to_depth roundtrip under simulated neighbor shifts
# ---------------------------------------------------------------------------

def _zeros_like_piece(x, h, dim):
    shape = list(x.shape)
    shape[dim] = h
    return jnp.zeros(shape, x.dtype)


def _sim_shifts(nbrs):
    """Collective-free `(from_low, from_high)` pair for ONE shard, fed
    from its 3x3 neighborhood `nbrs[(di, dj)]` (None = domain boundary ->
    Dirichlet zeros, like a missing ppermute peer).

    The y-round runs on the x-padded block, so the y strips are sliced
    from the neighbor's x-padded block, assembled here from the corner
    entries at the pad depth implied by the current operand shape.
    """
    def xpad(nb, col, hx):
        west, east = nbrs.get((-1, col)), nbrs.get((1, col))
        lo = west[-hx:] if west is not None else jnp.zeros(
            (hx,) + nb.shape[1:], nb.dtype)
        hi = east[:hx] if east is not None else jnp.zeros(
            (hx,) + nb.shape[1:], nb.dtype)
        return jnp.concatenate([lo, nb, hi], axis=0)

    def strip(x, h, dim, side):
        col = -1 if side == "low" else 1
        if dim == 0:
            nb = nbrs.get((col, 0))
            if nb is None:
                return _zeros_like_piece(x, h, 0)
            return nb[-h:] if side == "low" else nb[:h]
        nb = nbrs.get((0, col))
        if nb is None:
            return _zeros_like_piece(x, h, 1)
        hx = (x.shape[0] - nb.shape[0]) // 2
        nbp = xpad(nb, col, hx) if hx else nb
        return nbp[:, -h:] if side == "low" else nbp[:, :h]

    return (lambda x, h, axis_name, dim: strip(x, h, dim, "low"),
            lambda x, h, axis_name, dim: strip(x, h, dim, "high"))


def _neighborhood(bx, by, nz, has_w, has_e, has_s, has_n, seed):
    """Random center block + the 3x3 neighborhood of a rectangular
    domain: corners exist iff both adjacent sides do."""
    rng = np.random.RandomState(seed)
    exists = {(-1, 0): has_w, (1, 0): has_e, (0, -1): has_s, (0, 1): has_n,
              (-1, -1): has_w and has_s, (-1, 1): has_w and has_n,
              (1, -1): has_e and has_s, (1, 1): has_e and has_n}
    nbrs = {k: (jnp.asarray(rng.randn(bx, by, nz), jnp.float32)
                if ok else None)
            for k, ok in exists.items()}
    centre = jnp.asarray(rng.randn(bx, by, nz), jnp.float32)
    return centre, nbrs


@settings(max_examples=20, deadline=None)
@given(dims=hst.sampled_from([(4, 4, 2), (6, 4, 3), (8, 6, 2)]),
       h=hst.integers(1, 4), d_raw=hst.integers(0, 4),
       has_w=hst.booleans(), has_e=hst.booleans(),
       has_s=hst.booleans(), has_n=hst.booleans(),
       seed=hst.integers(0, 999))
def test_property_exchange_to_depth_roundtrip(dims, h, d_raw, has_w, has_e,
                                              has_s, has_n, seed):
    """Shallow strip + zero pad == full-depth exchange with the outer
    (h - depth) band zeroed — the valid-centre-preserving contract the
    per-field exchange (`TBPhysics.halo_lags`) rests on."""
    bx, by, nz = dims
    depth = min(d_raw, h)
    centre, nbrs = _neighborhood(bx, by, nz, has_w, has_e, has_s, has_n,
                                 seed)
    shifts = _sim_shifts(nbrs)
    full = H.halo_exchange_2d(centre, h, "x", "y", shift_fns=shifts)
    shallow = H.exchange_to_depth(centre, depth, h, "x", "y",
                                  shift_fns=shifts)
    assert full.shape == shallow.shape == (bx + 2 * h, by + 2 * h, nz)
    ii = np.arange(bx + 2 * h)[:, None, None]
    jj = np.arange(by + 2 * h)[None, :, None]
    band = ((ii < h - depth) | (ii >= bx + h + depth)
            | (jj < h - depth) | (jj >= by + h + depth))
    expect = np.where(band, 0.0, np.asarray(full))
    np.testing.assert_array_equal(np.asarray(shallow), expect)
    # and the centre is always the untouched local block
    np.testing.assert_array_equal(
        np.asarray(shallow[h:h + bx, h:h + by]), np.asarray(centre))


def test_exchange_to_depth_full_depth_is_plain_exchange():
    """depth == h is bit-identical to halo_exchange_2d (no pad branch)."""
    centre, nbrs = _neighborhood(6, 4, 2, True, True, True, True, 7)
    shifts = _sim_shifts(nbrs)
    np.testing.assert_array_equal(
        np.asarray(H.exchange_to_depth(centre, 3, 3, "x", "y",
                                       shift_fns=shifts)),
        np.asarray(H.halo_exchange_2d(centre, 3, "x", "y",
                                      shift_fns=shifts)))


# ---------------------------------------------------------------------------
# Per-field exchange depths: bounded by the uniform depth, monotone in T
# ---------------------------------------------------------------------------

def _one_device_plan(physics, T, order):
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    return H.DistTBPlan(mesh=mesh, grid_shape=(64, 64, 8), physics=physics,
                        order=order, T=T)


@settings(max_examples=20, deadline=None)
@given(name=hst.sampled_from(["acoustic", "tti", "elastic"]),
       T=hst.integers(1, 6), order=hst.sampled_from([2, 4, 8]))
def test_property_field_depths_bounded_and_monotone(name, T, order):
    tp = phys.PHYSICS[name]
    h = T * tp.step_radius(order)
    depths = tp.field_halo_depths(T, order)
    assert len(depths) == len(tp.state_fields)
    assert all(0 <= d <= h for d in depths)          # never exceed uniform
    assert max(depths) == h                          # some field binds full
    deeper = tp.field_halo_depths(T + 1, order)
    assert all(b >= a for a, b in zip(depths, deeper))  # monotone in T


@settings(max_examples=10, deadline=None)
@given(name=hst.sampled_from(["acoustic", "tti", "elastic"]),
       T=hst.integers(1, 4), order=hst.sampled_from([2, 4]))
def test_property_dist_plan_field_depths(name, T, order):
    """`DistTBPlan.field_depths` == the physics' cone depths, none above
    the uniform depth and one at it, at ANY tile depth including the
    remainder depths below plan.T."""
    tp = phys.PHYSICS[name]
    plan = _one_device_plan(tp, T, order)
    for T_depth in range(1, T + 1):
        h = T_depth * plan.r_step
        depths = plan.field_depths(T_depth)
        assert depths == tp.field_halo_depths(T_depth, order)
        assert len(depths) == len(tp.state_fields)
        assert all(d <= h for d in depths) and max(depths) == h


# ---------------------------------------------------------------------------
# Time-nested pass geometry
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(T_steps=hst.integers(1, 8), inner_T=hst.integers(1, 8),
       r=hst.sampled_from([1, 2, 4]),
       block=hst.sampled_from([(8, 8), (8, 16), (12, 12)]),
       tile=hst.sampled_from([(4, 4), (4, 8), (8, 8), (12, 4)]))
def test_property_pass_geometry_telescopes(T_steps, inner_T, r, block,
                                           tile):
    geoms = nested_pass_geometry(block, tile, T_steps, inner_T, r)
    assert sum(g.T for g in geoms) == T_steps
    assert len(geoms) == -(-T_steps // inner_T)
    assert geoms[0].d_in == T_steps * r
    assert geoms[-1].d_out == 0
    t0 = 0
    prev_d = T_steps * r
    for g in geoms:
        assert g.t0 == t0
        t0 += g.T
        assert 1 <= g.T <= inner_T
        assert g.d_in == prev_d          # depths telescope pass-to-pass
        assert g.d_in == g.d_out + g.T * r
        prev_d = g.d_out
        assert g.halo == g.T * r
        assert g.include_halo == (g.T > 1)
        for ax in (0, 1):
            need = block[ax] + 2 * g.d_out
            assert g.grid[ax] % tile[ax] == 0       # kernel-grid contract
            assert need <= g.grid[ax] < need + tile[ax]  # minimal round-up
            assert g.ntiles[ax] == g.grid[ax] // tile[ax]
    # all but the last pass run at exactly the inner depth
    assert all(g.T == inner_T for g in geoms[:-1])


def test_pass_geometry_flat_is_single_pass():
    geoms = nested_pass_geometry((16, 16), (8, 8), 4, 4, 2)
    assert len(geoms) == 1 and geoms[0].grid == (16, 16)
    assert geoms[0].d_out == 0 and geoms[0].d_in == 8


def test_pass_geometry_rejects_bad_depths():
    with pytest.raises(ValueError):
        nested_pass_geometry((16, 16), (8, 8), 4, 0, 2)


# ---------------------------------------------------------------------------
# Per-pass sharded table binning on the unified core/tables.py helpers
# ---------------------------------------------------------------------------

def _fake_plan(pgrid, block):
    """`_pass_source_tables` only reads pgrid/block — no mesh needed."""
    import types
    return types.SimpleNamespace(pgrid=pgrid, block=block)


def _fake_sources(pts):
    import types
    return types.SimpleNamespace(points=np.asarray(pts, np.int64))


@settings(max_examples=15, deadline=None)
@given(seed=hst.integers(0, 2 ** 16),
       pgrid=hst.sampled_from([(1, 1), (2, 1), (2, 2)]),
       T_steps=hst.integers(1, 4), inner_T=hst.integers(1, 4),
       r=hst.sampled_from([1, 2]))
def test_property_pass_source_binning_matches_windows(seed, pgrid, T_steps,
                                                      inner_T, r):
    """Every (shard, tile, slot) entry of `_pass_source_tables` lies in
    that window, with correct window-local coords, and the per-window
    multiplicity equals brute-force window membership — for every pass of
    a random time-nested geometry (the O(pairs) `tables.axis_tile_range`
    enumeration vs the O(windows) definition)."""
    rng = np.random.RandomState(seed)
    px, py = pgrid
    block = (16, 16)
    plan = _fake_plan(pgrid, block)
    nx, ny = px * block[0], py * block[1]
    pts = np.stack([rng.randint(0, nx, 12), rng.randint(0, ny, 12),
                    rng.randint(0, 8, 12)], axis=1)
    g = _fake_sources(pts)
    for geom in nested_pass_geometry(block, (8, 8), T_steps,
                                     min(inner_T, T_steps), r):
        coords, sid, mask, nnz = (np.asarray(a) for a in
                                  H._pass_source_tables(plan, g, geom))
        # the live entries are each window's slots 0..nnz-1
        assert (nnz == mask.sum(axis=-1)).all()
        assert (mask == (np.arange(coords.shape[3]) < nnz[..., None])).all()
        ntx, nty = geom.ntiles
        d, hp = geom.d_out, geom.halo
        pad = 0 if geom.include_halo else hp
        span = (geom.tile[0] + 2 * (hp - pad), geom.tile[1] + 2 * (hp - pad))
        got = np.zeros((px, py, ntx * nty, len(pts)), np.int32)
        for sx in range(px):
            for sy in range(py):
                for t in range(ntx * nty):
                    ti, tj = t // nty, t % nty
                    ox = sx * block[0] + ti * geom.tile[0] - d - hp
                    oy = sy * block[1] + tj * geom.tile[1] - d - hp
                    for k in range(coords.shape[3]):
                        if mask[sx, sy, t, k] == 0.0:
                            continue
                        p = sid[sx, sy, t, k]
                        # window-local coords reconstruct the global point
                        assert (coords[sx, sy, t, k] ==
                                pts[p] - (ox, oy, 0)).all()
                        got[sx, sy, t, p] += 1
                    # brute-force membership for this window
                    lox, loy = ox + pad, oy + pad
                    for p in range(len(pts)):
                        inside = (lox <= pts[p, 0] < lox + span[0]
                                  and loy <= pts[p, 1] < loy + span[1])
                        assert got[sx, sy, t, p] == int(inside), (
                            (sx, sy, t, p, geom))


def test_pass_source_tables_overflow_names_shard_and_tile():
    plan = _fake_plan((2, 1), (16, 16))
    g = _fake_sources([[2, 2, 0], [3, 3, 1], [2, 3, 2]])
    geoms = nested_pass_geometry((16, 16), (8, 8), 2, 2, 1)
    with pytest.raises(ValueError) as ei:
        H._pass_source_tables(plan, g, geoms[0], cap=1)
    msg = str(ei.value)
    assert "shard" in msg and "cap=1" in msg and "requires cap=" in msg


def test_pass_receiver_tables_overflow_names_shard_and_tile():
    import types
    idx = np.zeros((3, 8, 3), np.int64)   # all gather points in one tile
    idx[:, :, 0] = 2
    idx[:, :, 1] = 2
    w = np.full((3, 8), 0.125)
    rec = types.SimpleNamespace(indices=idx, weights=w, num=3)
    plan = _fake_plan((1, 1), (16, 16))
    geoms = nested_pass_geometry((16, 16), (8, 8), 2, 2, 1)
    with pytest.raises(ValueError) as ei:
        H._pass_receiver_tables(plan, rec, geoms[0], cap=4)
    msg = str(ei.value)
    assert "cap=4" in msg and "requires cap=24" in msg
