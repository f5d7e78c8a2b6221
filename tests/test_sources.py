"""Tests for the paper's §II.A source-precomputation scheme.

These enforce the paper's correctness contract: the grid-aligned decomposed
structures (SM/SID/src_dcmp, z-compression, tile tables) reproduce the
original off-the-grid Listing-1 injection exactly.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_stub import given, hst, settings

from repro.core import sources as S
from repro.core.grid import Grid


GRID = Grid(shape=(12, 10, 14), spacing=(10.0, 10.0, 10.0))


def _rand_sources(n, seed=0, inside=True):
    rng = np.random.RandomState(seed)
    lo = np.zeros(3)
    hi = np.asarray(GRID.extent)
    pad = 5.0 if inside else 0.0
    coords = lo + pad + rng.rand(n, 3) * (hi - lo - 2 * pad)
    return S.SparseOperator(coords)


def _listing1_inject(u, op, grid, wavelets, t):
    """The paper's Listing-1 off-the-grid injection (oracle)."""
    st = S.interp_stencil(op, grid)
    u = np.array(u)
    for s in range(op.num):
        for i in range(st.indices.shape[1]):
            xs = tuple(st.indices[s, i])
            u[xs] += st.weights[s, i] * wavelets[t, s]
    return u


class TestInterpStencil:
    def test_weights_sum_to_one(self):
        op = _rand_sources(7)
        st = S.interp_stencil(op, GRID)
        np.testing.assert_allclose(st.weights.sum(axis=1), 1.0, atol=1e-12)

    def test_on_grid_point_single_weight(self):
        # a source exactly on a grid point gets weight 1 on that point
        op = S.SparseOperator(np.array([[30.0, 40.0, 50.0]]))
        st = S.interp_stencil(op, GRID)
        assert np.isclose(st.weights.max(), 1.0)
        nz = st.weights[0] > 1e-12
        assert nz.sum() == 1
        np.testing.assert_array_equal(st.indices[0][np.argmax(st.weights[0])],
                                      [3, 4, 5])


class TestPrecompute:
    def test_discovery_matches_injection_discovery(self):
        op = _rand_sources(5, seed=1)
        wav = S.ricker_wavelet(nt=8, dt=0.001, f0=10.0, num=5)
        wav += 0.5  # ensure nonzero at t=0 for Listing-2 discovery
        g_idx = S.precompute(op, GRID, wav, discover_by_injection=False)
        g_inj = S.precompute(op, GRID, wav, discover_by_injection=True)
        np.testing.assert_array_equal(np.asarray(g_idx.points),
                                      np.asarray(g_inj.points))
        np.testing.assert_allclose(np.asarray(g_idx.src_dcmp),
                                   np.asarray(g_inj.src_dcmp), rtol=1e-6)

    def test_sm_sid_consistency(self):
        op = _rand_sources(4, seed=2)
        wav = S.ricker_wavelet(6, 0.001, 10.0, 4)
        g = S.precompute(op, GRID, wav)
        sm, sid = g.sm(GRID.shape), g.sid(GRID.shape)
        assert set(np.unique(sm)) <= {0, 1}
        # SID is -1 exactly where SM is 0, unique ascending elsewhere
        assert np.all((sid >= 0) == (sm == 1))
        ids = sid[sid >= 0]
        np.testing.assert_array_equal(np.sort(ids), np.arange(g.npts))
        # points are in SID order
        np.testing.assert_array_equal(
            sid[tuple(np.asarray(g.points).T)], np.arange(g.npts))

    @pytest.mark.parametrize("by_injection", [False, True])
    def test_no_grid_sized_array_and_dense_sm_sid_on_request(self,
                                                            by_injection):
        """`precompute` puts nothing grid-sized on a device — every array
        it leaves alive follows the affected points — and the SM / SID
        made on request from the points equal the dense volumes the
        paper's Listing 2 builds, bit for bit, as does src_dcmp read
        through the dense SID."""
        op = _rand_sources(5, seed=11)
        wav = S.ricker_wavelet(6, 0.001, 10.0, 5) + 0.5
        before = {id(a) for a in jax.live_arrays()}
        g = S.precompute(op, GRID, wav, discover_by_injection=by_injection)
        new = [a for a in jax.live_arrays() if id(a) not in before]
        assert new and all(a.size < GRID.npoints for a in new)
        # the dense volumes, built the paper's way: inject into an empty
        # grid, read off the non-zero points in order
        st = S.interp_stencil(op, GRID)
        dense = np.zeros(GRID.shape)
        for k in range(op.num):
            for i in range(st.indices.shape[1]):
                dense[tuple(st.indices[k, i])] += st.weights[k, i] * wav[0, k]
        pts = np.argwhere(dense != 0.0)
        sm = np.zeros(GRID.shape, np.uint8)
        sid = np.full(GRID.shape, -1, np.int32)
        sm[tuple(pts.T)] = 1
        sid[tuple(pts.T)] = np.arange(len(pts), dtype=np.int32)
        got_sm, got_sid = g.sm(GRID.shape), g.sid(GRID.shape)
        assert got_sm.dtype == sm.dtype and got_sid.dtype == sid.dtype
        np.testing.assert_array_equal(got_sm, sm)
        np.testing.assert_array_equal(got_sid, sid)
        ids = sid[tuple(st.indices.reshape(-1, 3).T)]
        dcmp = np.zeros((wav.shape[0], len(pts)))
        np.add.at(dcmp.T, ids, (wav[:, np.repeat(np.arange(op.num), 8)]
                                * st.weights.reshape(-1)[None]).T)
        np.testing.assert_array_equal(np.asarray(g.src_dcmp),
                                      dcmp.astype(np.float32))

    def test_decomposition_matches_listing1(self):
        """Scatter of src_dcmp == the original off-the-grid injection."""
        op = _rand_sources(6, seed=3)
        nt = 5
        wav = np.random.RandomState(0).randn(nt, 6)
        g = S.precompute(op, GRID, wav)
        for t in range(nt):
            u = S.inject(jnp.zeros(GRID.shape), g, jnp.asarray(t))
            oracle = _listing1_inject(np.zeros(GRID.shape), op, GRID, wav, t)
            np.testing.assert_allclose(np.asarray(u), oracle, atol=1e-6)

    def test_colliding_sources_accumulate(self):
        """Two sources sharing affected points (paper: 'points being affected
        by more than one source')."""
        coords = np.array([[31.0, 41.0, 51.0], [33.0, 43.0, 53.0]])
        op = S.SparseOperator(coords)
        wav = np.array([[1.0, 2.0], [3.0, 4.0]])
        g = S.precompute(op, GRID, wav)
        st = S.interp_stencil(op, GRID)
        # both sources share the 8-point cube around (3,4,5)
        shared = set(map(tuple, st.indices[0].reshape(-1, 3).tolist())) & \
            set(map(tuple, st.indices[1].reshape(-1, 3).tolist()))
        assert shared, "test setup: sources must collide"
        for t in range(2):
            u = S.inject(jnp.zeros(GRID.shape), g, jnp.asarray(t))
            oracle = _listing1_inject(np.zeros(GRID.shape), op, GRID, wav, t)
            np.testing.assert_allclose(np.asarray(u), oracle, atol=1e-6)

    def test_linearity_of_decomposition(self):
        """src_dcmp is linear in the wavelets (it is a fixed weight matrix)."""
        op = _rand_sources(3, seed=4)
        w1 = np.random.RandomState(1).randn(4, 3)
        w2 = np.random.RandomState(2).randn(4, 3)
        ga = S.precompute(op, GRID, w1)
        gb = S.precompute(op, GRID, w2)
        gab = S.precompute(op, GRID, 2.0 * w1 + 3.0 * w2)
        np.testing.assert_allclose(
            np.asarray(gab.src_dcmp),
            2.0 * np.asarray(ga.src_dcmp) + 3.0 * np.asarray(gb.src_dcmp),
            rtol=1e-5)


class TestZCompression:
    def test_nnz_counts(self):
        op = _rand_sources(5, seed=5)
        wav = S.ricker_wavelet(4, 0.001, 10.0, 5)
        g = S.precompute(op, GRID, wav)
        zc = S.z_compress(g, GRID.shape)
        np.testing.assert_array_equal(np.asarray(zc.nnz_mask),
                                      g.sm(GRID.shape).sum(axis=2))

    def test_injection_equivalence(self):
        """Listing-5 (z-compressed) == Listing-4 (masked) == scatter."""
        op = _rand_sources(5, seed=6)
        wav = np.random.RandomState(3).randn(4, 5)
        g = S.precompute(op, GRID, wav)
        zc = S.z_compress(g, GRID.shape)
        for t in range(4):
            t_ = jnp.asarray(t)
            u_scatter = S.inject(jnp.zeros(GRID.shape), g, t_)
            u_dense = S.dense_increment(g, t_, GRID.shape)
            u_zc = S.inject_zcompressed(jnp.zeros(GRID.shape), g, zc, t_)
            np.testing.assert_allclose(np.asarray(u_scatter),
                                       np.asarray(u_dense), atol=1e-6)
            np.testing.assert_allclose(np.asarray(u_scatter),
                                       np.asarray(u_zc), atol=1e-6)


class TestTileTables:
    @pytest.mark.parametrize("tile,halo", [((4, 4), 2), ((8, 4), 4),
                                           ((16, 16), 8)])
    def test_tile_scatter_equivalence(self, tile, halo):
        """Scattering via per-tile tables == global scatter."""
        op = _rand_sources(6, seed=7)
        wav = np.random.RandomState(4).randn(3, 6)
        g = S.precompute(op, GRID, wav)
        tab = S.tile_source_tables(g, GRID.shape, tile, halo)
        nx, ny, nz = GRID.shape
        tx, ty = tile
        ntx, nty = -(-nx // tx), -(-ny // ty)
        for t in range(3):
            u = np.zeros(GRID.shape, np.float64)
            vals = np.asarray(g.src_dcmp)[t]
            for ti in range(ntx):
                for tj in range(nty):
                    tt = ti * nty + tj
                    n = int(tab.nnz[tt])
                    for k in range(n):
                        lx, ly, lz = np.asarray(tab.coords[tt, k])
                        sid = int(tab.sid[tt, k])
                        gx = ti * tx - halo + lx
                        gy = tj * ty - halo + ly
                        u[gx, gy, lz] += vals[sid] * float(tab.scale[tt, k])
            ref = np.asarray(S.inject(jnp.zeros(GRID.shape), g,
                                      jnp.asarray(t)))
            np.testing.assert_allclose(u, ref, atol=1e-6)

    def test_local_coords_within_window(self):
        op = _rand_sources(8, seed=8)
        wav = np.ones((2, 8))
        g = S.precompute(op, GRID, wav)
        tile, halo = (4, 4), 4
        tab = S.tile_source_tables(g, GRID.shape, tile, halo)
        nnz = np.asarray(tab.nnz)
        coords = np.asarray(tab.coords)
        for tt in range(nnz.shape[0]):
            for k in range(nnz[tt]):
                lx, ly, _ = coords[tt, k]
                assert halo <= lx < halo + tile[0]
                assert halo <= ly < halo + tile[1]


class TestTileTableEdgeCases:
    """Degenerate inputs the sharded layer feeds the table builders."""

    def test_zero_sources(self):
        """An empty source set produces all-padding tables (cap 1, every
        sid -1, zero scale) of the right tile count — no special-casing in
        the consumers."""
        op = S.SparseOperator(np.zeros((0, 3)))
        g = S.precompute(op, GRID, np.zeros((4, 0)))
        assert g.npts == 0
        tab = S.tile_source_tables(g, GRID.shape, (4, 4), 2,
                                   include_halo=True)
        ntx, nty = -(-GRID.shape[0] // 4), -(-GRID.shape[1] // 4)
        assert tab.coords.shape == (ntx * nty, 1, 3)
        assert np.all(np.asarray(tab.nnz) == 0)
        assert np.all(np.asarray(tab.sid) == -1)
        assert np.all(np.asarray(tab.scale) == 0.0)

    def test_zero_receivers(self):
        gr = S.GriddedReceivers(jnp.zeros((0, 8, 3), jnp.int32),
                                jnp.zeros((0, 8), jnp.float32))
        tab = S.tile_receiver_tables(gr, GRID.shape, (4, 4), 2)
        assert np.all(np.asarray(tab.nnz) == 0)
        assert np.all(np.asarray(tab.rid) == -1)
        assert np.all(np.asarray(tab.weight) == 0.0)

    def test_point_on_tile_boundary_owned_by_next_tile(self):
        """A point at exactly x = tx belongs to tile 1's centre, and its
        window-local coordinate equals the halo overhang."""
        pts = np.array([[4, 0, 0]], np.int32)  # exactly on the x boundary
        g = S.GriddedSources(jnp.asarray(pts), jnp.ones((2, 1), jnp.float32))
        tab = S.tile_source_tables(g, GRID.shape, (4, 4), 0)
        nty = -(-GRID.shape[1] // 4)
        owner = np.flatnonzero(np.asarray(tab.nnz))
        assert list(owner) == [1 * nty + 0]
        np.testing.assert_array_equal(np.asarray(tab.coords[owner[0], 0]),
                                      [0, 0, 0])

    def test_include_halo_duplicates_into_every_window(self):
        """include_halo=True assigns a point to EVERY tile whose window
        (centre + halo) contains it — the paper's Fig. 4b dependency —
        with consistent window-local coordinates."""
        pts = np.array([[4, 4, 1]], np.int32)  # corner of 4 tile centres
        g = S.GriddedSources(jnp.asarray(pts), jnp.ones((2, 1), jnp.float32))
        tile, halo = (4, 4), 2
        tab = S.tile_source_tables(g, GRID.shape, tile, halo,
                                   include_halo=True)
        nnz = np.asarray(tab.nnz)
        ntx, nty = -(-GRID.shape[0] // 4), -(-GRID.shape[1] // 4)
        hit = np.flatnonzero(nnz)
        # windows of tiles (ti, tj) with ti*4 - 2 <= 4 < ti*4 + 6 -> ti in
        # {0, 1}; same in y -> exactly 4 windows, one entry each
        assert sorted(hit) == [0 * nty + 0, 0 * nty + 1,
                               1 * nty + 0, 1 * nty + 1]
        assert np.all(nnz[hit] == 1)
        for tt in hit:
            ti, tj = tt // nty, tt % nty
            lx, ly, lz = np.asarray(tab.coords[tt, 0])
            assert (lx, ly, lz) == (4 - (ti * 4 - halo), 4 - (tj * 4 - halo),
                                    1)
        # without halo the same point is owned exactly once
        tab0 = S.tile_source_tables(g, GRID.shape, tile, halo)
        assert int(np.asarray(tab0.nnz).sum()) == 1

    def test_receiver_boundary_gather_points_split_by_owner(self):
        """A receiver whose 8 gather points straddle a tile boundary gets
        its entries split across the owning tiles; partials still sum to
        the exact interpolation."""
        # place the receiver between grid x=3 and x=4 (tile edge at 4)
        rec = S.SparseOperator(np.array([[35.0, 21.0, 13.0]]))
        gr = S.precompute_receivers(rec, GRID)
        tab = S.tile_receiver_tables(gr, GRID.shape, (4, 4), 2)
        nnz = np.asarray(tab.nnz)
        assert (nnz > 0).sum() >= 2  # entries in at least two tiles
        u = np.random.RandomState(11).rand(*GRID.shape).astype(np.float32)
        out = 0.0
        nty = -(-GRID.shape[1] // 4)
        for tt in np.flatnonzero(nnz):
            ti, tj = tt // nty, tt % nty
            for k in range(nnz[tt]):
                lx, ly, lz = np.asarray(tab.coords[tt, k])
                out += float(tab.weight[tt, k]) * u[ti * 4 - 2 + lx,
                                                    tj * 4 - 2 + ly, lz]
        ref = float(np.asarray(S.interpolate(jnp.asarray(u), gr))[0])
        np.testing.assert_allclose(out, ref, rtol=1e-4)


class TestReceivers:
    def test_interpolation_roundtrip(self):
        """A receiver exactly on a grid point reads the grid value."""
        rec = S.SparseOperator(np.array([[20.0, 30.0, 40.0]]))
        gr = S.precompute_receivers(rec, GRID)
        u = jnp.arange(GRID.npoints, dtype=jnp.float32).reshape(GRID.shape)
        val = S.interpolate(u, gr)
        np.testing.assert_allclose(np.asarray(val), np.asarray(u[2, 3, 4]),
                                   rtol=1e-6)

    def test_interpolation_linear_field(self):
        """Trilinear interpolation is exact on (multi)linear fields."""
        rec = _rand_sources(9, seed=9)
        gr = S.precompute_receivers(rec, GRID)
        nx, ny, nz = GRID.shape
        X, Y, Z = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                              indexing="ij")
        u = jnp.asarray(2.0 * X + 3.0 * Y - Z + 1.0, jnp.float32)
        vals = S.interpolate(u, gr)
        fi = GRID.physical_to_index(rec.coords)
        expect = 2 * fi[:, 0] + 3 * fi[:, 1] - fi[:, 2] + 1.0
        np.testing.assert_allclose(np.asarray(vals), expect, rtol=1e-4)

    def test_tile_receiver_partials_sum(self):
        rec = _rand_sources(5, seed=10)
        gr = S.precompute_receivers(rec, GRID)
        tab = S.tile_receiver_tables(gr, GRID.shape, (4, 4), 2)
        u = np.random.RandomState(5).rand(*GRID.shape).astype(np.float32)
        # accumulate partials per receiver from the tile tables
        out = np.zeros(5)
        nnz = np.asarray(tab.nnz)
        nx, ny, _ = GRID.shape
        nty = -(-ny // 4)
        for tt in range(nnz.shape[0]):
            ti, tj = tt // nty, tt % nty
            for k in range(nnz[tt]):
                lx, ly, lz = np.asarray(tab.coords[tt, k])
                rid = int(tab.rid[tt, k])
                gx, gy = ti * 4 - 2 + lx, tj * 4 - 2 + ly
                out[rid] += float(tab.weight[tt, k]) * u[gx, gy, lz]
        ref = np.asarray(S.interpolate(jnp.asarray(u), gr))
        np.testing.assert_allclose(out, ref, rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(n=hst.integers(1, 6), seed=hst.integers(0, 2 ** 16), nt=hst.integers(1, 4))
def test_property_decomposed_equals_listing1(n, seed, nt):
    """Property: for ANY source set and wavelets, the grid-aligned scatter
    equals the off-the-grid Listing-1 injection (the paper's core claim)."""
    rng = np.random.RandomState(seed)
    coords = rng.rand(n, 3) * (np.asarray(GRID.extent) - 10.0) + 5.0
    op = S.SparseOperator(coords)
    wav = rng.randn(nt, n)
    g = S.precompute(op, GRID, wav)
    t = int(rng.randint(nt))
    u = S.inject(jnp.zeros(GRID.shape), g, jnp.asarray(t))
    oracle = _listing1_inject(np.zeros(GRID.shape), op, GRID, wav, t)
    np.testing.assert_allclose(np.asarray(u), oracle, atol=1e-5)


class TestSincEndToEnd:
    """The wider Kaiser-sinc kernel through the full §II pipeline: the
    decompose/inject machinery is footprint-agnostic, so a radius-r spec
    must reproduce the Listing-1 oracle run with the same stencil."""

    def _spec(self, r=2):
        from repro.core import interp as I
        return I.InterpSpec(kernel="sinc", radius=r)

    def test_precompute_inject_matches_listing1(self):
        spec = self._spec()
        rng = np.random.RandomState(11)
        ext = np.asarray(GRID.extent)
        # deep interior so the radius-2 support stays in-grid
        op = S.SparseOperator(25.0 + rng.rand(3, 3) * (ext - 50.0))
        wav = S.ricker_wavelet(3, 1e-3, f0=15.0, num=3)
        g = S.precompute(op, GRID, wav, interp=spec)
        st = S.interp_stencil(op, GRID, spec)
        assert st.indices.shape[1] == 4 ** 3
        for t in range(3):
            u = S.inject(jnp.zeros(GRID.shape), g, jnp.asarray(t))
            oracle = np.zeros(GRID.shape)
            for s in range(op.num):
                for i in range(st.indices.shape[1]):
                    oracle[tuple(st.indices[s, i])] += \
                        st.weights[s, i] * wav[t, s]
            np.testing.assert_allclose(np.asarray(u), oracle, atol=1e-6)

    def test_receiver_interpolation_constant_and_linear_fields(self):
        # normalized sinc rows reproduce a CONSTANT field exactly; a
        # linear field only approximately (the Kaiser window trades
        # polynomial reproduction for band-limited accuracy)
        spec = self._spec()
        rng = np.random.RandomState(12)
        ext = np.asarray(GRID.extent)
        pts = 25.0 + rng.rand(5, 3) * (ext - 50.0)
        # f32 gather path here; the f64 row-sum-1 exactness claim is
        # pinned in test_interp.py on the numpy coefficients directly
        gr = S.precompute_receivers(S.SparseOperator(pts), GRID, interp=spec)
        const = jnp.full(GRID.shape, 3.25, jnp.float32)
        np.testing.assert_allclose(np.asarray(S.interpolate(const, gr)),
                                   3.25, atol=1e-5)
        xs = np.arange(GRID.shape[0], dtype=np.float64)
        field = jnp.asarray(np.broadcast_to(
            xs[:, None, None], GRID.shape).copy())
        got = np.asarray(S.interpolate(field, gr))
        np.testing.assert_allclose(got, pts[:, 0] / GRID.spacing[0],
                                   atol=0.05)

    def test_tile_tables_carry_wider_footprint(self):
        spec = self._spec()
        rng = np.random.RandomState(13)
        ext = np.asarray(GRID.extent)
        op = S.SparseOperator(25.0 + rng.rand(2, 3) * (ext - 50.0))
        wav = S.ricker_wavelet(2, 1e-3, f0=15.0, num=2)
        g = S.precompute(op, GRID, wav, interp=spec)
        gr = S.precompute_receivers(op, GRID, interp=spec)
        tab = S.tile_source_tables(g, GRID.shape, (4, 4), 1)
        rtab = S.tile_receiver_tables(gr, GRID.shape, (4, 4), 1)
        assert int(np.asarray(tab.nnz).sum()) == g.npts
        # every (receiver, support-point) pair with non-zero weight binned
        nz = int((np.asarray(gr.weights) != 0.0).sum())
        assert int(np.asarray(rtab.nnz).sum()) == nz
