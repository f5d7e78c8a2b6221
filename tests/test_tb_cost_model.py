"""TB cost-model invariants: `TBPlan` analytics and the per-physics
autotuner (`plan_for_physics` / `PHYSICS_COSTS`).

The analytic model is what stands in for the paper's Table-I autotuning
sweep on TPU, so its qualitative behaviour is contract: temporal blocking
must save HBM traffic, the trapezoid's redundant-rim overlap must grow
with T and shrink with tile size, and when overlap growth beats the
traffic savings (the paper's SO-12 result) the sweep must fall back to
T = 1.
"""
import math

import pytest

from repro.core.temporal_blocking import (PHYSICS_COSTS, TBPlan,
                                          autotune_plan, plan_for_physics,
                                          plan_hierarchy)


# ---------------------------------------------------------------------------
# TBPlan invariants
# ---------------------------------------------------------------------------

def test_overlap_factor_is_one_without_blocking():
    assert TBPlan((32, 32), T=1, radius=0).overlap_factor() == 1.0
    # T=1 still reads a halo but computes the window once; overlap > 1
    assert TBPlan((32, 32), T=1, radius=2).overlap_factor() > 1.0


def test_overlap_factor_monotone_in_T_and_tile():
    base = TBPlan((32, 32), T=2, radius=2).overlap_factor()
    deeper = TBPlan((32, 32), T=8, radius=2).overlap_factor()
    bigger = TBPlan((128, 128), T=2, radius=2).overlap_factor()
    assert deeper > base        # more redundant rim per step
    assert bigger < base        # amortized over a larger centre
    assert base > 1.0


def test_overlap_factor_closed_form():
    """overlap = sum_k prod_d (tile + 2(T-k)r) / (T * prod_d tile)."""
    plan = TBPlan((16, 8), T=3, radius=2)
    expect = sum((16 + 2 * (3 - k) * 2) * (8 + 2 * (3 - k) * 2)
                 for k in range(3)) / (3 * 16 * 8)
    assert math.isclose(plan.overlap_factor(), expect)


def test_vmem_bytes_scales_with_fields_and_window():
    plan = TBPlan((32, 32), T=4, radius=2)
    nz = 128
    one = plan.vmem_bytes(nz, fields=1)
    wx, wy, wz = plan.window(nz)
    assert one == wx * wy * wz * 4
    assert plan.vmem_bytes(nz, fields=13) == 13 * one  # elastic windows
    assert plan.vmem_bytes(nz, fields=5, dtype_bytes=2) == one * 5 // 2


def test_hbm_traffic_drops_with_T():
    """The whole point of temporal blocking: bytes/point-step falls ~T-fold
    (minus the halo re-read) for tiles comfortably larger than the halo."""
    nz = 128
    t1 = TBPlan((64, 64), T=1, radius=2).hbm_bytes_per_point_step(nz)
    t8 = TBPlan((64, 64), T=8, radius=2).hbm_bytes_per_point_step(nz)
    assert t8 < t1 / 4
    # and the naive (no-halo) lower bound is never beaten
    naive = (4 + 1) * 4.0 / 8  # read+write fields over T=8
    assert t8 > naive


def test_hbm_traffic_counts_fields():
    nz = 64
    plan = TBPlan((32, 32), T=2, radius=2)
    a = plan.hbm_bytes_per_point_step(nz, read_fields=4, write_fields=2)
    b = plan.hbm_bytes_per_point_step(nz, read_fields=13, write_fields=9)
    assert b > 2 * a  # elastic moves >2x the acoustic bytes


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------

def test_autotune_respects_vmem_budget():
    plan, log = autotune_plan(nz=128, radius=2, vmem_budget=8 * 2 ** 20)
    assert plan.vmem_bytes(128, 5) <= 8 * 2 ** 20
    assert all(TBPlan(t[:2], t[2], 2).vmem_bytes(128, 5) <= 8 * 2 ** 20
               for t in log)


def test_autotune_rejects_impossible_budget():
    with pytest.raises(ValueError):
        autotune_plan(nz=4096, radius=8, vmem_budget=2 ** 10)


def test_autotune_falls_back_to_T1_when_compute_bound():
    """The paper's SO-12 result: when the kernel is compute-bound, any
    T > 1 only adds redundant rim flops, so the sweep returns T = 1."""
    plan, _ = autotune_plan(nz=512, radius=12, flops_per_point=1e5)
    assert plan.T == 1


def test_autotune_blocks_when_memory_bound():
    plan, _ = autotune_plan(nz=512, radius=2, flops_per_point=40.0)
    assert plan.T > 1


# ---------------------------------------------------------------------------
# Interconnect term (the sharded outer trapezoid, DESIGN.md §4)
# ---------------------------------------------------------------------------

def test_exchange_bytes_closed_form():
    """x exchange: 2 strips (H, by, nz); y exchange on the x-padded block:
    2 strips (bx + 2H, H, nz) — per exchanged field."""
    plan = TBPlan((16, 16), T=3, radius=2)  # halo H = 6
    bx, by, nz, f = 32, 24, 128, 9
    expect = (2 * 6 * by * nz + 2 * (bx + 12) * 6 * nz) * f * 4
    assert plan.exchange_bytes_per_tile((bx, by), nz, fields=f) == expect


def test_exchange_bytes_grow_with_depth():
    """Deeper tiles exchange more bytes (the rim grows with H = T*r) but
    amortize latency: per point-step, the latency share falls as 1/T."""
    block, nz = (64, 64), 128
    b2 = TBPlan((16, 16), T=2, radius=2).exchange_bytes_per_tile(block, nz)
    b8 = TBPlan((16, 16), T=8, radius=2).exchange_bytes_per_tile(block, nz)
    assert b8 > b2
    lat2 = TBPlan((16, 16), T=2, radius=2).exchange_seconds_per_point_step(
        block, nz, 1, link_bw=1e30, link_latency=1.0)
    lat8 = TBPlan((16, 16), T=8, radius=2).exchange_seconds_per_point_step(
        block, nz, 1, link_bw=1e30, link_latency=1.0)
    assert lat8 < lat2 / 3.9


def test_mesh_aware_autotune_respects_block():
    """Plans whose halo or tile exceed the per-device block are infeasible
    (single-hop neighbor exchange)."""
    block = (32, 32)
    plan, log = autotune_plan(nz=128, radius=2, mesh_block=block)
    assert plan.halo <= min(block)
    assert plan.tile[0] <= block[0] and plan.tile[1] <= block[1]
    assert all(TBPlan(t[:2], t[2], 2).halo <= min(block) for t in log)
    assert all("comm_s" in e for e in log.values())


def test_mesh_aware_latency_vs_bandwidth_regimes():
    """Latency-dominated interconnect -> deep T (amortize the exchange
    count); bandwidth-starved interconnect -> shallow T (rim bytes grow
    with the exchange depth) — the multi-chip SO-12 analogue."""
    kw = dict(nz=128, radius=2, mesh_block=(32, 32))
    lat_bound, _ = autotune_plan(link_bw=1e30, link_latency=1.0, **kw)
    bw_bound, _ = autotune_plan(link_bw=1e3, link_latency=0.0, **kw)
    assert bw_bound.T == 1
    assert lat_bound.T > bw_bound.T


def test_plan_for_physics_mesh_aware():
    """plan_for_physics prices the exchange with the physics' state-field
    count (what actually crosses the link: 2 acoustic, 9 elastic)."""
    kw = dict(nz=128, order=4, mesh_block=(32, 32), link_bw=1e9,
              link_latency=1e-6)
    _, log_ac = plan_for_physics("acoustic", **kw)
    _, log_el = plan_for_physics("elastic", **kw)
    key = next(k for k in log_ac if k in log_el)
    assert log_el[key]["comm_s"] > log_ac[key]["comm_s"]
    # elastic halos are 2x deeper per step: feasible depths shrink
    el_plan, _ = plan_for_physics("elastic", nz=128, order=4,
                                  mesh_block=(16, 16))
    assert el_plan.halo <= 16


def test_exchange_bytes_per_field_depths():
    """Per-field depths price each field's strip at its own depth; zero
    depth drops the field from both the bytes and the latency term."""
    plan = TBPlan((16, 16), T=2, radius=2)  # halo 4
    block, nz = (32, 32), 128

    def strip(d):
        return 2 * d * nz * (32 + 32 + 2 * d) * 4

    got = plan.exchange_bytes_per_tile(block, nz, depths=(4, 2, 0))
    assert got == strip(4) + strip(2)
    # uniform call unchanged
    assert plan.exchange_bytes_per_tile(block, nz, fields=3) == 3 * strip(4)
    # latency counts only the fields that actually move
    lat = plan.exchange_seconds_per_point_step(
        block, nz, 3, link_bw=1e30, link_latency=1.0, depths=(4, 2, 0))
    lat_all = plan.exchange_seconds_per_point_step(
        block, nz, 3, link_bw=1e30, link_latency=1.0)
    assert lat == pytest.approx(lat_all * 2 / 3)


def test_elastic_per_field_exchange_reduced():
    """The acceptance signal: with the physics' halo lags, elastic moves
    fewer bytes per exchange than the uniform-depth baseline (stresses are
    first differentiated one half-step after the velocities, TTI/acoustic
    previous-time levels are pointwise-only)."""
    for physics in ("acoustic", "tti", "elastic"):
        hier, _ = plan_hierarchy(physics, nz=128, order=4, block=(32, 32))
        assert hier.exchange_bytes(128) < hier.exchange_bytes_uniform(128)


def test_plan_hierarchy_inner_divides_block():
    block = (48, 48)
    hier, log = plan_hierarchy("acoustic", nz=128, order=4, block=block,
                               tiles=(8, 12, 16, 24, 32, 48))
    assert block[0] % hier.inner.tile[0] == 0
    assert block[1] % hier.inner.tile[1] == 0
    assert hier.halo <= min(block)
    # every feasible sweep entry divides too (the inner kernel grid needs it)
    assert all(block[0] % t[0] == 0 and block[1] % t[1] == 0 for t in log)


def test_plan_hierarchy_overlap_credit():
    """Overlap is selected when the exchange is worth hiding (comparable
    to compute) and rejected when the exchange is ~free (the rim-strip
    recompute would be pure loss)."""
    kw = dict(nz=128, order=4, block=(32, 32))
    costly, _ = plan_hierarchy("acoustic", link_bw=1e9, link_latency=1e-5,
                               **kw)
    free, _ = plan_hierarchy("acoustic", link_bw=1e30, link_latency=0.0,
                             **kw)
    assert costly.overlap
    assert not free.overlap


def test_nested_vmem_below_flat_at_fixed_outer_T():
    """The time-nesting acceptance invariant: at a FIXED outer exchange
    depth, shrinking the inner T shrinks the VMEM window while the
    exchange bytes per point-step are unchanged (they depend only on the
    outer depth)."""
    block, nz = (64, 64), 128
    _, log = autotune_plan(nz=nz, radius=2, mesh_block=block,
                           tiles=(16,), depths=(1, 2, 4, 8),
                           outer_depths=(8,))
    entries = {k[2]: e for k, e in log.items()
               if k[:2] == (16, 16) and k[3] == 8}
    assert set(entries) == {1, 2, 4, 8}
    for ti in (1, 2, 4):
        assert entries[ti]["vmem_bytes"] < entries[8]["vmem_bytes"]
        assert entries[ti]["exchange_bytes"] == entries[8]["exchange_bytes"]
    vmems = [entries[t]["vmem_bytes"] for t in (1, 2, 4, 8)]
    assert vmems == sorted(vmems)
    # (nested compute may be cheaper OR dearer than deep-flat: block-level
    # rim redundancy vs tile-level trapezoid overlap — the rim pricing
    # itself is pinned by test_nested_compute_multiplier_collapses_to_flat)


def test_nested_compute_multiplier_collapses_to_flat():
    """inner T == outer T with a block-dividing tile IS the flat schedule
    (single pass, no extended rim)."""
    plan = TBPlan((16, 16), T=4, radius=2)
    assert plan.nested_compute_multiplier((64, 64), 4) == \
        pytest.approx(plan.overlap_factor())
    assert plan.nested_hbm_bytes_per_point_step((64, 64), 4, 128) == \
        pytest.approx(plan.hbm_bytes_per_point_step(128))
    # nesting pays rim compute: two depth-2 passes per depth-4 exchange
    half = TBPlan((16, 16), T=2, radius=2)
    assert half.nested_compute_multiplier((64, 64), 4) > \
        half.overlap_factor()


def test_plan_hierarchy_selects_nested_under_vmem_pressure():
    """A latency-dominated link wants a deep exchange; a tight VMEM
    budget forbids the deep flat window — the joint sweep must decouple
    the levels (inner T < outer T, outer T a multiple of inner T) and the
    chosen nested plan's window must be strictly smaller than the flat
    plan's at the same exchange depth."""
    hier, log = plan_hierarchy("acoustic", nz=128, order=4, block=(64, 64),
                               vmem_budget=2 * 2 ** 20, link_bw=1e30,
                               link_latency=1.0, tiles=(8, 16, 32),
                               depths=(1, 2, 4, 8))
    assert hier.outer_T % hier.inner.T == 0
    assert hier.inner.T < hier.outer_T
    flat = TBPlan(hier.inner.tile, hier.outer_T, hier.inner.radius)
    assert hier.vmem_bytes(128, 5) < flat.vmem_bytes(128, 5)
    assert hier.vmem_bytes(128, 5) <= 2 * 2 ** 20
    # equal exchange bytes at equal outer depth, by construction
    assert hier.exchange_bytes(128) == \
        hier.outer.exchange_bytes_per_tile((64, 64), 128,
                                           depths=hier.field_depths)


def test_nested_sweep_keeps_flat_variant():
    """An inner depth that divides none of `outer_depths` still competes
    with its flat (T_out == T) schedule instead of silently vanishing
    from the sweep."""
    _, log = autotune_plan(nz=128, radius=2, mesh_block=(64, 64),
                           tiles=(16,), depths=(3, 6), outer_depths=(4, 8))
    assert (16, 16, 3, 3) in log and (16, 16, 6, 6) in log
    assert all(k[3] % k[2] == 0 for k in log)


def test_plan_hierarchy_outer_is_multiple_of_inner():
    for physics in ("acoustic", "tti", "elastic"):
        hier, log = plan_hierarchy(physics, nz=128, order=4, block=(32, 32))
        assert hier.outer_T % hier.inner.T == 0
        assert hier.halo == hier.outer_T * hier.inner.radius
        # every swept candidate respects the divisibility contract
        assert all(k[3] % k[2] == 0 for k in log)


def test_serialized_exchange_is_additive():
    """Without overlap the exchange blocks the tile: cost = max(comp, mem)
    + comm, not max of the three."""
    _, log = autotune_plan(nz=128, radius=2, mesh_block=(32, 32),
                           link_bw=1e9, link_latency=1e-6)
    for e in log.values():
        assert e["cost_s"] == pytest.approx(
            max(e["compute_s"], e["memory_s"]) + e["comm_s"])


# ---------------------------------------------------------------------------
# Per-physics pricing
# ---------------------------------------------------------------------------

def test_physics_costs_registry():
    ac, ti, el = (PHYSICS_COSTS[k] for k in ("acoustic", "tti", "elastic"))
    # acoustic reproduces the autotune_plan defaults: the kernel holds a
    # pair of windows per state field (u_prev, u) plus m and damp
    assert (ac.fields, ac.read_fields) == (6, 4)
    assert (ti.fields, el.fields) == (14, 22)
    # field counts: state + params
    assert (ti.state_fields, ti.param_fields) == (4, 6)
    assert (el.state_fields, el.param_fields) == (9, 4)
    # elastic/TTI consume double halo per step
    for order in (4, 8):
        assert ac.step_radius(order) == order // 2
        assert ti.step_radius(order) == order
        assert el.step_radius(order) == order
    # flop density ordering: TTI's rotated Laplacian is the most
    # compute-heavy, acoustic the lightest (paper §III.B)
    assert ti.flops_per_point(8) > el.flops_per_point(8) \
        > ac.flops_per_point(8)


def test_plan_for_physics_acoustic_matches_defaults():
    """Acoustic pricing must collapse to the plain autotune_plan call the
    benchmarks have always made (same radius/fields/flops)."""
    ac = PHYSICS_COSTS["acoustic"]
    got, _ = plan_for_physics("acoustic", nz=512, order=4)
    want, _ = autotune_plan(nz=512, radius=2,
                            flops_per_point=ac.flops_per_point(4),
                            fields=6, read_fields=4, write_fields=2)
    assert got == want


def test_plan_for_physics_high_order_falls_back():
    """Fig. 9 ordering: at SO-12 the heavy physics autotune back to the
    spatially-blocked schedule (T = 1), while memory-bound acoustic at
    SO-4 keeps a deep time tile."""
    assert plan_for_physics("tti", nz=512, order=12)[0].T == 1
    # elastic SO-12's 22 whole-z windows fit only 8-wide tiles (ROADMAP R1)
    assert plan_for_physics("elastic", nz=512, order=12,
                            tiles=(8, 16, 32))[0].T == 1
    assert plan_for_physics("acoustic", nz=512, order=4)[0].T > 1


def test_physics_costs_match_kernel_specs():
    """PHYSICS_COSTS keeps numeric copies of the kernel step specs so core
    never imports kernels — guard the two registries against drift."""
    from repro.kernels import tb_physics as phys
    for name, pc in PHYSICS_COSTS.items():
        tp = phys.PHYSICS[name]
        assert pc.state_fields == len(tp.state_fields)
        assert pc.param_fields == len(tp.param_fields)
        assert pc.evolved_fields == len(tp.evolved_fields)
        assert pc.radius_mult == tp.radius_mult
        assert pc.halo_lag_units == tp.halo_lags
        for order in (2, 4, 8, 12):
            assert pc.step_radius(order) == tp.step_radius(order)
            for T in (1, 2, 4):
                h = T * tp.step_radius(order)
                depths = tp.field_halo_depths(T, order)
                assert depths == tuple(
                    max(h - lag, 0) for lag in pc.exchange_lags(order))
                assert max(depths) == h  # some field always ships full
    assert set(PHYSICS_COSTS) == set(phys.PHYSICS)


def test_plan_for_physics_kwargs_override():
    plan, _ = plan_for_physics("elastic", nz=128, order=4, depths=(1, 2),
                               tiles=(32,))
    assert plan.tile == (32, 32) and plan.T in (1, 2)
