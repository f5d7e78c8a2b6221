"""Distributed-layer tests.

The halo-exchange propagator and the dry-run need >1 device; they run in a
subprocess with forced host devices (XLA locks device count at first init,
so the main test process, which sees 1 device, cannot host them).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV8 = {**os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": os.path.join(REPO, "src")}


def _run(args, env=None, timeout=900):
    return subprocess.run([sys.executable, *args], cwd=REPO,
                          env=env or ENV8, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.slow
@pytest.mark.parametrize("physics,T,order,n,nt", [
    ("acoustic", 1, 4, 32, 8),    # spatially-blocked baseline path
    ("acoustic", 2, 4, 32, 8),
    ("acoustic", 4, 8, 64, 8),
    ("acoustic", 2, 4, 32, 7),    # nt % T != 0 -> remainder tile
    ("elastic", 2, 4, 32, 5),     # 9-field tuple exchange + remainder
    ("tti", 2, 4, 32, 5),         # coupled p/r + remainder
])
def test_distributed_equals_reference(physics, T, order, n, nt):
    """Sharded temporally-blocked propagation == Listing-1 reference on a
    4x2 device mesh (paper contract, multi-device), for every physics —
    wavefields AND per-step receiver traces."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              physics, "--n", str(n), "--nt", str(nt), "--T", str(T),
              "--order", str(order)])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("T,nt", [
    (2, 4),
    (3, 7),   # the shrinking trapezoid over the shard's external domain
])
def test_distributed_pallas_inner_equals_reference(T, nt):
    """The SAME Pallas TB kernel runs per shard (inner trapezoid) under the
    deep-halo exchange (outer trapezoid) — the unified execution layer."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--inner",
              "pallas", "--n", "32", "--nt", str(nt), "--T", str(T)])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("physics,inner", [
    ("acoustic", "jnp"), ("acoustic", "pallas"),
    ("tti", "jnp"), ("tti", "pallas"),
    ("elastic", "jnp"), ("elastic", "pallas"),
])
def test_two_level_inner_tile_equals_reference(physics, inner):
    """Hierarchical plan: inner tile (4, 8) STRICTLY smaller than the
    (8, 16) shard block, spatially tiling the exchanged block inside the
    per-shard schedule — both executors, every physics, remainder tile
    included (nt=5, T=2)."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              physics, "--inner", inner, "--inner-tile", "4,8",
              "--n", "32", "--nt", "5", "--T", "2"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("physics,inner,inner_T,outer_T", [
    # acoustic r_step=2 on the (8, 16) block: outer_T=4 (halo 8) with
    # every proper divisor as the inner depth
    ("acoustic", "jnp", 1, 4), ("acoustic", "jnp", 2, 4),
    ("acoustic", "pallas", 1, 4), ("acoustic", "pallas", 2, 4),
    # TTI/elastic r_step=4: outer_T=2 (halo 8) nested as two depth-1
    # passes per exchange
    ("tti", "jnp", 1, 2), ("tti", "pallas", 1, 2),
    ("elastic", "jnp", 1, 2), ("elastic", "pallas", 1, 2),
])
def test_time_nested_equals_reference(physics, inner, inner_T, outer_T):
    """The tentpole: inner_T < outer_T runs outer_T/inner_T inner passes
    per deep exchange over pass-by-pass-shrinking windows — bit-exact
    against the single-level reference for every physics and both
    executors, nt % outer_T != 0 included (nt=6: remainder 2 for
    acoustic, whole tiles for TTI/elastic at outer_T=2 — nt=5 covers
    their remainder)."""
    nt = 6 if physics == "acoustic" else 5
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              physics, "--inner", inner, "--inner-tile", "4,8",
              "--n", "32", "--nt", str(nt), "--T", str(inner_T),
              "--outer-T", str(outer_T)])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("inner_T", [1, 2])
def test_time_nested_overlap_equals_reference(inner_T):
    """Overlap composes with nesting: the split first step consumes pass
    0's first timestep, the remaining T-1 steps chunk at the inner depth
    — inner_T=2 makes that remainder odd (passes of depth 2 then 1), so
    the shallower-than-inner_T final pass is exercised WITH overlap."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              "acoustic", "--inner", "pallas", "--inner-tile", "4,8",
              "--overlap", "--n", "32", "--nt", "7", "--T", str(inner_T),
              "--outer-T", "4"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


def test_inner_depth_guard():
    """inner_plan.T above the exchange depth is rejected at validate."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.temporal_blocking import TBPlan
    from repro.distributed.halo import DistTBPlan
    from repro.kernels import tb_physics as phys

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    plan = DistTBPlan(mesh=mesh, grid_shape=(32, 32, 8),
                      physics=phys.ACOUSTIC, order=4, T=2,
                      inner_plan=TBPlan((8, 8), 4, 2))
    with pytest.raises(ValueError, match="inner plan depth"):
        plan.validate()
    # nested depths below T are accepted
    plan._replace(inner_plan=TBPlan((8, 8), 1, 2)).validate()


@pytest.mark.slow
@pytest.mark.parametrize("physics,inner", [
    ("acoustic", "pallas"), ("elastic", "jnp"), ("tti", "jnp"),
])
def test_overlapped_exchange_equals_reference(physics, inner):
    """The overlapped deep exchange (split interior/rim first step, then
    the inner executor at depth H - r_step) is bit-compatible with the
    serialized schedule — combined with an inner tile below the block."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              physics, "--inner", inner, "--inner-tile", "4,8",
              "--overlap", "--n", "32", "--nt", "5", "--T", "2"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


@pytest.mark.slow
def test_uniform_halo_matches_per_field():
    """--uniform-halo (full-depth exchange for every field) and the
    default per-field depths agree with the reference — the depth
    reduction never changes valid centres, only exchange bytes."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              "elastic", "--uniform-halo", "--n", "32", "--nt", "4",
              "--T", "2"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


@pytest.mark.slow
def test_auto_plan_self_check():
    """--auto-plan runs the joint (T, inner tile, overlap) autotuner for
    the shard block and the chosen hierarchical plan passes parity."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--auto-plan",
              "--n", "32", "--nt", "8"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "auto-plan:" in r.stdout
    assert "CHECK PASS" in r.stdout


@pytest.mark.slow
def test_fig12_dryrun_reports_joint_plans():
    """The scaling benchmark's cost-model sweep reports joint (outer,
    inner tile, inner T, overlap) selections with elastic exchange bytes
    reduced vs the uniform-depth baseline, and demonstrates the nested
    acceptance point: a deep-outer plan whose VMEM window is strictly
    smaller than the flat plan's at equal exchange bytes (asserted inside
    the sweep itself)."""
    r = _run(["-m", "benchmarks.fig12_scaling", "--dryrun"],
             env={**os.environ,
                  "PYTHONPATH": os.pathsep.join(
                      (os.path.join(REPO, "src"), REPO))})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "# plan elastic" in r.stdout
    assert "T=" in r.stdout and "overlap=" in r.stdout
    assert "inner_T=" in r.stdout
    assert "# nested acoustic" in r.stdout and "vs flat" in r.stdout


@pytest.mark.slow
def test_receiver_traces_invariant_across_T():
    """Per-step receiver traces are a schedule invariant: T in {1, 2, 4}
    must produce the same (nt, nrec) trace (regression for the old
    'receivers only every T steps' restriction)."""
    r = _run(["-m", "repro.launch.stencil_dist", "--sweep-T", "1,2,4",
              "--n", "32", "--nt", "8"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "SWEEP PASS" in r.stdout


@pytest.mark.slow
def test_halo_depth_guard():
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--n", "16",
              "--nt", "8", "--T", "8", "--order", "8"])
    assert r.returncode != 0
    assert "halo depth" in (r.stdout + r.stderr)


@pytest.mark.slow
def test_dryrun_single_cell_multipod():
    """Multi-pod (2, 16, 16) mesh lower+compile for one representative
    cell, inside the dry-run's own 512-device process."""
    out = os.path.join(REPO, "results", "test_dryrun_cell.json")
    r = _run(["-m", "repro.launch.dryrun", "--arch", "qwen3-1.7b",
              "--shape", "decode_32k", "--multipod", "--out", out],
             env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.load(open(out))[0]
    assert rec["status"] == "ok"
    assert rec["devices"] == 512
    assert rec["memory"]["temp_size_in_bytes"] > 0


def test_sharding_rules_divisibility():
    """Rules must never shard a non-divisible dim (MQA kv=1 over tp=16)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro import configs
    from repro.distributed.sharding import ShardingRules
    from repro.launch import mesh as mesh_lib
    from repro.models import api

    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
    cfg = configs.get("granite-34b")
    rules = ShardingRules(mesh=mesh, cfg=cfg)
    # fake tp=16 axis sizes by checking divisibility logic directly
    params = api.param_specs(cfg, configs.TRAIN_4K)
    specs = rules.param_pspecs(params)

    def check(path, leaf, spec):
        for d, ax in enumerate(spec):
            if ax is not None:
                assert leaf.shape[d] % rules.axis_size(ax) == 0

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), params, specs)


def test_zero1_adds_data_sharding():
    from repro import configs
    from repro.distributed.sharding import ShardingRules
    from repro.launch import mesh as mesh_lib
    from repro.models import api
    from repro.optim import adamw_init
    import jax

    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
    cfg = configs.get_reduced("qwen2-7b")
    rules = ShardingRules(mesh=mesh, cfg=cfg)
    params = api.param_specs(cfg, configs.TRAIN_4K)
    opt = jax.eval_shape(lambda p: adamw_init(p), params)
    specs = rules.opt_pspecs(opt)
    # at least the large master leaves must carry a "data" axis
    found = []
    jax.tree_util.tree_map(
        lambda s: found.append(any(ax == ("data",) or ax == "data"
                                   for ax in s)), specs.master)
    assert any(found)
