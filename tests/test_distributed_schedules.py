"""The sharded layer's schedules against the reference: inner tiles
below the shard block, time-nested inner passes and the overlapped
exchange, each on a forced 8-device host mesh in a subprocess
(`launch/stencil_dist.py --check`), the Pallas TB kernel per shard.
`test_distributed.py` holds the flat schedule."""
import pytest

from test_distributed import _run


@pytest.mark.parametrize("physics", ["acoustic", "tti", "elastic"])
def test_two_level_inner_tile_equals_reference(physics):
    """Hierarchical plan: inner tile (4, 8) STRICTLY smaller than the
    (8, 16) shard block, spatially tiling the exchanged block inside the
    per-shard schedule — every physics, remainder tile included (nt=5,
    T=2)."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              physics, "--inner-tile", "4,8",
              "--n", "32", "--nt", "5", "--T", "2"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


@pytest.mark.parametrize("physics,inner_T,outer_T", [
    # acoustic r_step=2 on the (8, 16) block: outer_T=4 (halo 8) with
    # every proper divisor as the inner depth
    ("acoustic", 1, 4), ("acoustic", 2, 4),
    # TTI/elastic r_step=4: outer_T=2 (halo 8) nested as two depth-1
    # passes per exchange
    ("tti", 1, 2), ("elastic", 1, 2),
])
def test_time_nested_equals_reference(physics, inner_T, outer_T):
    """The tentpole: inner_T < outer_T runs outer_T/inner_T inner passes
    per deep exchange over pass-by-pass-shrinking windows — bit-exact
    against the single-level reference for every physics, nt % outer_T
    != 0 included (nt=6: remainder 2 for acoustic, whole tiles for
    TTI/elastic at outer_T=2 — nt=5 covers their remainder)."""
    nt = 6 if physics == "acoustic" else 5
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              physics, "--inner-tile", "4,8",
              "--n", "32", "--nt", str(nt), "--T", str(inner_T),
              "--outer-T", str(outer_T)])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


@pytest.mark.parametrize("inner_T", [1, 2])
def test_time_nested_overlap_equals_reference(inner_T):
    """Overlap composes with nesting: the split first step consumes pass
    0's first timestep, the remaining T-1 steps chunk at the inner depth
    — inner_T=2 makes that remainder odd (passes of depth 2 then 1), so
    the shallower-than-inner_T final pass is exercised WITH overlap."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              "acoustic", "--inner-tile", "4,8",
              "--overlap", "--n", "32", "--nt", "7", "--T", str(inner_T),
              "--outer-T", "4"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout


@pytest.mark.parametrize("physics", ["acoustic", "elastic", "tti"])
def test_overlapped_exchange_equals_reference(physics):
    """The overlapped deep exchange (split interior/rim first step, then
    the kernel at depth H - r_step) is bit-compatible with the
    serialized schedule — combined with an inner tile below the block."""
    r = _run(["-m", "repro.launch.stencil_dist", "--check", "--physics",
              physics, "--inner-tile", "4,8",
              "--overlap", "--n", "32", "--nt", "5", "--T", "2"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "CHECK PASS" in r.stdout
