"""The sharded entry point `halo.sharded_propagate` and the sharded layer's
counters on a 2x2 mesh of forced host devices, in a subprocess
(`_sharded_case.py`; XLA fixes the device count at its first use).  The
Pallas inner kernel runs in interpret mode."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV4 = {**os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.join(REPO, "src")}


def _case(mode):
    r = subprocess.run([sys.executable, os.path.join(REPO, "tests",
                                                     "_sharded_case.py"),
                        mode], cwd=REPO, env=ENV4, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_entry_equals_reference():
    """The entry, at the shape class the autotuner picks at 1024^3 (inner
    and outer T 4, overlap, per-field depths (6, 8), an inner tile
    narrower in x than in y), with a remainder tile and the source's
    cell on a four-shard corner, equals the plain acoustic reference —
    wavefields and traces — within `test_distributed.py`'s tolerance;
    it donates the initial state and leaves one block a shard."""
    out = _case("parity")
    assert out["mesh"] == [2, 2]
    assert (out["T"], out["inner_T"], out["overlap"]) == (4, 4, True)
    assert out["depths"] == [6, 8]
    assert out["tile"][0] < out["tile"][1]
    assert out["remainder"] != 0
    for name, (err, scale) in out["errs"].items():
        assert scale > 0.0, name
        assert err <= 5e-4 * scale + 1e-6, (name, err, scale)
    assert out["donated"]
    assert out["shards"] == [[16, 16, 16]]


def test_sharded_padded_caps_equal_reference():
    """The entry with every pass table's caps raised 4-fold over its
    fullest (shard, tile) gives the bits of the entry at the caps its
    tables need, and equals the plain reference within the tolerance
    above: the kernel's slot loops run each tile's live entries alone."""
    out = _case("padded")
    assert out["caps"] and all(c >= 4 for c in out["caps"])
    assert out["bitwise"] == [True, True, True]
    for name, (err, scale) in out["errs"].items():
        assert scale > 0.0, name
        assert err <= 5e-4 * scale + 1e-6, (name, err, scale)


@pytest.mark.parametrize("plan", ["flat", "nested"])
def test_sharded_counters_equal_brute_force(plan):
    """`sharded_counts` against counts taken another way, over one main
    tile and one remainder tile, for the flat 1024^3 class and for nested
    passes after the split first step: the ppermutes and the bytes they
    send, from the traced program itself; the update points from the
    kernel's slab loops and the split step's index boxes; the slot-loop
    iterations of the `halo.tables` span, live and at the caps, from the
    tables' live slots, slab by slab."""
    got = _case("counts")[plan]
    c = got["counters"]
    assert c["exchanges"] == got["ppermutes"] > 0
    assert c["exchange_bytes"] == got["bytes"] > 0
    assert sum(c["update_points"]) == got["update_points"]
    assert sum(c["useful_points"]) == got["useful_points"]
    live, at_cap = got["slot_iters"]
    assert got["span_slot_iters"] == [live, at_cap]
    assert 0 < live < at_cap
