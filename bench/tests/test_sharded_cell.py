"""The sharded cell at a small size on four forced host devices (a
subprocess: `_sharded_small.py`), a planted fault the one-chip cells
cannot have, and the cell's readers on a constructed four-device trace."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from harness import files, trace, yardstick

HERE = os.path.dirname(os.path.abspath(__file__))


def _run(mode):
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, os.path.join(HERE,
                                                     "_sharded_small.py"),
                        mode], env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    res = _run("sound")
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert {"gpts_per_s", "setup_s"} <= set(res["metrics"])
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name


def test_missing_halo_exchange_is_not_correct():
    """With the exchange from the low-x neighbour left out, the wave that
    crosses the x = 16 shard face is lost there: the run reads not
    correct (the one-chip cells have no exchange to leave out)."""
    res = _run("fault")
    assert res["correct"] is False
    assert res["checks"]["wavefield_rel_err"]["value"] > \
        res["checks"]["wavefield_rel_err"]["limit"]


# ---------------------------------------------------------------------------
# readers, on a constructed trace of four chips
# ---------------------------------------------------------------------------

KERNEL = ('%tb_time_tile.3 = (f32[8,8,8]) custom-call(f32[12,16,8] %a), '
          'custom_call_target="tpu_custom_call"')
PERMUTE = ("%collective-permute-start.1 = (f32[2,8,8], f32[2,8,8]) "
           "collective-permute-start(f32[2,8,8] %s)")
DONE = ("%collective-permute-done.1 = f32[2,8,8] "
        "collective-permute-done((f32[2,8,8], f32[2,8,8]) %p)")
FUSION = "%fusion.7 = f32[8,8,8] fusion(f32[8,8,8] %x), kind=kLoop"


def host(name, a, b):
    return {"kind": "host", "plane": "/host:CPU", "line": "python",
            "name": name, "start_ns": float(a), "dur_ns": float(b - a)}


def dev(name, a, b, chip):
    return {"kind": "device", "plane": f"/device:TPU:{chip}",
            "line": "XLA Ops", "name": name, "start_ns": float(a),
            "dur_ns": float(b - a)}


def four_chip_trace():
    """A 1000 ns window: the host dispatches (0-200), then each chip runs
    an exchange (start 10 ns, done 40 ns), a fusion and the kernel for
    500 ns; chip k starts 10*k ns late."""
    ev = [host("bench.window", 0, 1000), host("bench.unit", 0, 1000),
          host("halo.propagate", 0, 1000), host("halo.dispatch", 0, 200),
          host("halo.tables", 50, 100)]
    for k in range(4):
        t = 200 + 10 * k
        ev += [dev(PERMUTE, t, t + 10, k), dev(DONE, t + 10, t + 50, k),
               dev(FUSION, t + 50, t + 100, k),
               dev(KERNEL, t + 100, t + 600, k)]
    return ev


def context(summary, spans):
    cfg = {"useful_flops_per_point_step": 32, "state_fields": 2,
           "param_fields": 2, "dtype": "float32"}
    npoints, nt = 1000, 10
    return NS(summary=summary, spans=spans, cfg=cfg,
              devices=[object()] * 4, propagates=1,
              driver=NS(npoints=npoints, nt=nt),
              window=NS(units=[NS(point_steps=npoints * nt, shots=1)]),
              peaks={"f32_vpu_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9})


def rec(name, parent=None, **attrs):
    return NS(name=name, dur=0.0, parent=parent, attrs=attrs)


SPANS = [rec("halo.tables", parent="halo.dispatch",
             update_points=[300, 60], useful_points=[100, 20]),
         rec("halo.dispatch", parent="halo.propagate"),
         rec("halo.propagate")]


def test_readers_on_a_four_chip_trace():
    s = trace.reduce(four_chip_trace())
    assert s.devices == 4
    c = context(s, SPANS)
    # 32e4 flops over 4 chips at 1e9 flop/s: 8e-5 s; 8e4 bytes over 4
    # chips at 1e9 B/s: 2e-5 s; against 500 ns of kernel a chip
    share, note = files.metric("tb_kernel_roofline.sharded").read(c)
    assert share == pytest.approx(100.0 * 8e-5 / 500e-9)
    assert "compute" in note
    # the accepted reader sums the four chips' kernel time against the
    # same per-chip ideal: a quarter of the share
    quarter, _ = yardstick.kernel_roofline(c)
    assert share == pytest.approx(4 * quarter)
    # 50 ns of exchange a chip in a 1000 ns window
    assert files.metric("halo_exchange_pct.sharded").read(c)[0] == \
        pytest.approx(5.0)
    # each chip busy 600 ns
    assert files.metric("device_idle_pct.sharded").read(c) == \
        pytest.approx(40.0)
    # chip 0 idles 0-200 under halo.dispatch (50-100 under halo.tables,
    # the span inside it), and 800-1000 under halo.propagate
    assert files.metric("halo_dispatch_idle_s_per_call.sharded").read(c) \
        == pytest.approx(200e-9)
    assert files.metric("halo_rim_redundancy.sharded").read(c) == \
        pytest.approx(360 / 120)


@pytest.mark.parametrize("name", [
    "tb_kernel_roofline.sharded", "halo_exchange_pct.sharded",
    "halo_rim_redundancy.sharded", "halo_dispatch_idle_s_per_call.sharded",
    "device_idle_pct.sharded"])
def test_readers_read_nothing_from_a_program_without_the_layer(name):
    """A trace with no device operations and spans without the sharded
    layer's (what the parent's program would record) read as nothing."""
    ev = [host("bench.window", 0, 1000), host("ops.dispatch", 0, 200)]
    c = context(trace.reduce(ev), [rec("ops.dispatch")])
    assert files.metric(name).read(c) is None
    assert files.metric(name).read(context(None, None)) is None
