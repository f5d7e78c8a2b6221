"""The reduction from trace events to device metrics, on synthetic events
and on a small trace recorded on the chip."""
import json
import os

import pytest

from harness import files, trace, yardstick

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def host(name, a, b):
    return {"kind": "host", "plane": "/host:CPU", "line": "python",
            "name": name, "start_ns": float(a), "dur_ns": float(b - a)}


def dev(name, a, b, plane="/device:TPU:0"):
    return {"kind": "device", "plane": plane, "line": "XLA Ops",
            "name": name, "start_ns": float(a), "dur_ns": float(b - a)}


def test_busy_is_the_union_clipped_to_the_window():
    ev = [host("bench.window", 100, 1100),
          dev("a", 50, 300),            # starts before the window
          dev("b", 200, 400),           # overlaps a
          dev("c", 600, 700),
          dev("d", 1000, 1300)]         # ends after it
    s = trace.reduce(ev)
    assert s.window_s == pytest.approx(1000e-9)
    # [100, 400] + [600, 700] + [1000, 1100]
    assert s.busy_s == pytest.approx(500e-9)
    assert s.op_seconds["a"] == pytest.approx(200e-9)
    assert s.op_seconds["d"] == pytest.approx(100e-9)


def test_busy_is_averaged_over_devices():
    ev = [host("bench.window", 0, 1000), dev("k", 0, 1000),
          dev("k", 0, 500, plane="/device:TPU:1")]
    assert trace.reduce(ev).busy_s == pytest.approx(750e-9)
    assert trace.reduce(ev).devices == 2


def test_gaps_go_to_the_innermost_span_open_at_their_midpoint():
    ev = [host("bench.window", 0, 1000),
          host("bench.unit", 0, 1000),
          host("ops.tables", 100, 300),
          host("PjitFunction(scan)", 150, 250),   # not a span name
          dev("k", 300, 600), dev("k", 700, 1000)]
    s = trace.reduce(ev)
    idle = s.idle_by_host_span()
    assert idle["ops.tables"] == pytest.approx(300e-9)   # gap [0, 300]
    assert idle["bench.unit"] == pytest.approx(100e-9)   # gap [600, 700]
    assert sum(d for d, _ in s.gaps) == pytest.approx(
        s.window_s - s.busy_s)


def test_no_window_annotation_gives_nothing():
    assert trace.reduce([dev("k", 0, 10)]) is None


KERNEL = ('%closed_call.13 = (f32[512,512,512]{2,1,0:T(8,128)}, '
          'f32[256,8,56]{2,1,0}) custom-call(f32[544,544,512]{2,1,0} '
          '%pad.16), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={}}')
WHILE = ('%while.3 = (s32[]{:T(128)}, f32[512,512,512]{2,1,0:T(8,128)}) '
         'while((s32[]{:T(128)}, f32[512,512,512]{2,1,0}) %tuple.1), '
         'condition=%cond, body=%body')
PAD = ('%pad.17 = f32[544,544,512]{2,1,0:T(8,128)} pad(f32[512,512,512]'
       '{2,1,0:T(8,128)} %gte.379, f32[]{:T(128)} %c), padding=16_16x16_16')


def test_kernel_events_are_picked_by_their_custom_call_target():
    ev = [host("bench.window", 0, 100), dev(PAD, 0, 10),
          dev(KERNEL, 10, 70), dev("%copy.1 = f32[8] copy(f32[8] %a)", 70,
                                   80)]
    s = trace.reduce(ev)
    sec, n = s.seconds_of(yardstick.is_tb_kernel)
    assert n == 1 and sec == pytest.approx(60e-9)


def test_op_names_keep_the_instruction_opcode_and_target():
    assert trace.op_name({"name": KERNEL}) == \
        "%closed_call.13 custom-call tpu_custom_call"
    assert trace.op_name({"name": PAD}) == "%pad.17 pad"
    assert trace.op_name({"name": WHILE}) == "%while.3 while"


def test_containers_count_once_in_busy_and_not_among_ops():
    # the scan's while op spans the kernel and the pads inside it
    ev = [host("bench.window", 0, 100), dev(WHILE, 0, 90),
          dev(PAD, 0, 10), dev(KERNEL, 10, 80)]
    s = trace.reduce(ev)
    assert s.busy_s == pytest.approx(90e-9)
    assert sorted(s.op_seconds) == ["%closed_call.13 custom-call "
                                    "tpu_custom_call", "%pad.17 pad"]
    assert sum(sec for _, sec in s.ops) == pytest.approx(80e-9)


def _recorded():
    with open(os.path.join(DATA, "small_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_consistently():
    """A 64x64x128 acoustic propagate (nt 47) traced on a TPU v5 lite by
    the benchmark's drivers: one kernel call per time tile."""
    ev = _recorded()
    s = trace.reduce(ev)
    assert s is not None and s.devices == 1
    assert 0.0 < s.busy_s <= s.window_s
    # leaves run one after another; the loop ops that hold them add a
    # little busy time of their own
    total = sum(sec for _, sec in s.ops)
    assert 0.9 * s.busy_s <= total <= s.busy_s * (1 + 1e-9)
    sec, n = s.seconds_of(yardstick.is_tb_kernel)
    with open(os.path.join(DATA, "small_trace.tiles")) as f:
        assert n == int(f.read())
    assert 0.0 < sec <= total
    assert sum(d for d, _ in s.gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    assert all(label != "(no host span)" for _, label in s.gaps)


class _Units:
    def __init__(self, point_steps):
        self.point_steps, self.shots = point_steps, 1


class _Ctx:
    """The fields the yardstick reads, for one 512^3 acoustic propagate of
    399 steps whose kernel took 6 s."""

    def __init__(self):
        from harness import driver
        bench = files.benchmark()
        self.cfg = files.config(bench, "acoustic-so4-512")
        self.driver = type("D", (), {"npoints": 512 ** 3, "nt": 399})()
        self.window = driver.Window([_Units(512 ** 3 * 399)])
        self.window.units[0].t0, self.window.units[0].t1 = 0.0, 8.0
        self.propagates = 1
        self.devices = [object()]
        self.peaks = {"f32_vpu_flops_per_s": 1.0e13,
                      "hbm_bytes_per_s": 819e9}
        ev = [host("bench.window", 0, 8e9), dev(KERNEL, 0, 6e9),
              dev(PAD, 6e9, 7e9)]
        self.summary = trace.reduce(ev)


def test_roofline_arithmetic_from_the_configs_counts():
    ctx = _Ctx()
    flops = 32.0 * 512 ** 3 * 399
    bytes_ = 6 * 4 * 512 ** 3
    assert yardstick.useful_flops(ctx) == flops
    assert yardstick.compulsory_bytes(ctx) == bytes_
    share, note = yardstick.kernel_roofline(ctx)
    # compute-bound: 1.71e12 flops / 1e13 flop/s = 0.171 s of 6 s
    assert share == pytest.approx(100.0 * flops / 1.0e13 / 6.0)
    assert "compute" in note
    assert yardstick.device_idle(ctx) == pytest.approx(100.0 * 1.0 / 8.0)
    assert yardstick.step_mfu(ctx) == pytest.approx(
        100.0 * flops / 8.0 / 1.0e13)


def test_roofline_is_silent_without_kernel_events():
    ctx = _Ctx()
    ctx.summary = trace.reduce([host("bench.window", 0, 10),
                                dev("fusion", 0, 5)])
    assert yardstick.kernel_roofline(ctx) is None


def test_useful_flops_follow_the_stated_count():
    """Each configuration's count is the rule its `useful_flops_origin`
    states, applied to the central weights of its space order."""
    from harness import numerics as nm

    bench = files.benchmark()
    for entry in bench["configs"]:
        cfg = files.config(bench, entry["name"])
        order = int(cfg["space_order"])
        if cfg["physics"] == "acoustic":
            taps = 3 * (len(nm.central_weights(order, 2)) - 1) + 1
            want = (2 * taps - 1) + 7
        else:
            d1 = 2 * sum(w != 0.0 for w in nm.central_weights(order, 1)) - 1
            want = 14 * d1 + 46
        assert cfg["useful_flops_per_point_step"] == want, entry["name"]
