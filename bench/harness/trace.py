"""From a profiler trace to the numbers the device metrics read.

`capture(dir)` runs the JAX profiler with the Python tracer off (host
annotations and runtime events only), `load(dir)` flattens the written
`.xplane.pb` into plain event dicts, and `reduce(events, ...)` computes,
inside the benchmark's `bench.window` annotation:

  busy_s       union of the intervals in which an operation ran on a
               device, averaged over the devices traced
  window_s     length of the window annotation
  ops          every leaf device operation in the window (not a `while`
               or call that only contains others), with the seconds of it
               that fall inside; a kernel's reader picks its own
  op_seconds   device time per operation name (`op_name`)
  gaps         the device's idle intervals, each with the innermost host
               span (a dotted name, as the benchmark's and the program's
               annotations are) open at its midpoint

An event dict is {"kind": "device" | "host", "plane", "line", "name",
"start_ns", "dur_ns"}; a recorded small trace of that form is checked in
`tests/`.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from typing import Callable, Dict, List, Optional

WINDOW = "bench.window"
# device lines whose events are single operations (XLA's op line)
DEVICE_OP_LINES = ("XLA Ops",)
# span-like names: dotted identifiers, as the benchmark's and program's
# own annotations are named
_SPAN_NAME = re.compile(r"^[A-Za-z_][\w]*(\.[\w-]+)+$")


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> List[dict]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    events = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            device = plane.name.startswith("/device:")
            host = plane.name.startswith("/host:")
            for line in plane.lines:
                if device and line.name not in DEVICE_OP_LINES:
                    continue
                if not (device or host):
                    continue
                for e in line.events:
                    events.append({
                        "kind": "device" if device else "host",
                        "plane": plane.name, "line": line.name,
                        "name": e.name, "start_ns": float(e.start_ns),
                        "dur_ns": float(e.duration_ns)})
    return events


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def window_of(events: List[dict], name: str = WINDOW):
    ws = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
          if e["kind"] == "host" and e["name"] == name]
    if not ws:
        return None
    return min(a for a, _ in ws), max(b for _, b in ws)


class Summary:
    def __init__(self, window, busy_s, ops, gaps, devices):
        self.window = window
        self.window_s = (window[1] - window[0]) * 1e-9
        self.busy_s = busy_s
        self.ops = ops
        self.gaps = gaps
        self.devices = devices

    @property
    def op_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for e, sec in self.ops:
            out[op_name(e)] = out.get(op_name(e), 0.0) + sec
        return out

    def seconds_of(self, pick: Callable[[dict], bool]):
        """(device seconds, event count) of the operations `pick` selects."""
        chosen = [sec for e, sec in self.ops if pick(e)]
        return sum(chosen), len(chosen)

    def idle_by_host_span(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for dur, label in self.gaps:
            out[label] = out.get(label, 0.0) + dur
        return out


def _host_label(spans, t):
    """The innermost span open at time t, else "(no host span)"."""
    best = None
    for e in spans:
        if e["start_ns"] > t:
            break
        if e["start_ns"] + e["dur_ns"] >= t and (
                best is None or e["dur_ns"] <= best["dur_ns"]):
            best = e
    return best["name"] if best else "(no host span)"


def op_name(e: dict) -> str:
    """The name a device operation is reported under: XLA's op line names
    an event by its whole HLO instruction, `%name = type opcode(...),
    attrs`; this keeps `%name opcode` and a custom call's target."""
    text = e["name"]
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text
    depth, i = 0, 0
    for i, ch in enumerate(rest):         # skip the (possibly tuple) type
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].split("(", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return " ".join([name, opcode] + ([target.group(1)] if target else []))


def _leaves(events):
    """The events of one device line that contain no other event."""
    events = sorted(events, key=lambda e: (e["start_ns"],
                                           -e["start_ns"] - e["dur_ns"]))
    container = set()
    stack = []
    for i, e in enumerate(events):
        end = e["start_ns"] + e["dur_ns"]
        while stack and (events[stack[-1]]["start_ns"]
                         + events[stack[-1]]["dur_ns"]) <= e["start_ns"]:
            stack.pop()
        if stack and end <= (events[stack[-1]]["start_ns"]
                             + events[stack[-1]]["dur_ns"]):
            container.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(events) if i not in container]


def reduce(events: List[dict], window_name: str = WINDOW
           ) -> Optional[Summary]:
    win = window_of(events, window_name)
    if win is None:
        return None
    w0, w1 = win
    by_dev: Dict[str, list] = {}
    lines: Dict[tuple, list] = {}
    for e in events:
        if e["kind"] != "device":
            continue
        a = max(e["start_ns"], w0)
        b = min(e["start_ns"] + e["dur_ns"], w1)
        if b <= a:
            continue
        by_dev.setdefault(e["plane"], []).append((a, b))
        lines.setdefault((e["plane"], e["line"]), []).append(e)
    ops = []
    for evs in lines.values():
        for e in _leaves(evs):
            a = max(e["start_ns"], w0)
            b = min(e["start_ns"] + e["dur_ns"], w1)
            ops.append((e, (b - a) * 1e-9))
    if not by_dev:
        return Summary(win, 0.0, [], [], 0)
    busy = {d: _union(iv) for d, iv in by_dev.items()}
    busy_ns = sum(sum(b - a for a, b in u) for u in busy.values()) / len(busy)
    spans = sorted((e for e in events if e["kind"] == "host"
                    and _SPAN_NAME.match(e["name"])),
                   key=lambda e: e["start_ns"])
    first = busy[sorted(busy)[0]]
    gaps = []
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append(((b - a) * 1e-9, _host_label(spans, 0.5 * (a + b))))
    return Summary(win, busy_ns * 1e-9, ops, gaps, len(busy))
