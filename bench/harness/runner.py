"""One run of one cell: set-up, the measured window, the check, and the
result line.

Everything the run needs is found by name (`files`): the cell's
configuration and traffic mix, the configuration's physics (its reference
and earth model), the mix's driver, and one reader per metric.
A reader is `read(ctx) -> value | (value, note) | None`; None leaves the
metric out of the line.
"""
from __future__ import annotations

import math
import shutil
import sys
import tempfile
import time

from harness import compare, files, program, trace

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
FAR_OFF = 1e300


class Context:
    """What the metric readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @property
    def propagates(self) -> int:
        """Whole propagates (shots) computed in the window."""
        return sum(u.point_steps for u in self.window.units) // (
            self.driver.npoints * self.driver.nt)


def applies(metric: dict, cell: dict, bench: dict) -> bool:
    """A metric applies to the cells its `workloads` lists; without the
    key, an end-to-end metric applies to every cell and a per-layer one to
    every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    if "moves" in metric:
        e2e = next(m for m in bench["end_to_end"]
                   if m["name"] == metric["moves"])
        return applies(e2e, cell, bench)
    return True


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def run(bench, cell, seed, seconds, traced, devices, t_start, cfg=None,
        mix=None, peaks=None):
    """Returns the result dict; its `checks` key comes last.  `cfg`, `mix`
    and `peaks` replace what the cell's names would find (the tests run
    small copies on the CPU through them)."""
    from jax import monitoring

    cfg = cfg or files.config(bench, cell["config"])
    mix = mix or files.mix(cell["traffic"])
    physics = files.physics(cfg["physics"])
    program.on_path()
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(kw.get("fun_name"))
        if name == BACKEND_COMPILE else None)

    driver = files.driver(mix["driver"])(cfg, mix, physics, seed)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    say(f"cell {cell['name']}: {driver.describe()}")
    say(f"setup_s {setup_s!r} ({len(compiles)} programs compiled or loaded)")

    n0 = len(compiles)
    summary = span_records = None
    if traced:
        from repro.telemetry import spans

        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            collector = spans.enable(jax_profiler=True)
            try:
                with trace.capture(tmp):
                    window = driver.window(seconds)
            finally:
                spans.disable()
            span_records = collector.records()
            events = trace.load(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        summary = trace.reduce(events)
    else:
        window = driver.window(seconds)
    in_window = compiles[n0:]
    say(f"window {window.elapsed_s!r} s, {len(window.units)} units of "
        f"{[u.t1 - u.t0 for u in window.units]!r} s; programs compiled or "
        f"loaded inside it: {len(in_window)} "
        f"{sorted(set(map(str, in_window)))}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    checks, failed = driver.check(window)
    correct = failed == 0 and all(compare.passes(v, lim)
                                  for _, v, lim in checks)

    ctx = Context(cell=cell, cfg=cfg, mix=mix, driver=driver, window=window,
                  setup_s=setup_s, seconds=seconds, summary=summary,
                  spans=span_records, devices=devices,
                  peaks=peaks or files.peaks(devices[0].device_kind))
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if not applies(m, cell, bench):
            continue
        got = files.metric(m["name"]).read(ctx)
        if isinstance(got, tuple):
            got, note = got
            say(f"{m['name']}: {note}")
        if got is not None:
            metrics[m["name"]] = {"value": got, "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct,
              "attempted": sum(u.shots for u in window.units),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])
        gaps = sorted(summary.idle_by_host_span().items(),
                      key=lambda kv: -kv[1])
        result["breakdown"] = {"device_ops": [list(kv) for kv in ops[:10]],
                               "idle_gaps": [list(kv) for kv in gaps[:10]]}
    # JSON has no infinity: an output with no energy, a NaN or a wrong
    # shape reads as FAR_OFF
    result["checks"] = {name: {"value": v if math.isfinite(v) else FAR_OFF,
                               "limit": lim} for name, v, lim in checks}
    for name, v, lim in checks:
        say(f"check {name} {v!r} limit {lim!r}")
    return result
