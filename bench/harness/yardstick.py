"""Work counts from the configuration, and the shares built on them.

Useful flops are the configuration's `useful_flops_per_point_step` times
the grid points and steps the window computed.  Compulsory bytes are what
one propagate cannot avoid moving: every state and parameter field read
once and every state field written once.  A roofline share is the least
time the chip could take for that work, the larger of flops over the
float32 vector-unit peak and bytes over the HBM peak (`peaks.json`),
divided by the kernel's device time in the trace.
"""
from __future__ import annotations

ITEMSIZE = {"float32": 4}


def is_tb_kernel(e: dict) -> bool:
    """The temporally-blocked stencil kernel's events: the Mosaic custom
    calls its `pallas_call` lowers to (`tpu_custom_call`; the program gives
    the call no name of its own, and it is the only Pallas kernel on the
    cells' paths)."""
    return 'custom_call_target="tpu_custom_call"' in e["name"]


def useful_flops(ctx) -> float:
    per = float(ctx.cfg["useful_flops_per_point_step"])
    return per * sum(u.point_steps for u in ctx.window.units)


def compulsory_bytes(ctx) -> float:
    cfg = ctx.cfg
    fields = 2 * int(cfg["state_fields"]) + int(cfg["param_fields"])
    return (float(ctx.propagates) * ctx.driver.npoints * fields
            * ITEMSIZE[cfg["dtype"]])


def kernel_roofline(ctx, pick=is_tb_kernel):
    if ctx.summary is None:
        return None
    seconds, n = ctx.summary.seconds_of(pick)
    if n == 0 or not seconds > 0.0:
        return None
    chips = len(ctx.devices)
    t_flops = useful_flops(ctx) / (ctx.peaks["f32_vpu_flops_per_s"] * chips)
    t_bytes = compulsory_bytes(ctx) / (ctx.peaks["hbm_bytes_per_s"] * chips)
    bound = "compute" if t_flops >= t_bytes else "HBM bandwidth"
    share = 100.0 * max(t_flops, t_bytes) / seconds
    return share, (f"bound by {bound}: {max(t_flops, t_bytes)!r} s of "
                   f"{seconds!r} s kernel time in {n} events "
                   f"(flops {t_flops!r} s, bytes {t_bytes!r} s)")


def device_idle(ctx):
    s = ctx.summary
    if s is None or not s.window_s > 0.0 or not s.ops:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)


def step_mfu(ctx):
    """Useful flops of the whole window over its host-clock length, as a
    share of the chips' float32 peak."""
    peak = ctx.peaks["f32_vpu_flops_per_s"] * len(ctx.devices)
    return 100.0 * useful_flops(ctx) / ctx.window.elapsed_s / peak


def span_seconds(ctx, name: str):
    if ctx.spans is None:
        return None
    durs = [r.dur for r in ctx.spans if r.name == name]
    return sum(durs) if durs else None
