"""The temporally-blocked kernel's share of its roofline on each chip of a
sharded cell: the least time one chip could take for its share of the
useful flops and compulsory bytes of the propagates run, over the kernel's
device time per traced chip.  (`yardstick.kernel_roofline` sets the
chips' summed kernel time against the same per-chip ideal, which reads a
share divided by the chips on a multi-chip cell.)"""
from harness import yardstick


def read(ctx):
    s = ctx.summary
    if s is None or not s.devices:
        return None
    seconds, n = s.seconds_of(yardstick.is_tb_kernel)
    if n == 0 or not seconds > 0.0:
        return None
    per_chip = seconds / s.devices
    chips = len(ctx.devices)
    t_flops = yardstick.useful_flops(ctx) / (
        ctx.peaks["f32_vpu_flops_per_s"] * chips)
    t_bytes = yardstick.compulsory_bytes(ctx) / (
        ctx.peaks["hbm_bytes_per_s"] * chips)
    bound = "compute" if t_flops >= t_bytes else "HBM bandwidth"
    share = 100.0 * max(t_flops, t_bytes) / per_chip
    return share, (f"bound by {bound}: {max(t_flops, t_bytes)!r} s of "
                   f"{per_chip!r} s kernel time a chip ({n} events on "
                   f"{s.devices} chips; flops {t_flops!r} s, bytes "
                   f"{t_bytes!r} s)")
