"""The level fold's share of its roofline: the least time the chip could
take for the fold's work (the larger of its bytes at peak HBM bandwidth and
its operations at peak FLOP/s, from ``bench/roofline.py``) over the
kernel's measured time per call."""
from bench import roofline, trace

KERNEL = trace.KERNEL


def read(ctx):
    s = trace.op_seconds(ctx.trace, lambda n: KERNEL in n)
    if s <= 0:
        return None
    drv = ctx.driver
    work = roofline.fold_work(drv.parent, drv.k, drv.B)
    least = max(work["bytes"] / ctx.peaks["hbm_bytes_per_s"],
                work["ops"] / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / (s / trace.calls(ctx.trace))
