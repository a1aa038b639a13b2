"""Device time per call of the level-fold kernel
(``kernels/minplus/levelfold.py``), every level's launch together."""
from bench import trace

KERNEL = trace.KERNEL


def read(ctx):
    s = trace.op_seconds(ctx.trace, lambda n: KERNEL in n)
    return s / trace.calls(ctx.trace) * 1e3 if s > 0 else None
