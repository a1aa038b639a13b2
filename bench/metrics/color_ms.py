"""Device time per call of the on-device color program
(``engine.batched._color_packed``)."""
from bench import trace


def read(ctx):
    s = trace.op_seconds(ctx.trace, lambda n: "_color_packed" in n,
                         line=trace.MODULES)
    return s / trace.calls(ctx.trace) * 1e3 if s > 0 else None
