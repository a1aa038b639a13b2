"""Device time per wave of the penalty-loop program
(``engine.congestion._device_driver``)."""
from bench import trace


def read(ctx):
    s = trace.op_seconds(ctx.trace, lambda n: "_device_driver" in n,
                         line=trace.MODULES)
    return s / trace.calls(ctx.trace) * 1e3 if s > 0 else None
