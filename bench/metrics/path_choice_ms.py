"""Device time per wave of the path-choice loop
(``engine.congestion._device_paths``): every tenant's candidate trees
solved, its tree chosen and its claims ranked, round after round."""
from bench import trace


def read(ctx):
    s = trace.op_seconds(ctx.trace, lambda n: "_device_paths" in n,
                         line=trace.MODULES)
    return s / trace.calls(ctx.trace) * 1e3 if s > 0 else None
