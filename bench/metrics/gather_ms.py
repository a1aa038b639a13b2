"""Device time per call of the engine's gather program
(``engine.batched._gather_packed``: the level folds with their child
gathers and transposes)."""
from bench import trace


def read(ctx):
    s = trace.op_seconds(ctx.trace, lambda n: "_gather_packed" in n,
                         line=trace.MODULES)
    return s / trace.calls(ctx.trace) * 1e3 if s > 0 else None
