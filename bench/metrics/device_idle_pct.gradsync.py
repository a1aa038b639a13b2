"""Share of the traced window in which no operation ran, averaged over
the chips of the gradient-sync cell."""
from bench import trace


def read(ctx):
    return trace.idle_pct(ctx.trace)
