"""Time per whole-gradient all-reduce: the window's seconds over the
all-reduces completed in it, every one of them (host clock, each call
timed until every leaf of its sum is ready)."""


def read(ctx):
    return ctx.window_s / ctx.units * 1e3 if ctx.units else None
