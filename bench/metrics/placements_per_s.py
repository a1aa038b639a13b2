"""Tenant placements answered over the whole window, per second."""


def read(ctx):
    return ctx.units / ctx.window_s
