"""Process start to the first timed call: imports, inputs, warm-up and
compilation (or reading compiled programs from the cache)."""


def read(ctx):
    return ctx.setup_s
