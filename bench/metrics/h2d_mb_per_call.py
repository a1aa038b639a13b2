"""Megabytes (1e6 bytes) copied to the device per solve: the program's
``engine.upload_bytes`` over ``engine.solves`` counters, warm-up included."""
from bench.spans import counter_ratio


def read(ctx):
    r = counter_ratio("engine.upload_bytes", "engine.solves")
    return None if r is None else r / 1e6
