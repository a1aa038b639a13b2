"""Tenants per wave whose chosen tree changed between the path-choice
loop's first and last round: the program's ``paths.moves`` over
``paths.waves`` counters, warm-up included."""
from bench.spans import counter_ratio


def read(ctx):
    return counter_ratio("paths.moves", "paths.waves")
