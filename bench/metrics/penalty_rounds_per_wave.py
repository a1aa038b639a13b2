"""Penalty-loop rounds per loop: the program's ``penalty.rounds`` over
``penalty.loops`` counters, warm-up included."""
from bench.spans import counter_ratio


def read(ctx):
    return counter_ratio("penalty.rounds", "penalty.loops")
