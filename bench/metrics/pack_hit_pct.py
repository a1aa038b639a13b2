"""Share of the Forests built that reused a cached load-independent layout,
in %: the program's ``engine.pack_hits`` over ``engine.forests_built``
counters, warm-up included."""
from bench.spans import counter_ratio


def read(ctx):
    r = counter_ratio("engine.pack_hits", "engine.forests_built")
    return None if r is None else r * 100
