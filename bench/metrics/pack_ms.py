"""Host packing per solve: the ``engine.pack`` spans (``build_forest``,
``build_fleet_forest``) less the device-busy time inside them."""
from bench.spans import host_ms_per_call


def read(ctx):
    return host_ms_per_call(ctx, "engine.pack")
