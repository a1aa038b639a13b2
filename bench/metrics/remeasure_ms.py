"""The host re-measure per wave: the ``engine.remeasure`` spans
(``measure_fleet_multi`` after the penalty loop and the residual ledgers)
less the device-busy time inside them."""
from bench.spans import host_ms_per_call


def read(ctx):
    return host_ms_per_call(ctx, "engine.remeasure")
