"""Program build per wave: the ``schedule.build_programs`` spans (one
``build_program`` per tenant) less the device-busy time inside them."""
from bench.spans import host_ms_per_call


def read(ctx):
    return host_ms_per_call(ctx, "schedule.build_programs")
