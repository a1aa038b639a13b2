"""Device-to-host per solve: the ``engine.readback`` spans (the color
dispatch, the wait for the device and the copy of the masks and costs to
the host) less the device-busy time inside them."""
from bench.spans import host_ms_per_call


def read(ctx):
    return host_ms_per_call(ctx, "engine.readback")
