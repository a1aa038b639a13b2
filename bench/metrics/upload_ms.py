"""Host-to-device transfer per solve: the ``engine.upload`` spans
(sanitising the packed arrays and copying them to the device, and the
penalty loop's slot twins and constants) less the device-busy time inside
them."""
from bench.spans import host_ms_per_call


def read(ctx):
    return host_ms_per_call(ctx, "engine.upload")
