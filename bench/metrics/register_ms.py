"""Job registration per wave: the ``orchestrator.register`` spans (ledger
updates and ``_register_job`` per tenant) less the device-busy time inside
them."""
from bench.spans import host_ms_per_call


def read(ctx):
    return host_ms_per_call(ctx, "orchestrator.register")
