"""Claims applied to and released from the physical switch ledger, per
wave: the ``orchestrator.fabric_ledger`` spans less the device-busy time
inside them."""
from bench.spans import host_ms_per_call


def read(ctx):
    return host_ms_per_call(ctx, "orchestrator.fabric_ledger")
