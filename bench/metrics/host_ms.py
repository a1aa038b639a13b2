"""Per decision call: the call's span on the host clock less the part in
which the device ran anything (packing, transfers, planner, orchestrator)."""
from bench import trace


def read(ctx):
    self_s = trace.host_self_s(ctx.trace)
    return float(self_s.mean()) * 1e3 if len(self_s) else None
