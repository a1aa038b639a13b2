"""95th percentile of the decision calls' latency (call to answer on the
host), over every call of the window."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_s, 95)) * 1e3
