"""What the readers of the gradient-sync cell's per-layer metrics share:
a time per all-reduce of the traced window, and which device operations
are collectives.

A collective is an ``XLA Ops`` event whose HLO name is an all-reduce, a
collective-permute, an all-gather, a reduce-scatter or an all-to-all, or
the start or end of one (``%collective-permute-start.3``), or that
carries the name of the JAX collective it was lowered from (on a TPU
v5e the broadcast's all-reduces read ``%psum_invariant.214``). Every
other operation of the sync (the receive-accumulate scatter-add, the
folds, the buffer set-up) is the sync's own arithmetic.
"""
from __future__ import annotations

import re

import numpy as np

from bench import trace

_COLLECTIVE = re.compile(r"all-reduce|collective-permute|all-gather|"
                         r"reduce-scatter|all-to-all|psum|pmax|pmin|"
                         r"ppermute|all_gather|all_to_all|reduce_scatter")


def is_collective(label: str) -> bool:
    return _COLLECTIVE.search(label.split(" ")[0]) is not None


def device_busy_s(ctx, device: int = 0) -> float | None:
    """Seconds of the traced window in which the ``device``-th chip ran
    any operation; None where it ran none."""
    t = ctx.trace
    devs = trace.devices(t)
    if len(devs) <= device:
        return None
    lo, hi = trace.window(t)
    s = float(np.diff(trace.busy(devs[device], lo, hi), axis=1).sum()) / 1e9
    return s if s > 0 else None


def per_sync_ms(ctx, seconds: float | None) -> float | None:
    """Milliseconds per all-reduce started inside the traced window."""
    n = trace.calls(ctx.trace)
    return seconds / n * 1e3 if seconds and n else None
