"""Host time of the program's own spans, for the readers of per-layer
metrics that name a span (``repro.telemetry``).

A span metric is the union of that span's intervals inside the traced
window, less the time in which the first device ran anything inside them
(the rule ``host_ms`` applies to ``bench_call``), per ``bench_call``. A
trace with no such span (a program that emits none) reads None.
"""
from __future__ import annotations

import numpy as np

from bench import trace


def overlap_s(iv: np.ndarray, bz: np.ndarray) -> float:
    """Seconds in which the disjoint sorted intervals ``iv`` and ``bz``
    (each (m, 2) ns) overlap."""
    if not len(iv) or not len(bz):
        return 0.0
    before = np.r_[0.0, np.cumsum(bz[:, 1] - bz[:, 0])]

    def busy_until(x):
        j = np.searchsorted(bz[:, 0], x, side="right")
        tail = np.where(j > 0, np.maximum(bz[j - 1, 1] - x, 0.0), 0.0)
        return before[j] - tail
    return float((busy_until(iv[:, 1]) - busy_until(iv[:, 0])).sum()) / 1e9


def host_ms_per_call(ctx, name: str) -> float | None:
    """Milliseconds per call of span ``name`` in which no device ran."""
    t = ctx.trace
    lo, hi = trace.window(t)
    iv = trace.clip(trace.union(trace.host_spans(t, name)), lo, hi)
    n = trace.calls(t)
    if not len(iv) or not n:
        return None
    devs = trace.devices(t)
    bz = trace.busy(devs[0], lo, hi) if devs else np.zeros((0, 2))
    host_s = float(np.diff(iv, axis=1).sum()) / 1e9 - overlap_s(iv, bz)
    return host_s / n * 1e3


def counter_ratio(num: str, den: str) -> float | None:
    """``num`` over ``den`` from the program's counters as they stand,
    warm-up included; None where the program keeps no such counters."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    c = telemetry.counters()
    return c[num] / c[den] if c.get(den) and num in c else None
