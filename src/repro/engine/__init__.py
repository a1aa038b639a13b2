"""Batched multi-tenant SOAR placement engine.

``solve_batch(trees, loads, k, avail)`` solves B phi-BIC instances in one
device-resident level-synchronous JAX sweep — fused level-fold gather plus
on-device traceback; only masks and costs leave the accelerator (see
``batched.py``). ``solve_congestion`` iterates that solve under penalty-
reweighted link rates to minimize *max-link congestion* across tenants
sharing one tree — by default the whole round loop runs on device as one
jitted ``lax.while_loop`` (see ``congestion.py``). Engine behavior is
configured through the frozen :class:`EngineOptions` dataclass (see
``options.py``); the serial per-instance solvers stay in ``repro.core``.

``solve_fleet`` generalizes the congestion loop to N aggregation trees
hanging off a shared core: per-round profiling and penalty reweighting run
over the union of tree-local and shared-core links inside the same jitted
while-loop, and ``solve_congestion`` is its degenerate single-tree call.
"""
from .batched import (BatchResult, cache_stats, color_batch, gather_batch,
                      solve_batch, solve_forest)
from .congestion import CongestionResult, solve_congestion, solve_fleet
from .options import EngineOptions, pallas_fold

__all__ = ["BatchResult", "CongestionResult", "EngineOptions", "cache_stats",
           "color_batch", "gather_batch", "pallas_fold", "solve_batch",
           "solve_congestion", "solve_fleet", "solve_forest"]
