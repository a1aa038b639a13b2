"""Benchmark runner: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--fast]`` runs every benchmark,
writes CSVs under experiments/bench/, and prints a per-figure summary.
Each module also asserts the paper's qualitative claims (SOAR optimal /
best-in-class, scaling trends), so a green run doubles as validation.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache

from . import (admission, beyond_bottleneck, beyond_budget, congestion,
               degraded, engine_throughput, fig6_strategies, fig7_online,
               fig8_usecases, fig9_runtime, fig10_scaling, fig11_scalefree,
               fleet, paper_claims, recovery)

BENCHES = [
    ("paper_claims (Figs 1-3 + brute-force optimality)", paper_claims.run, {}),
    ("fig6_strategies", fig6_strategies.run, {}),
    ("fig7_online", fig7_online.run, {}),
    ("fig8_usecases", fig8_usecases.run, {}),
    ("fig9_runtime", fig9_runtime.run, {}),
    ("fig10_scaling", fig10_scaling.run, {}),
    ("fig11_scalefree", fig11_scalefree.run, {}),
    ("engine_throughput (batched vs serial placement)",
     engine_throughput.run, {}),
    ("congestion (driver vs utilization-only placement)",
     congestion.run, {}),
    ("fleet (coupled multi-tree vs independent per-tree solves)",
     fleet.run, {}),
    ("admission (device-side hard admission vs host claim accounting)",
     admission.run, {}),
    ("beyond_bottleneck (paper §8 conjecture)", beyond_bottleneck.run, {}),
    ("beyond_budget (paper §8 open problem 2)", beyond_budget.run, {}),
    ("recovery (preplan cache + degraded mode + chaos)", recovery.run, {}),
    ("degraded (partial capacity + chaos training)", degraded.run, {}),
]

FAST_OVERRIDES = {
    "fig6_strategies": dict(reps=3),
    "fig7_online": dict(reps=2),
    "fig8_usecases": dict(reps=2),
    "fig9_runtime": dict(reps=1, sizes=(256, 512, 1024), ks=(4, 16, 64),
                         engine_b=8),
    "fig10_scaling": dict(reps=1, sizes=(256, 512, 1024)),
    "fig11_scalefree": dict(reps=2, sizes=(256, 512, 1024)),
    "engine_throughput": dict(reps=2, batches=(8, 64)),
    "congestion (": dict(tenants=(8,), max_rounds=4, reps=1),
    "fleet (": dict(tenants=(8,), max_rounds=4, reps=1),
    "admission (": dict(tenants=(16,), reps=1),
    "recovery (": dict(n_pods=2, racks=2, events=30),
    "degraded (": dict(n_pods=2, racks=2, events=25, seq=16),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced reps/sizes for CI-style runs")
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark name")
    args = ap.parse_args(argv)
    enable_compile_cache(Path(__file__).resolve().parents[1])

    t_all = time.perf_counter()
    for name, fn, kw in BENCHES:
        if args.only and args.only not in name:
            continue
        if args.fast:
            for key, ov in FAST_OVERRIDES.items():
                if key in name:
                    kw = {**kw, **ov}
        print(f"\n=== {name} ===", flush=True)
        t0 = time.perf_counter()
        fn(**kw)
        print(f"[{name}: {time.perf_counter() - t0:.1f}s]", flush=True)
    print(f"\nAll benchmarks done in {time.perf_counter() - t_all:.1f}s; "
          f"CSVs in experiments/bench/")


if __name__ == "__main__":
    main()
