"""Small copies of the benchmark's cells that run on the CPU.

``make_root`` copies ``bench/`` into a temporary root and writes a
``BENCHMARK.json`` whose cells use tiny configurations and mixes, so that
``bench.run.run_cell`` drives every driver, reader and comparison of a run
here, without the check for a chip.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TREE = {"parent": [-1, 0, 1, 1], "rho": [16.0, 16.0, 2.0, 2.0],
        "load": [0, 0, 2, 2]}
FLEET = {"n_trees": 2, "core_rho": [64.0], "core_path": [[0], [0]]}
CONFIGS = {
    "bt256_k8": {"tree": {"kind": "bt", "n_total": 256}, "k": 8},
    "fleet2_cap1": {"fleet": FLEET, "tree": TREE, "k": 2, "capacity": 1},
}
TRAFFIC = {
    "batch4": {"driver": "batch_solve", "tenants_per_call": 4,
               "tenant_pool": 64, "max_calls_per_s": 2000,
               "load": {"dist": "power-law", "lo": 1, "hi": 63, "mean": 5},
               "check_samples": 6},
    "admit4": {"driver": "fleet_admission", "tenants_per_wave": 4,
               "least_per_tree": 1, "max_waves_per_s": 2000, "check_waves": 50,
               "control_waves": 6},
}
CELLS = [("batch", "bt256_k8", "batch4", 1), ("admit", "fleet2_cap1", "admit4", 1)]
E2E = {"batch": ["placements_per_s", "decision_p95_ms"],
       "admit": ["wave_placements_per_s", "wave_p95_ms"]}


def make_root(tmp: Path) -> Path:
    """A root holding a copy of ``bench/`` and the tiny cells."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    peaks = json.loads((tmp / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (tmp / "bench" / "peaks.json").write_text(json.dumps(peaks))
    for name, cfg in CONFIGS.items():
        (tmp / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, tr in TRAFFIC.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": n, "source": "test", "reduced": [],
                        "file": f"bench/configs/{n}.json", "why": "test"}
                       for n in CONFIGS]
    spec["workloads"] = [{"name": c, "config": g, "traffic": t, "chips": k,
                          "why": "test"} for c, g, t, k in CELLS]
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [c for c, e in E2E.items() if m["name"] in e]
    # the per-layer metrics read a TPU's trace, which the CPU has not
    spec["per_layer"] = []
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, cell: str, seed: int = 7, seconds: float = 0.5,
        traced: bool = False) -> dict:
    import jax
    from bench.registry import Registry
    from bench.run import run_cell
    import time
    reg = Registry.load(root, root / "bench")
    chips = reg.cell(cell).chips
    return run_cell(reg, cell, seed, seconds, traced,
                    jax.devices()[:chips], time.perf_counter())
