#!/usr/bin/env python3
"""The gradient-sync cell's comparison with faults planted, on the chips,
at the cell's own size.

    python3 tests/bench/gradsync_chip_readings.py --seconds 4 \\
        --faults dropped_contribution --seeds 1,2,3

For each fault of ``gradsync_faults.FAULTS`` named and each seed: one run
of the cell through ``bench.run.run_cell`` (set-up, a short window, the
comparison) with the fault planted under the timed path. Prints one JSON
line per run with its checks. Needs the cell's 4 TPU chips; exits 2
without them.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

CELL = "dp4_dsv2lite_gradsync"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench.registry import Registry
    from bench.run import NO_CHIP, run_cell
    from gradsync_faults import FAULTS
    reg = Registry.load(ROOT, ROOT / "bench")
    chips = reg.cell(CELL).chips
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"{CELL} needs {chips} TPU chips", file=sys.stderr)
        return NO_CHIP
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(ROOT)
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            undo = FAULTS[fault]()
            try:
                out = run_cell(reg, CELL, seed, args.seconds, False,
                               devs[:chips], time.perf_counter())
            finally:
                undo()
            print(json.dumps({"fault": fault, "seed": seed,
                              "correct": out["correct"],
                              "failed": out["failed"],
                              "attempted": out["attempted"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
