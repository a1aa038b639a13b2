#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <name> --seconds <s> \\
        --seeds 1,2,... --control-seeds 3,4,5

In one process, for every seed of ``--seeds``, runs the cell's traffic for a
short window and compares what the program produced with the plain
reference, as a benchmark run does: the largest reading over those seeds is
each number's lower reading. For every seed of ``--control-seeds`` it does
the same with the control in the program's place (each driver's
``check(control=True)``: the reference in the precision below the
configuration's, or the program's own path that breaks a guarantee the
configuration states): the smallest reading is the upper one. Prints one
JSON object with every reading; needs the cell's chips, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.registry import Registry  # noqa: E402
from bench.run import NO_CHIP, drive  # noqa: E402


def readings(reg: Registry, name: str, seeds, control_seeds,
             seconds: float, devices) -> dict:
    cell = reg.cell(name)
    config, traffic = reg.config(cell.config), reg.traffic(cell.traffic)
    Driver = reg.driver(traffic["driver"])
    out = {"program": {}, "control": {}}
    for seed, control in ([(s, False) for s in seeds]
                          + [(s, True) for s in control_seeds]):
        t0 = time.perf_counter()
        drv = Driver(config, traffic, seed, seconds, devices)
        drv.warm()
        lat, _, _, _, _ = drive(drv, seconds, False)
        drv.release()
        v = drv.check(control=control)
        row = {n: x for n, x, _ in v["checks"]}
        row.update(calls=len(lat), failed=v["failed"],
                   seconds=time.perf_counter() - t0)
        out["control" if control else "program"][str(seed)] = row
        print(f"[{'control' if control else 'program'}] seed={seed} "
              f"{json.dumps(row)}", flush=True)
    names = {n for r in out["program"].values() for n in r} - {
        "calls", "failed", "seconds"}
    out["lower"] = {n: max(r[n] for r in out["program"].values())
                    for n in sorted(names)}
    out["upper"] = {n: min(r[n] for r in out["control"].values()
                           if n in r)
                    for n in sorted(names)
                    if any(n in r for r in out["control"].values())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    reg = Registry.load(ROOT, BENCH)
    cell = reg.cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"control: cell {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return NO_CHIP
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(ROOT)
    out = readings(reg, cell.name,
                   [int(s) for s in args.seeds.split(",")],
                   [int(s) for s in args.control_seeds.split(",")],
                   args.seconds, devs[: cell.chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
