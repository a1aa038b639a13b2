#!/usr/bin/env python3
"""Runs one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` and the files under ``bench/`` (see
``bench/registry.py``). A run:

1. checks that JAX sees a TPU with as many chips as the cell asks for, and
   exits 2 with no result otherwise;
2. turns on JAX's persistent compilation cache at ``<repo>/.jax_cache`` (or
   where ``JAX_COMPILATION_CACHE_DIR`` says);
3. builds the cell's inputs from ``--seed`` and warms up every shape the
   traffic uses: ``setup_s`` runs from process start to the first timed
   call;
4. drives the program in a closed loop for ``--seconds``, timing each call
   until its answer is on the host; with ``--trace 1`` the window is
   traced and the per-layer metrics are read from the trace;
5. reads the peak device memory, frees the program's state, and compares
   what the window produced with the plain reference (``correct``).

Earlier lines of standard output give the device, the latency median and
tail with their sample count, the compiles inside the window (there should
be none) and the compile-cache counters. The last line is one JSON object;
the numbers compared for ``correct`` come last in it, under ``checks``, and
are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import trace as tr  # noqa: E402
from bench.registry import Registry  # noqa: E402

NO_CHIP = 2


@dataclasses.dataclass
class Ctx:
    """What a metric reader sees of a finished run."""

    cell: str
    config: dict
    traffic: dict
    driver: object
    chips: int
    peaks: dict
    latencies_s: np.ndarray
    units: int
    window_s: float
    setup_s: float
    counters: dict
    trace: dict | None = None


class _CompileCounter:
    """Counts programs lowered in this process (each new jit shape)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


def peaks_for(kind: str, bench: Path = BENCH) -> dict:
    with open(bench / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def drive(drv, seconds: float, traced: bool) -> tuple:
    """The measured window: calls back to back until ``seconds`` have
    passed. Traced, the profiler records the first ``tr.TRACE_SECONDS`` of
    it. Returns (latencies, units answered, window start, window seconds,
    [trace dict] or [])."""
    import jax
    lat: list[float] = []
    units = 0
    got: list = []
    tracing = traced
    if traced:
        prof = tr.capture()
        got = prof.__enter__()
        span = jax.profiler.TraceAnnotation(tr.WINDOW)
        span.__enter__()
    w0 = time.perf_counter()
    i = 0
    while True:
        t0 = time.perf_counter()
        if tracing:
            with jax.profiler.TraceAnnotation(tr.CALL):
                units += drv.call(i)
        else:
            units += drv.call(i)
        t1 = time.perf_counter()
        lat.append(t1 - t0)
        drv.after(i)
        i += 1
        if tracing and t1 - w0 >= min(seconds, tr.TRACE_SECONDS):
            span.__exit__(None, None, None)
            prof.__exit__(None, None, None)
            tracing = False
        if t1 - w0 >= seconds:
            break
    return lat, units, w0, time.perf_counter() - w0, got


def run_cell(reg: Registry, name: str, seed: int, seconds: float,
             traced: bool, devices: list, t_start: float) -> dict:
    """One run of cell ``name`` on ``devices``; returns the result dict."""
    from repro.launch.compile_cache import compile_stats

    cell = reg.cell(name)
    config, traffic = reg.config(cell.config), reg.traffic(cell.traffic)
    kind = devices[0].device_kind
    peaks = peaks_for(kind, reg.bench)
    counter = _CompileCounter()
    t_drv = time.perf_counter()
    drv = reg.driver(traffic["driver"])(config, traffic, seed, seconds,
                                        devices)
    t_warm = time.perf_counter()
    drv.warm()
    # what set-up built stays alive all run: keep it out of the collector's
    # scans, so that a full collection in the window costs what the
    # window's own objects cost
    gc.collect()
    gc.freeze()
    lowered0 = counter.n
    lat, units, w0, window_s, got = drive(drv, seconds, traced)
    setup_s = w0 - t_start
    # where set-up went: to the chip's first use, building the driver's
    # inputs and program state, warming every shape
    print(f"[setup] to_driver_s={t_drv - t_start} driver_s={t_warm - t_drv} "
          f"warm_s={w0 - t_warm}", flush=True)
    gc.unfreeze()
    in_window = counter.n - lowered0
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    counters = drv.counters()
    drv.release()
    verdict = drv.check()
    lat_a = np.asarray(lat)
    q = np.percentile(lat_a, [50, 90, 95, 99, 100]) * 1e3
    print(f"[latency] calls={len(lat)} median_ms={q[0]} p90_ms={q[1]} "
          f"p95_ms={q[2]} p99_ms={q[3]} max_ms={q[4]} "
          f"over_1.5x_median={int((lat_a > 1.5 * np.median(lat_a)).sum())}",
          flush=True)
    print(f"[compiles] in_window={in_window} "
          f"{' '.join(f'{k}={v}' for k, v in compile_stats().items())}",
          flush=True)
    ctx = Ctx(cell=name, config=config, traffic=traffic, driver=drv,
              chips=len(devices), peaks=peaks, latencies_s=lat_a,
              units=units, window_s=window_s, setup_s=setup_s,
              counters=counters, trace=got[0] if traced else None)
    metrics = {}
    for m in reg.metrics(name, traced):
        v = reg.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    checks = verdict["checks"]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(v <= lim for _, v, lim in checks)
               and verdict["failed"] == 0,
           "attempted": len(lat), "failed": int(verdict["failed"]),
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = tr.busy_s(ctx.trace)
        device["window_s"] = tr.window_s(ctx.trace)
        out["breakdown"] = tr.breakdown(ctx.trace)
    out["checks"] = {n: {"value": float(v), "limit": float(lim)}
                     for n, v, lim in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    reg = Registry.load(ROOT, BENCH)
    cell = reg.cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s); "
              f"nothing was run", file=sys.stderr)
        return NO_CHIP
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache(ROOT)
    devs = devs[: cell.chips]
    print(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)} jax={jax.__version__} compile_cache={cache}",
          flush=True)
    out = run_cell(reg, cell.name, args.seed, args.seconds,
                   bool(args.trace), devs, T_START)
    for n, c in out["checks"].items():
        print(f"[check] {n}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
