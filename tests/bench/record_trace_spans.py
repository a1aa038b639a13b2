#!/usr/bin/env python3
"""Records the trace that ``test_bench_spans.py`` reads as its fixture.

    python tests/bench/record_trace_spans.py <out.json>

Two small ``solve_batch`` calls (BT(256), 8 tenants, k=8) and two
admission waves of the k=16 fat-tree fleet of ``bench/configs`` (32
tenants, two per pod), each inside ``bench_call``, with the wave's jobs
released between waves, all inside one ``bench_window``; every shape is
warmed first. The trace is reduced by ``bench.trace.load`` and written as
JSON. Run on a TPU, it also prints, for one device operation under each
of the named scopes ``levelfold``, ``color`` and ``penalty_round``, which
field of its event carries the scope, and the event that names the Pallas
kernel. It reads them from the profiler's Perfetto export, which keeps
the per-operation fields that ``jax.profiler.ProfileData`` does not show.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

SCOPES = ("levelfold", "color", "penalty_round")


def _calls():
    import jax
    from bench import trace as tr
    from bench.drivers.fleet_admission import fleet_from
    from repro.core import bt, sample_load
    from repro.engine import solve_batch
    from repro.runtime import Orchestrator, OrchestratorConfig

    config = json.loads((ROOT / "bench/configs/fattree16_cap2.json")
                        .read_text())
    orch = Orchestrator(fleet_from(config), OrchestratorConfig(
        k=int(config["k"]), capacity=int(config["capacity"])))
    t = bt(256, "constant")
    loads = [sample_load(t, "power-law", seed=s) for s in range(8)]

    def solve():
        return solve_batch([t] * 8, loads, 8)

    def wave():
        return orch.begin_workloads(fleet=[2] * 16, congestion_aware=True,
                                    device_admission=True)

    def release():
        orch.release_workloads(list(orch.jobs))

    solve()
    wave()
    release()

    def run():
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for _ in range(2):
                with jax.profiler.TraceAnnotation(tr.CALL):
                    solve()
                with jax.profiler.TraceAnnotation(tr.CALL):
                    wave()
                release()
    return run


def _scope_fields(d: str) -> dict:
    """scope -> (op name, [(field, value)]) of the first device operation
    whose fields name it; "kernel" -> the name of the first operation
    named after the Pallas kernel."""
    import gzip
    path, = glob.glob(os.path.join(d, "**", "perfetto_trace.json.gz"),
                      recursive=True)
    with gzip.open(path) as f:
        events = json.load(f)
    events = events["traceEvents"] if isinstance(events, dict) else events
    found: dict = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        name, args = str(e.get("name", "")), e.get("args") or {}
        if "kernel" not in found and name.startswith("levelfold"):
            found["kernel"] = name
        for s in SCOPES:
            hit = [(k, str(v)[:160]) for k, v in args.items()
                   if f"/{s}/" in str(v)]
            if s not in found and hit:
                found[s] = (name, hit)
    return found


def main(argv) -> int:
    import jax
    from bench import trace as tr
    out = Path(argv[1])
    run = _calls()
    with tempfile.TemporaryDirectory(prefix="spans_trace_") as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, create_perfetto_trace=True,
                                 profiler_options=opts)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        trace = tr.load(path)
        fields = _scope_fields(d)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(trace, separators=(",", ":")))
    print(f"[trace] {out} bytes={out.stat().st_size} "
          f"platform={jax.devices()[0].platform}")
    for s in (*SCOPES, "kernel"):
        print(f"[scope] {s}: {fields.get(s, 'no device op names it')}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
