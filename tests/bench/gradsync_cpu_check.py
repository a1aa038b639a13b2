"""The gradient-sync cell's driver and comparison on 4 host devices.

    PYTHONPATH=src python tests/bench/gradsync_cpu_check.py

Runs in a process of its own, since the forced device count has to be set
before JAX starts. Builds a root holding a copy of ``bench/`` and a
``BENCHMARK.json`` with one small cell: the configuration's fabric, the
``grad_sync`` mix, and a few small leaves instead of the 27 of the
configuration. Then, through ``bench.run.run_cell`` (everything a run does
but the check for a chip), runs the cell as it is, reads the control's
numbers with ``bench.control.readings``, and runs it again with the timed
path broken underneath in each way ``gradsync_faults.FAULTS`` lists. Prints one JSON
object: ``{"runs": {case: result}, "control": readings}``.
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

CELL, CONFIG = "dp4_dsv2lite_gradsync", "dsv2lite_ep8_dp4"
# a norm scale, a stacked matrix, an odd-sized leaf and one that spans
# several blocks of the host reference
LEAVES = [["final_norm/scale", [64], "float32"],
          ["layers/attn/w_o", [2, 16, 24], "float32"],
          ["layers/moe/router/w", [3, 5, 7], "float32"],
          ["embed_tokens", [3, 1 << 21], "float32"]]


def make_root(tmp: Path) -> Path:
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    peaks = json.loads((tmp / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (tmp / "bench" / "peaks.json").write_text(json.dumps(peaks))
    path = tmp / "bench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["leaves"] = LEAVES
    path.write_text(json.dumps(cfg))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [w for w in spec["workloads"] if w["name"] == CELL]
    spec["per_layer"] = []          # they read a TPU's trace
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def main() -> int:
    import jax
    from bench.control import readings
    from bench.registry import Registry
    from bench.run import run_cell
    from gradsync_faults import FAULTS
    assert jax.device_count() == 4, jax.devices()
    devs = jax.devices()
    out: dict = {"runs": {}}
    with tempfile.TemporaryDirectory(prefix="gradsync_cpu_") as d:
        root = make_root(Path(d))
        reg = Registry.load(root, root / "bench")
        cases = [("sound", None)] + list(FAULTS.items())
        for case, plant in cases:
            undo = plant() if plant else None
            try:
                out["runs"][case] = run_cell(
                    reg, CELL, 2**33 + 17, 0.5, False, devs,
                    time.perf_counter())
            finally:
                if undo:
                    undo()
        out["control"] = readings(reg, CELL, [5, 2**40 + 1], [3, 4, 6],
                                  0.3, devs)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
