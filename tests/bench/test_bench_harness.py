"""The benchmark's harness on the CPU: trace reduction, fold bytes, lookup
of cells by name, and refusal to run without a chip."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import bench_testlib as L
from bench import roofline, trace as tr

HERE = Path(__file__).resolve().parent


def _trace(ops, modules, calls, window, host=()):
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS, "events": [list(e) for e in ops]},
        {"name": tr.MODULES, "events": [list(e) for e in modules]}]}
    ev = [[tr.WINDOW, *window]] + [[tr.CALL, *c] for c in calls]
    ev += [list(e) for e in host]
    return {"planes": [dev, {"name": "/host:CPU",
                             "lines": [{"name": "python3", "events": ev}]}]}


def test_trace_reduction_by_hand():
    # window [0, 100); ops [10, 30) and [20, 40) overlap, [60, 70) apart,
    # [95, 105) half outside: busy = 30 + 10 + 5 = 45
    t = _trace(
        ops=[("%fusion.1", 10, 20), ("%k.2 tpu_custom_call", 20, 20),
             ("%fusion.3", 60, 10), ("%copy.4", 95, 10)],
        modules=[("jit_f(1)", 10, 30), ("jit_g(2)", 60, 10)],
        calls=[(5, 45), (55, 30)], window=(0, 100),
        host=[("prepare", 40, 15)])
    assert tr.busy_s(t) == pytest.approx(45e-9)
    assert tr.window_s(t) == pytest.approx(100e-9)
    assert tr.idle_pct(t) == pytest.approx(55.0)
    assert tr.op_seconds(t, lambda n: tr.KERNEL in n) == pytest.approx(20e-9)
    assert tr.op_seconds(t, lambda n: "jit_g" in n, line=tr.MODULES) == \
        pytest.approx(10e-9)
    # call 1 spans [5, 50): busy 30 inside; call 2 [55, 85): busy 10
    assert tr.host_self_s(t) == pytest.approx([15e-9, 20e-9])
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["jit_f:fusion", pytest.approx(20e-9)]
    # idle [0, 10), [40, 60), [70, 95); each named by the shortest host
    # span over its middle
    assert b["idle_gaps"] == [["bench_call", pytest.approx(25e-9)],
                              ["prepare", pytest.approx(20e-9)],
                              ["bench_call", pytest.approx(10e-9)]]
    assert tr.op_kind("%all-reduce.3") == "all-reduce"


def _naive_busy(events, lo, hi):
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events)
    tot, cur_s, cur_e = 0.0, None, None
    for s, e in iv:
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return tot + (cur_e - cur_s if cur_e is not None else 0.0)


def test_trace_reduction_on_recorded_trace():
    """One admission wave of a 16-tree fleet (the admission cell as first
    laid out, with rates 2/16/64), traced on a v5e."""
    t = json.loads((HERE / "trace_admit_wave.json").read_text())
    lo, hi = tr.window(t)
    dev = tr.devices(t)[0]
    ops = next(ln for ln in dev["lines"] if ln["name"] == tr.OPS)["events"]
    busy = _naive_busy(ops, lo, hi)
    assert tr.busy_s(t) == pytest.approx(busy / 1e9)
    assert tr.idle_pct(t) == pytest.approx(100 * (1 - busy / (hi - lo)))
    mods = next(ln for ln in dev["lines"]
                if ln["name"] == tr.MODULES)["events"]
    loop = [e for e in mods if "_device_driver" in e[0]]
    assert len(loop) == 1
    assert tr.op_seconds(t, lambda n: "_device_driver" in n,
                         line=tr.MODULES) == pytest.approx(loop[0][2] / 1e9)
    kern = [e for e in ops if tr.KERNEL in e[0]]
    assert kern and tr.op_seconds(t, lambda n: tr.KERNEL in n) == \
        pytest.approx(_naive_busy(kern, lo, hi) / 1e9)
    (a, b), = tr.host_spans(t, tr.CALL)
    inside = _naive_busy(ops, a, b)
    assert tr.host_self_s(t) == pytest.approx([(b - a - inside) / 1e9])
    bd = tr.breakdown(t)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0].startswith("jit__device_driver:")


def test_fold_work_by_hand():
    # BT(8): root 0 (depth 0), 1-2 (depth 1), leaves 3-6 (depth 2)
    parent = np.array([-1, 0, 0, 1, 1, 2, 2])
    # k = 2: K = 3. Level 0: 1 parent, 2 children, nl 2, kd min(3, 8) = 3;
    # level 1: 2 parents, 4 children, nl 3, kd min(3, 4) = 3
    lv = roofline.fold_levels(parent, 2)
    assert [(x["parents"], x["children"], x["nl"], x["kd"]) for x in lv] == \
        [(1, 2, 2, 3), (2, 4, 3, 3)]
    # bytes: children*nl*kd + parents*nl*kd + parents*(3+nl), x4 bytes
    # level 0: 12 + 6 + 5 = 23; level 1: 36 + 18 + 12 = 66
    # ops: children*(nl+1)*kd*(kd+1): 2*3*12 = 72; 4*4*12 = 192
    assert roofline.fold_work(parent, 2, batch=5) == {
        "bytes": (23 + 66) * 4 * 5, "ops": (72 + 192) * 5}
    # k = 8: the subtree sizes cap the columns: level 1 (size 3) kd 4,
    # level 0 (size 7) kd 8
    assert [x["kd"] for x in roofline.fold_levels(parent, 8)] == [8, 4]


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_parts_are_found_from_files_alone(tmp_path):
    """A configuration, a traffic mix (data for an existing driver) and a
    metric reader are added as new files and entries; nothing that was
    there changes, and a run reports the new metric."""
    root = L.make_root(tmp_path)
    before = _digest(root / "bench")
    bench = root / "bench"
    (bench / "configs" / "bt128_k4.json").write_text(json.dumps(
        {"tree": {"kind": "bt", "n_total": 128}, "k": 4}))
    (bench / "traffic" / "batch2.json").write_text(json.dumps(
        dict(L.TRAFFIC["batch4"], tenants_per_call=2)))
    (bench / "metrics" / "calls_per_s.py").write_text(
        "def read(ctx):\n    return len(ctx.latencies_s) / ctx.window_s\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "bt128_k4", "source": "test",
                            "file": "bench/configs/bt128_k4.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "small", "config": "bt128_k4",
                              "traffic": "batch2", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "calls_per_s", "unit": "calls/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(bench)
    assert all(after[f] == h for f, h in before.items())
    out = L.run(root, "small", seconds=0.3)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"calls_per_s", "setup_s"}


def _bench_cmd(root: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bt4096_batch",
         "--seed", str(2**33 + 3), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_chip():
    p = _bench_cmd(L.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU chip" in p.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    has no program to run."""
    spec = json.loads((L.ROOT / "BENCHMARK.json").read_text())
    shutil.copy(L.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(L.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bt4096_batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_fat_tree_configuration_has_the_source_shape():
    """k = 16: 16 pods of 8 edge switches with 8 hosts each (1,024 hosts),
    every link at one rate; each pod tree reaches the destination through
    one of the destination pod's 8 down-links, two trees to a link, and
    all through its access link."""
    from bench.drivers.fleet_admission import fleet_from
    cfg = json.loads((L.ROOT / "bench" / "configs" /
                      "fattree16_cap2.json").read_text())
    fleet = fleet_from(cfg)
    assert fleet.n_trees == 16
    assert sum(int(tp.load.sum()) for tp in fleet.topos) == 1024
    for tp in fleet.topos:
        edges = np.nonzero(tp.load)[0]
        assert len(edges) == 8 and set(tp.load[edges]) == {8}
        assert set(tp.tree.parent[edges]) == {1} and tp.tree.parent[1] == 0
        assert np.all(tp.tree.rho == 1.0)
        assert len(tp.device_leaf) == 64
    assert np.all(fleet.core_rho == 1.0) and len(fleet.core_rho) == 9
    down = [p[0] for p in fleet.core_path]
    assert sorted(down) == sorted(list(range(8)) * 2)
    assert all(p[1] == 8 for p in fleet.core_path)


def test_unknown_device_kind_is_an_error():
    from bench.run import peaks_for
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] > 0
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        peaks_for("TPU v0")


def test_fill_reference_by_hand():
    """A root with two leaf switches of one host each, k = 1, one slot per
    switch. The first tenant's best is the root blue: the leaves send 1
    each and the root 1, phi 3. The root is then full, and a blue leaf
    saves nothing, so the second tenant's best is all red: 1 + 1 + 2."""
    from types import SimpleNamespace
    from bench.drivers.fleet_admission import Driver
    d = SimpleNamespace(fleet=SimpleNamespace(n_trees=1),
                        parent=np.array([-1, 0, 0]), rho=np.ones(3),
                        load=np.array([0, 1, 1]), k=1, cap=1, _ref={})
    blue = np.array([[1, 0, 0], [0, 0, 0]])
    assert Driver._fill_ref(d, np.zeros(3, np.int64), [0, 0], blue) == \
        [3.0, 4.0]
    # the orchestrator's own claim on the root leaves it full from the start
    assert Driver._fill_ref(d, np.array([1, 0, 0]), [0], blue[1:]) == [4.0]
