"""The gradient-sync cell on the CPU: its configuration against the model
code, the work its roofline counts, the plain reference, the readers of
its trace, and the driver and comparison on 4 host devices with the timed
path sound and broken (``gradsync_cpu_check.py``)."""
from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import bench_testlib as L  # noqa: F401  (puts the repository on the path)
from bench import gradsync_reference as ref
from bench import gradsync_work as work
from bench import reference, sync_ops, trace as tr
from bench.registry import Registry

HERE = Path(__file__).resolve().parent
CFG = json.loads((L.ROOT / "bench" / "configs" /
                  "dsv2lite_ep8_dp4.json").read_text())
FAB = CFG["fabric"]
PEAKS = json.loads((L.ROOT / "bench" / "peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def _published_model(n_layers: int):
    """The program's model at DeepSeek-V2-Lite's published widths."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name="deepseek-v2-lite", family="moe", n_layers=n_layers,
        d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944, vocab=102400,
        attn_type="mla", kv_lora_rank=512, q_lora_rank=0, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, n_experts=64, n_shared_experts=2,
        top_k=6, d_ff_expert=1408, moe_dense_prefix=1, dtype="float32",
        norm_eps=1e-6)


def test_leaves_are_the_models_at_the_chips_share():
    """eval_shape of the model's parameters at 5 layers, with the stacked
    experts' expert axis cut to the 8 held and the vocabulary to an
    eighth, gives the configuration's 27 leaves."""
    import jax
    from repro.models import api
    shapes = jax.eval_shape(api.init_fn(_published_model(5)),
                            jax.random.PRNGKey(0))
    want = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        shape = list(leaf.shape)
        if "/experts/" in name:
            shape[1] //= 8
        if name == "embed_tokens":
            shape[0] //= 8
        if name == "lm_head":
            shape[1] //= 8
        want[name] = [shape, str(leaf.dtype)]
    got = {p: [s, d] for p, s, d in CFG["leaves"]}
    assert got == want and len(got) == 27


def test_totals_and_published_widths():
    sizes = {p: int(np.prod(s)) for p, s, _ in CFG["leaves"]}
    assert sum(sizes.values()) == 535_060_992
    leaves = {p: s for p, s, _ in CFG["leaves"]}
    assert leaves["layers/moe/router/w"] == [4, 2048, 64]
    assert CFG["router_outputs"] == 64
    assert max(sizes, key=sizes.get) == "layers/moe/experts/w_down"
    assert leaves["layers/moe/experts/w_down"] == [4, 8, 1408, 2048]
    widths = {"hidden_size": 2048, "num_attention_heads": 16,
              "kv_lora_rank": 512, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128,
              "intermediate_size": 10944, "moe_intermediate_size": 1408,
              "n_shared_experts": 2, "num_experts_per_tok": 6,
              "first_k_dense_replace": 1}
    assert {k: CFG[k] for k in widths} == widths
    assert CFG["q_lora_rank"] is None
    cut = {k: (v["published"], v["here"]) for k, v in CFG["reduced"].items()}
    assert cut == {"num_hidden_layers": (27, 5), "n_routed_experts": (64, 8),
                   "vocab_size": (102400, 12800)}
    assert all(CFG[k] == here for k, (_, here) in cut.items())
    spec = json.loads((L.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CFG["name"])
    assert sorted(entry["reduced"]) == sorted(cut)
    assert entry["source"] == CFG["source"]


def test_fabric_is_the_training_drivers():
    from repro.launch.train import dp_fleet
    topo = dp_fleet(FAB["n_devices"])
    assert topo.tree.parent.tolist() == FAB["parent"]
    assert topo.tree.rho.tolist() == FAB["rho"]
    assert topo.load.tolist() == FAB["load"]
    assert topo.device_leaf.tolist() == FAB["device_leaf"]


# -- the roofline's work -------------------------------------------------------

# dp_fleet(4): root 0; pods 1, 2; racks 3 (under 1), 4 (under 2); chips
# 5, 6 under rack 3 and 7, 8 under rack 4. A switch lives on its first
# child's chip: racks 3 and 4 on chips 0 and 2, pods 1 and 2 likewise,
# the root on chip 0.
K2_BLUE = [0, 0, 0, 1, 1, 0, 0, 0, 0]
WORK = {
    # blue racks fold 2 -> 1; chip 1 -> 0 and chip 3 -> 2 (first
    # PermuteRound), pod 2 on chip 2 -> root on chip 0 (second), the
    # root's 2 messages folded on chip 0, the sum sent to chips 1-3
    "k2": (K2_BLUE, {"sent": [1, 1, 1, 1], "recv": [2, 1, 2, 1],
                     "hbm": [3 + 3, 0, 3, 0]}),
    # nothing folds on the way: pod 2 forwards both of rack 4's messages,
    # and chip 0 folds all 4
    "all_red": ([0] * 9, {"sent": [1, 1, 2, 1], "recv": [3, 1, 2, 1],
                          "hbm": [5, 0, 0, 0]}),
    # racks fold 2 -> 1, pods pass 1 on, the blue root folds 2 -> 1
    "all_blue": ([1] * 9, {"sent": [1, 1, 1, 1], "recv": [2, 1, 2, 1],
                           "hbm": [3 + 3, 0, 3, 0]}),
}


@pytest.mark.parametrize("case", sorted(WORK))
def test_sync_work_by_hand(case):
    blue, want = WORK[case]
    B = 1000
    got = work.sync_work(FAB["parent"], FAB["load"], FAB["device_leaf"],
                         np.asarray(blue, bool), B)
    assert {k: v.tolist() for k, v in got.items()} == \
        {k: [x * B for x in v] for k, v in want.items()}


@pytest.mark.parametrize("case", sorted(WORK))
def test_sync_work_follows_the_placement(case):
    """The messages counted are the phi model's (Algorithm 1 on the
    placement), the ones the program's own ReduceProgram accounts for; the
    counts never look at the program's buffers."""
    from repro.collectives import build_program
    from repro.launch.train import dp_fleet
    blue = np.asarray(WORK[case][0], bool)
    topo = dp_fleet(4)
    prog = build_program(topo, blue)
    msgs = reference.messages(np.asarray(FAB["parent"]),
                              np.asarray(FAB["load"]), blue)
    assert int(msgs.sum()) == prog.total_network_messages
    assert reference.phi(FAB["parent"], FAB["rho"], FAB["load"], blue) == \
        prog.utilization
    home = work.homes(np.asarray(FAB["parent"]), FAB["device_leaf"])
    assert home.tolist() == [0, 0, 2, 0, 2, 0, 1, 2, 3]
    assert home[0] == prog.root_home and msgs[0] == prog.root_count


def test_least_seconds_at_the_peaks():
    blue, _ = WORK["k2"]
    nbytes = 4 * 535_060_992
    w = work.sync_work(FAB["parent"], FAB["load"], FAB["device_leaf"],
                       np.asarray(blue, bool), nbytes)
    # chip 0 receives two gradients (ICI-bound: 2 B at 200 GB/s) and its
    # sums move six (6 B at 819 GB/s)
    ici = 2 * nbytes / (PEAKS["ici_bits_per_s"] / 8)
    assert 6 * nbytes / PEAKS["hbm_bytes_per_s"] < ici
    assert work.least_seconds(w, PEAKS) == pytest.approx(ici)


# -- the plain reference -----------------------------------------------------------

def test_bf16_rounding_matches_ml_dtypes():
    import ml_dtypes
    g = np.random.default_rng(0)
    x = (g.standard_normal(1 << 16) * 10.0 ** g.integers(-20, 20, 1 << 16)
         ).astype(np.float32)
    x[:4] = [0.0, -0.0, 1.00390625, 1.01171875]       # ties to even
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert np.array_equal(ref.round_bf16(x), want)


def test_bound_and_excess():
    g = np.random.default_rng(1).standard_normal((4, 1000)).astype(
        np.float32)
    s, bound = ref.sum_and_bound(g)
    assert np.array_equal(s, g.astype(np.float64).sum(0))
    u = 2.0 ** -24
    assert ref.bound_factor(4) == pytest.approx(3 * u, rel=1e-6)
    np.testing.assert_allclose(bound, ref.bound_factor(4)
                               * np.abs(g.astype(np.float64)).sum(0))
    # every float32 order of the sum is within the bound
    for order in ([0, 1, 2, 3], [3, 1, 0, 2]):
        acc = g[order[0]].copy()
        for i in order[1:]:
            acc = acc + g[i]
        assert ref.excess(acc, s, bound) == 0
    assert ref.excess(g[:3].sum(0), s, bound) > 990
    assert ref.excess(ref.sum_bf16(g), s, bound) > 900
    bad = (g[0] + g[1] + g[2] + g[3]).astype(np.float32)
    bad[7] = np.nan
    assert ref.excess(bad, s, bound) == 1


# -- the trace's readers ------------------------------------------------------------

def _trace(n_dev=4):
    """Window [0, 1000) ns; two calls; on each chip a fold, a
    collective-permute pair and an all-reduce per call. Chip 0 also runs
    a scatter that overlaps its permute."""
    planes = []
    for d in range(n_dev):
        ops = []
        for c0 in (100, 600):
            ops += [["%fusion.1", c0, 50],
                    ["%collective-permute-start.2", c0 + 50, 10],
                    ["%collective-permute-done.2", c0 + 60, 90],
                    ["%all-reduce.3", c0 + 200, 100]]
            if d == 0:
                ops.append(["%scatter.4", c0 + 100, 100])
        planes.append({"name": f"/device:TPU:{d}", "lines": [
            {"name": tr.OPS, "events": ops},
            {"name": tr.MODULES, "events": [["jit_body(1)", 100, 300],
                                            ["jit_body(1)", 600, 300]]}]})
    host = [[tr.WINDOW, 0, 1000], [tr.CALL, 90, 420], [tr.CALL, 590, 400]]
    planes.append({"name": "/host:CPU",
                   "lines": [{"name": "python3", "events": host}]})
    return {"planes": planes}


def _ctx():
    drv = SimpleNamespace(parent=FAB["parent"],
                          load=FAB["load"], device_leaf=FAB["device_leaf"],
                          blue=np.asarray(K2_BLUE, bool),
                          grad_bytes=4 * 535_060_992)
    return SimpleNamespace(trace=_trace(), driver=drv, peaks=PEAKS)


def _read(name, ctx):
    return Registry.load(L.ROOT).reader(name)(ctx)


def test_readers_on_a_trace_by_hand():
    ctx = _ctx()
    # chip 0 per call: fold [0, 50) + permute [50, 150) + scatter
    # [100, 200) + all-reduce [200, 300): busy 300 ns; collectives 200 ns
    # (the permute pair and the all-reduce), the rest 150 ns
    assert sync_ops.is_collective("%collective-permute-done.2")
    assert sync_ops.is_collective("%all-reduce-start.7")
    assert sync_ops.is_collective("%psum_invariant.214")
    assert not sync_ops.is_collective("%scatter.4")
    assert not sync_ops.is_collective("%fusion.9 tpu_custom_call")
    assert _read("gradsync_device_ms", ctx) == pytest.approx(300e-6)
    assert _read("collective_ms", ctx) == pytest.approx(200e-6)
    assert _read("fold_ms", ctx) == pytest.approx(150e-6)
    # chips 1-3 are busy 250 ns a call: mean (300 + 3 * 250) * 2 of 1000
    assert _read("device_idle_pct.gradsync", ctx) == pytest.approx(
        100 * (1 - 2 * (300 + 3 * 250) / 4 / 1000))
    least = work.least_seconds(work.sync_work(
        FAB["parent"], FAB["load"], FAB["device_leaf"],
        np.asarray(K2_BLUE, bool), 4 * 535_060_992), PEAKS)
    assert _read("gradsync_roofline", ctx) == pytest.approx(
        100 * least / 300e-9)


def test_readers_find_nothing_without_device_ops():
    ctx = _ctx()
    for p in ctx.trace["planes"]:
        if p["name"].startswith("/device"):
            p["lines"] = []
    for name in ("gradsync_device_ms", "collective_ms", "fold_ms",
                 "device_idle_pct.gradsync", "gradsync_roofline"):
        assert _read(name, ctx) is None, name


def test_gradsync_ms_is_the_whole_window_over_every_sync():
    ctx = SimpleNamespace(window_s=51.3, units=180)
    assert _read("gradsync_ms", ctx) == pytest.approx(51.3 / 180 * 1e3)


def test_the_driver_takes_the_programs_whole_tree_reduce(monkeypatch):
    """Where the program has ``reduce_tree_local`` the cell times it;
    otherwise it maps ``reduce_local`` over the leaves."""
    from bench.drivers import grad_sync
    ta = importlib.import_module("repro.collectives.tree_allreduce")
    if hasattr(ta, "reduce_tree_local"):
        assert grad_sync.program_reduce() is ta.reduce_tree_local
        return
    seen = []
    monkeypatch.setattr(ta, "reduce_local",
                        lambda g, p, a: seen.append(a) or g)
    out = grad_sync.program_reduce()({"a": 1, "b": 2}, None, "data")
    assert out == {"a": 1, "b": 2} and seen == ["data", "data"]
    whole = object()
    monkeypatch.setattr(ta, "reduce_tree_local", whole, raising=False)
    assert grad_sync.program_reduce() is whole


# -- the driver and comparison on 4 host devices -----------------------------------

@pytest.fixture(scope="module")
def cpu_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(L.ROOT / "src"), str(L.ROOT)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    p = subprocess.run([sys.executable, str(HERE / "gradsync_cpu_check.py")],
                       cwd=L.ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(cpu_runs):
    out = cpu_runs["runs"]["sound"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(out["metrics"]) == {"gradsync_ms", "setup_s"}
    assert out["device"]["count"] == 4


def test_control_is_not_correct(cpu_runs):
    c = cpu_runs["control"]
    assert c["lower"] == {"grad_excess": 0.0, "unanswered": 0.0}
    assert c["upper"]["grad_excess"] > 0
    for row in c["control"].values():
        assert row["grad_excess"] > 0 and row["failed"] > 0


# each fault planted under the timed path, and the number that catches it
CAUGHT = {"dropped_contribution": "grad_excess", "bf16_inputs": "grad_excess",
          "leaf_unreduced": "grad_excess", "exchange_left_out": "grad_excess",
          "state_unchanged": "grad_excess", "answer_altered": "grad_excess",
          "half_the_leaves": "unanswered", "copy_altered": "grad_excess"}


@pytest.mark.parametrize("fault", sorted(CAUGHT))
def test_broken_path_is_not_correct(cpu_runs, fault):
    out = cpu_runs["runs"][fault]
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0
    c = out["checks"][CAUGHT[fault]]
    assert c["value"] > c["limit"], out["checks"]
