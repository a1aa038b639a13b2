"""The program's counters (``repro.telemetry``) on small trees, and that
tracing changes no answer."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro import telemetry
from repro.collectives import build_fleet
from repro.core import bt, sample_load
from repro.core.forest import build_forest, layout_stats
from repro.engine import cache_stats, solve_batch, solve_forest, solve_fleet
from repro.engine import batched
from repro.launch.compile_cache import compile_stats
from repro.runtime import Orchestrator, OrchestratorConfig


def _batch(n=64, B=4):
    t = bt(n, "constant")
    return [t] * B, [sample_load(t, "power-law", seed=s) for s in range(B)]


def _delta(before: dict, name: str) -> float:
    return telemetry.get(name) - before.get(name, 0)


def test_solve_batch_counts_one_solve_and_one_forest():
    trees, loads = _batch()
    c0 = telemetry.counters()
    solve_batch(trees, loads, 4)
    assert _delta(c0, "engine.solves") == 1
    assert _delta(c0, "engine.forests_built") == 1
    assert _delta(c0, "engine.upload_bytes") > 0


def test_upload_bytes_are_the_uploaded_arrays_and_a_resolve_hits():
    trees, loads = _batch()
    f = build_forest(trees, loads)
    c0 = telemetry.counters()
    solve_forest(f, 4)
    uploaded = batched._INPUT_CACHE[(id(f), np.dtype(jnp.float32).str)][1]
    assert _delta(c0, "engine.upload_bytes") == sum(x.nbytes
                                                    for x in uploaded)
    assert _delta(c0, "engine.upload_hits") == 0
    c1 = telemetry.counters()
    solve_forest(f, 4)
    assert _delta(c1, "engine.upload_bytes") == 0
    assert _delta(c1, "engine.upload_hits") == 1
    assert _delta(c0, "engine.solves") == 2


def test_penalty_rounds_are_the_loops_rounds():
    fleet = build_fleet(2, 2, 4, spine_rho=64.0)
    trees = [tp.tree for tp in fleet.topos]
    tree_of = [0, 0, 1, 1]
    loads = [fleet.topos[g].load for g in tree_of]
    c0 = telemetry.counters()
    res = solve_fleet(trees, loads, tree_of, 2, core_rho=fleet.core_rho,
                      core_path=fleet.core_path, max_rounds=6)
    assert _delta(c0, "penalty.loops") == 1
    assert _delta(c0, "penalty.rounds") == res.rounds
    assert _delta(c0, "engine.solves") == 1


def test_waves_are_counted_and_views_keep_their_keys():
    fleet = build_fleet(2, 2, 4, spine_rho=64.0)
    orch = Orchestrator(fleet, OrchestratorConfig(k=2, capacity=2))
    c0 = telemetry.counters()
    orch.begin_workloads(fleet=[1, 1], congestion_aware=True,
                         device_admission=True)
    assert _delta(c0, "orchestrator.waves") == 1
    assert _delta(c0, "penalty.loops") == 1
    assert set(layout_stats()) == {"forests_built", "distinct_layouts"}
    assert cache_stats() == layout_stats()
    assert set(compile_stats()) == {"hits", "misses", "compile_s"}
    assert layout_stats()["forests_built"] == telemetry.get(
        "engine.forests_built")


def test_reset_clears_counts_and_distinct_keys():
    saved = telemetry.counters()
    try:
        telemetry.reset()
        telemetry.count_distinct("t.keys", "a")
        telemetry.count_distinct("t.keys", "a")
        telemetry.count_distinct("t.keys", "b")
        assert telemetry.count("t.n", 3) == 3
        assert telemetry.counters() == {"t.keys": 2, "t.n": 3}
        telemetry.reset()
        assert telemetry.counters() == {}
        telemetry.count_distinct("t.keys", "a")
        assert telemetry.get("t.keys") == 1
    finally:
        telemetry.reset()
        for name, v in saved.items():
            telemetry.count(name, v)


def _answers():
    trees, loads = _batch(B=3)
    res = solve_batch(trees, loads, 4)
    fleet = build_fleet(2, 2, 4, spine_rho=64.0)
    orch = Orchestrator(fleet, OrchestratorConfig(k=2, capacity=2))
    progs = orch.begin_workloads(fleet=[2, 1], congestion_aware=True,
                                 device_admission=True)
    return (res.blue, res.costs, [p.utilization for p in progs],
            [r.copy() for r in orch._residuals])


def test_answers_are_the_same_with_a_profiler_session(tmp_path):
    plain = _answers()
    with jax.profiler.trace(str(tmp_path)):
        traced = _answers()
    assert np.array_equal(plain[0], traced[0])
    assert np.array_equal(plain[1], traced[1])
    assert plain[2] == traced[2]
    for a, b in zip(plain[3], traced[3]):
        assert np.array_equal(a, b)
