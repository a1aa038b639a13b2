"""Path choice on a multi-rooted fabric: ``fat_tree_fleet``, the physical
switch ledger, and the loop that picks each tenant's tree among its pod's.

Covers the degenerate case (a one-path fleet answers exactly as the
one-tree-per-tenant fleet always has: masks, ledgers and programs of
recorded waves, bit for bit), the fabric's shape, agreement with the plain
reference on seeded waves of a small k=4 fat-tree with one slot per switch,
claims ranked per physical switch across trees, and release and preemption
returning claims to the physical ledger.
"""
import dataclasses
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench.ecmp_reference import Fabric
from repro import telemetry
from repro.collectives import (ClusterTopology, Fleet, fat_tree_fleet,
                               plan_fleet)
from repro.core.tree import Tree
from repro.engine import solve_fleet
from repro.runtime import Orchestrator, OrchestratorConfig, PreemptionPolicy

ROOT = Path(__file__).resolve().parents[1]
WAVES = Path(__file__).resolve().parent / "fleet_one_path_waves.json"


def _cap2_fleet() -> Fleet:
    """``fattree16_cap2`` as its configuration spells it: 16 trees, each
    owning its switches, over the destination's shared core links."""
    cfg = json.loads((ROOT / "bench/configs/fattree16_cap2.json").read_text())
    t, fl = cfg["tree"], cfg["fleet"]
    load = np.asarray(t["load"], np.int64)

    def topo():
        return ClusterTopology(
            tree=Tree(np.asarray(t["parent"]), np.asarray(t["rho"], float)),
            device_leaf=np.repeat(np.arange(len(load)), load), load=load)
    return Fleet(topos=tuple(topo() for _ in range(fl["n_trees"])),
                 core_rho=np.asarray(fl["core_rho"], float),
                 core_path=tuple(tuple(p) for p in fl["core_path"]))


def _digest(prog) -> str:
    def enc(x):
        if isinstance(x, np.ndarray):
            return [str(x.dtype), x.tolist()]
        if isinstance(x, (list, tuple)):
            return [enc(v) for v in x]
        if hasattr(x, "__dataclass_fields__"):
            return [type(x).__name__] + [enc(getattr(x, f))
                                         for f in x.__dataclass_fields__]
        if isinstance(x, np.integer):
            return int(x)
        if isinstance(x, (float, np.floating)):
            return float(x).hex()
        return x
    return hashlib.sha256(json.dumps(enc(prog)).encode()).hexdigest()


# ---------------------------------------------------------------------------
# one path per pod: the recorded waves of the one-tree-per-tenant fleet


@pytest.mark.parametrize("build", ["config", "pinned"])
def test_one_path_fleet_answers_as_recorded(build):
    """Waves of fattree16_cap2 (1 and 2 slots a switch, jobs kept across
    some waves), recorded before path choice existed: the same masks,
    utilizations, programs, ledgers, round histories and shortfalls, from
    the configuration's fleet and from ``fat_tree_fleet(16, "pinned")``."""
    fleet = (_cap2_fleet() if build == "config"
             else fat_tree_fleet(16, paths="pinned"))
    assert not fleet.shared and fleet.n_groups == fleet.n_trees == 16
    for case in json.loads(WAVES.read_text())["cases"]:
        orch = Orchestrator(fleet, OrchestratorConfig(
            k=4, capacity=case["capacity"]))
        assert orch.blue.astype(int).tolist() == case["own"]
        for w, want in enumerate(case["waves"]):
            progs = orch.begin_workloads(fleet=want["counts"],
                                         congestion_aware=True,
                                         device_admission=True)
            jobs = sorted(orch.jobs.values(), key=lambda j: j.order)
            res = orch.last_congestion
            got = {
                "tree": [j.tree for j in jobs],
                "blue": [j.blue.astype(int).tolist() for j in jobs],
                "util": [float(p.utilization).hex() for p in progs],
                "program": [_digest(p) for p in progs],
                "residual": [r.tolist() for r in orch._residuals],
                "rounds": int(res.rounds),
                "history": [float(h).hex() for h in res.history],
                "dropped": int(orch.last_admission["dropped"])}
            for key, v in got.items():
                assert v == want[key], (case["capacity"], w, key)
            if w % 2 == 0:
                orch.release_workloads([j.job_id for j in jobs])


# ---------------------------------------------------------------------------
# the fabric


def test_fat_tree_fleet_shape():
    f = fat_tree_fleet(16)
    assert f.n_trees == 1024 and f.n_groups == 16
    assert all(len(g) == 64 for g in f.groups)
    assert f.n_switches == 320 and f.shared
    # tree 0's switches come first; its nodes are [core, agg, 8 edges]
    assert f.switch_id[0].tolist() == list(range(10))
    assert f.core_path[0] == (0, 8) and f.n_core == 9
    # candidate i of a pod: aggregation switch i mod 8, core i div 8
    agg = {int(f.switch_id[g][1]) for g in f.groups[3]}
    core = {int(f.switch_id[g][0]) for g in f.groups[3]}
    assert len(agg) == 8 and len(core) == 64
    assert {f.core_path[g][0] for g in f.groups[3][:8]} == set(range(8))
    # pods share core switches, never edge or aggregation switches
    s0 = set(np.concatenate([f.switch_id[g] for g in f.groups[0]]).tolist())
    s1 = set(np.concatenate([f.switch_id[g] for g in f.groups[1]]).tolist())
    assert len(s0 & s1) == 64
    # every tree shares one Tree object: one packed layout for them all
    assert len({id(tp.tree) for tp in f.topos}) == 1
    pinned = fat_tree_fleet(16, paths="pinned")
    assert not pinned.shared and pinned.n_switches == 160


@pytest.mark.parametrize("bad, match", [
    (dict(k=5), "even"),
    (dict(k=4, paths="some"), "paths"),
    (dict(k=4, rho=0.0), "positive"),
])
def test_fat_tree_fleet_validation(bad, match):
    with pytest.raises(ValueError, match=match):
        fat_tree_fleet(**bad)


def test_fleet_physical_ids_validation():
    f = fat_tree_fleet(4)
    with pytest.raises(ValueError, match="twice"):
        dataclasses.replace(f, switch_id=(np.zeros(4, np.int64),)
                            + f.switch_id[1:])
    with pytest.raises(ValueError, match="id maps"):
        dataclasses.replace(f, link_id=f.link_id[:3])
    with pytest.raises(ValueError, match="empty"):
        dataclasses.replace(f, groups=((),))
    with pytest.raises(ValueError, match="out of range"):
        dataclasses.replace(f, groups=((0, 99),))
    small = fat_tree_fleet(4, hosts_per_edge=1)
    mixed = Fleet(topos=(f.topos[0], small.topos[0]),
                  core_rho=np.ones(3), core_path=((0, 2), (1, 2)),
                  groups=((0, 1),))
    assert mixed.n_groups == 1
    six = fat_tree_fleet(4, pods=1)
    odd = ClusterTopology(tree=Tree(np.asarray([-1, 0, 0]), np.ones(3)),
                          device_leaf=np.asarray([1, 2]),
                          load=np.asarray([0, 1, 1]))
    with pytest.raises(ValueError, match="node-aligned"):
        Fleet(topos=(six.topos[0], odd), core_rho=np.ones(3),
              core_path=((0, 2), (1, 2)), groups=((0, 1),))


# ---------------------------------------------------------------------------
# path choice against the plain reference


def _waves(orch, fleet, ref, cap, T, waves, seed, release=True):
    """Admit seeded waves and compare each with the reference; returns
    the largest readings."""
    rng = np.random.default_rng(seed)
    G = fleet.n_groups
    worst = dict(fill_gap=0.0, util_gap=0.0, capacity_excess=0.0,
                 ledger_gap=0.0, path_invalid=0.0, budget_excess=0.0)
    for _ in range(waves):
        counts = 1 + rng.multinomial(T - G, np.full(G, 1.0 / G))
        progs = orch.begin_workloads(fleet=counts.tolist(),
                                     congestion_aware=True,
                                     device_admission=True)
        jobs = sorted(orch.jobs.values(), key=lambda j: j.order)
        assert len(jobs) == len(progs) == T
        left = np.full(ref.n_switches, cap, np.int64)
        left[ref.switches[0][orch.blue]] -= 1
        pods = [p for p, c in enumerate(counts) for _ in range(c)]
        for j, p, pr in zip(jobs, pods, progs):
            worst["path_invalid"] += ref.pod_of(j.tree) != p
            worst["budget_excess"] = max(worst["budget_excess"],
                                         j.blue.sum() - orch.cfg.k)
            worst["fill_gap"] = max(worst["fill_gap"], abs(
                pr.utilization - ref.least_phi(p, left)))
            worst["util_gap"] = max(worst["util_gap"],
                                    abs(pr.utilization - ref.phi(j.blue)))
            left[ref.switches[j.tree][j.blue]] -= 1
        worst["capacity_excess"] = max(worst["capacity_excess"],
                                       -left.min())
        # the ledger is the fabric's: tree ids name physical switches
        claims = np.zeros(fleet.n_switches, np.int64)
        np.add.at(claims, fleet.switch_id[0][orch.blue], 1)
        for j in jobs:
            np.add.at(claims, fleet.switch_id[j.tree][j.blue], 1)
        worst["ledger_gap"] = max(worst["ledger_gap"], np.abs(
            orch.fabric_ledger + claims - cap).max())
        if release:
            orch.release_workloads([j.job_id for j in jobs])
    return worst


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_path_choice_matches_reference(seed):
    """k=4 fat-tree, one slot a switch, 12 tenants a wave over 4 pods of
    4 candidate trees: contention on every kind of switch."""
    fleet = fat_tree_fleet(4)
    ref = Fabric({"k": 4, "rho": 1.0}, 2)
    orch = Orchestrator(fleet, OrchestratorConfig(k=2, capacity=1))
    worst = _waves(orch, fleet, ref, 1, 12, 4, seed)
    assert all(v == 0 for v in worst.values()), worst


def test_path_choice_counters_and_spans():
    telemetry.reset()
    fleet = fat_tree_fleet(4)
    orch = Orchestrator(fleet, OrchestratorConfig(k=2, capacity=1))
    orch.begin_workloads(fleet=[3, 3, 3, 3], congestion_aware=True,
                         device_admission=True)
    c = telemetry.counters()
    assert c["paths.waves"] == 1 and c["paths.candidates"] == 12 * 4
    assert 0 <= c["paths.moves"] <= 12
    # the one-path loop counts no path choice
    before = telemetry.counters()
    plan_fleet(fat_tree_fleet(4, paths="pinned"), 2, counts=[1, 1, 1, 1])
    assert telemetry.counters()["paths.waves"] == before["paths.waves"]


def test_claims_ranked_per_physical_switch_across_trees():
    """Two tenants on two trees that share their aggregation switch, one
    slot on it: the later tenant in order cannot claim it through its
    own tree, and takes the pod's other aggregation switch instead."""
    fleet = fat_tree_fleet(4, pods=1)
    loads = [fleet.topos[0].load] * 2
    trees = [tp.tree for tp in fleet.topos]
    res = solve_fleet(trees, loads, [0, 0], 2,
                      candidates=np.asarray([list(fleet.groups[0])] * 2),
                      switch_id=fleet.switch_id, link_id=fleet.link_id,
                      core_rho=fleet.core_rho, core_path=fleet.core_path,
                      residual=np.ones(fleet.n_switches, np.int64))
    agg = [int(fleet.switch_id[g][1]) for g in res.tree_of]
    assert res.blue[0, 1] and res.blue[1, 1] and agg[0] != agg[1]
    assert res.residual_after.shape == (fleet.n_switches,)
    assert res.residual_after.min() >= 0
    # fixed to one tree each, the same claim is ranked out
    one = solve_fleet(trees, loads, [0, 2], 2, switch_id=fleet.switch_id,
                      link_id=fleet.link_id, core_rho=fleet.core_rho,
                      core_path=fleet.core_path,
                      residual=np.ones(fleet.n_switches, np.int64))
    assert fleet.switch_id[0][1] == fleet.switch_id[2][1]
    assert one.blue[0, 1] and not one.blue[1, 1]


def test_release_and_preemption_on_the_physical_ledger():
    fleet = fat_tree_fleet(4)
    orch = Orchestrator(fleet, OrchestratorConfig(k=2, capacity=1))
    start = orch.fabric_ledger.copy()
    orch.begin_workloads(fleet=[2, 2, 2, 2], congestion_aware=True,
                         device_admission=True)
    assert orch._residuals is None           # trees share switches
    held = orch.fabric_ledger.copy()
    assert (held <= start).all() and (held < start).any()
    freed = orch.release_workloads(list(orch.jobs))
    assert freed == int((start - held).sum())
    assert np.array_equal(orch.fabric_ledger, start)
    # low-priority jobs crowd pod 3, then a wave of 9 more there makes room
    ref = Fabric({"k": 4, "rho": 1.0}, 2)
    orch.begin_workloads(fleet=[1, 1, 1, 4], congestion_aware=True,
                         device_admission=True, priority=0)
    orch.begin_workloads(fleet=[1, 1, 1, 9], congestion_aware=True,
                         device_admission=True, priority=1,
                         preemption=PreemptionPolicy("priority", 16))
    ev = orch.preemption_events
    assert ev and ev[-1]["victims"] and ev[-1]["freed"] > 0
    claims = np.zeros(fleet.n_switches, np.int64)
    np.add.at(claims, fleet.switch_id[0][orch.blue], 1)
    for j in orch.jobs.values():
        assert ref.pod_of(j.tree) >= 0
        np.add.at(claims, fleet.switch_id[j.tree][j.blue], 1)
    assert np.array_equal(orch.fabric_ledger + claims,
                          np.full(fleet.n_switches, 1))
    orch.release_workloads(list(orch.jobs))
    assert np.array_equal(orch.fabric_ledger, start)


def test_host_fleet_path_claims_on_the_physical_ledger():
    """Without in-loop admission the claims still land on the physical
    switches of the trees the loop chose, collisions re-solved."""
    fleet = fat_tree_fleet(4)
    orch = Orchestrator(fleet, OrchestratorConfig(k=2, capacity=1))
    start = orch.fabric_ledger.copy()
    progs = orch.begin_workloads(fleet=[2, 2, 2, 2], congestion_aware=True)
    assert len(progs) == 8 and orch.fabric_ledger.min() >= 0
    claims = sum(int(j.blue.sum()) for j in orch.jobs.values())
    assert int((start - orch.fabric_ledger).sum()) == claims


def test_path_choice_needs_the_device_loop():
    fleet = fat_tree_fleet(4, pods=1)
    with pytest.raises(ValueError, match="device loop"):
        solve_fleet([tp.tree for tp in fleet.topos],
                    [fleet.topos[0].load], [0], 2,
                    candidates=np.asarray([list(fleet.groups[0])]),
                    device_loop=False)


def test_release_of_an_unknown_job_keeps_the_ledger_whole():
    """Jobs before a bad id are released, claims and all; the bad id
    raises."""
    fleet = fat_tree_fleet(4)
    orch = Orchestrator(fleet, OrchestratorConfig(k=2, capacity=1))
    start = orch.fabric_ledger.copy()
    orch.begin_workloads(fleet=[1, 1, 1, 1], congestion_aware=True,
                         device_admission=True)
    ids = sorted(orch.jobs)
    with pytest.raises(KeyError, match="unknown job id"):
        orch.release_workloads(ids[:2] + [10**6] + ids[2:])
    assert sorted(orch.jobs) == ids[2:]
    orch.release_workloads(ids[2:])
    assert np.array_equal(orch.fabric_ledger, start)
