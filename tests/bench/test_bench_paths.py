"""The multipath admission cell (``fleet_paths``) on the CPU, on a k=4
fat-tree: its comparison passes the program, fails both controls, and
catches each planted fault."""
from __future__ import annotations

import json

import numpy as np
import pytest

import bench_testlib as L

FABRIC = {"k": 4, "pods": 4, "hosts_per_edge": 2, "rho": 1.0}
CONFIG = {"fabric": FABRIC, "k": 2, "capacity": 1}
TRAFFIC = {"driver": "fleet_paths", "tenants_per_wave": 12,
           "least_per_pod": 1, "max_waves_per_s": 2000, "check_waves": 40,
           "control_waves": 6}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = L.make_root(tmp_path_factory.mktemp("bench_paths"))
    bench = root / "bench"
    (bench / "configs" / "ecmp4_cap1.json").write_text(json.dumps(CONFIG))
    (bench / "traffic" / "paths12.json").write_text(json.dumps(TRAFFIC))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ecmp4_cap1", "source": "test",
                            "reduced": [], "why": "test",
                            "file": "bench/configs/ecmp4_cap1.json"})
    spec["workloads"].append({"name": "paths", "config": "ecmp4_cap1",
                              "traffic": "paths12", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("wave_placements_per_s", "wave_p95_ms"):
            m["workloads"].append("paths")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_paths_cell_is_correct(root):
    out = L.run(root, "paths", seed=2**33 + 9)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {
        "unanswered", "util_gap", "budget_excess", "fill_gap",
        "capacity_excess", "ledger_gap", "path_invalid"}
    assert {"setup_s", "wave_placements_per_s", "wave_p95_ms"} <= set(
        out["metrics"])


def test_paths_controls_are_not_correct(root):
    """Pods pinned to one tree leave a better tree unused (``fill_gap``);
    a ledger per tree overfills the shared switches
    (``capacity_excess``)."""
    import jax
    from bench.control import readings
    from bench.registry import Registry
    reg = Registry.load(root, root / "bench")
    out = readings(reg, "paths", [1], [3, 4, 5], 0.3, jax.devices()[:1])
    assert all(v == 0 for v in out["lower"].values()), out["lower"]
    for row in out["control"].values():
        assert row["fill_gap"] > 0 and row["capacity_excess"] > 0, row


def _swap_fleet(monkeypatch, make):
    """The program admitting every wave on ``make(orch)``'s fleet."""
    from repro.runtime import Orchestrator
    orig = Orchestrator.begin_workloads

    def broken(self, *a, **kw):
        if not getattr(self, "_planted", False):
            make(self)
            self._planted = True
        return orig(self, *a, **kw)
    monkeypatch.setattr(Orchestrator, "begin_workloads", broken)


def _pinned(monkeypatch):
    """Each pod's tenants held to the one tree of its single path."""
    import dataclasses

    def make(orch):
        h = FABRIC["k"] // 2
        orch.fleet = dataclasses.replace(orch.fleet, groups=tuple(
            (grp[p % h + h * ((p // h) % h)],)
            for p, grp in enumerate(orch.fleet.groups)))
    _swap_fleet(monkeypatch, make)


def _ledger_per_tree(monkeypatch):
    """Claims kept on a ledger per tree, blind to the shared switches."""
    from repro.collectives import Fleet

    def make(orch):
        f = orch.fleet
        orch.fleet = Fleet(topos=f.topos, core_rho=f.core_rho,
                           core_path=f.core_path, groups=f.groups)
        orch._set_ledgers()
    _swap_fleet(monkeypatch, make)


def _foreign_tree(monkeypatch):
    """A tenant's job filed on another pod's tree."""
    from repro.runtime import Orchestrator
    orig = Orchestrator.begin_workloads

    def broken(self, *a, **kw):
        progs = orig(self, *a, **kw)
        job = max(self.jobs.values(), key=lambda j: j.order)
        per_pod = len(self.fleet.groups[0])
        object.__setattr__(job, "tree",
                           (job.tree + per_pod) % self.fleet.n_trees)
        return progs
    monkeypatch.setattr(Orchestrator, "begin_workloads", broken)


def _ledger_unchanged(monkeypatch):
    from repro.runtime import Orchestrator
    orig = Orchestrator.begin_workloads

    def broken(self, *a, **kw):
        before = self.fabric_ledger.copy()
        progs = orig(self, *a, **kw)
        self.fabric_ledger[:] = before
        return progs
    monkeypatch.setattr(Orchestrator, "begin_workloads", broken)


FAULTS = {
    "pinned_path": (_pinned, {"fill_gap"}),
    "ledger_per_tree": (_ledger_per_tree, {"capacity_excess"}),
    "foreign_tree": (_foreign_tree, {"path_invalid"}),
    "ledger_unchanged": (_ledger_unchanged, {"ledger_gap"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_paths_fault_is_caught(root, monkeypatch, fault):
    plant, caught = FAULTS[fault]
    plant(monkeypatch)
    out = L.run(root, "paths", seed=13)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0
    bad = {n for n, c in out["checks"].items() if c["value"] > c["limit"]}
    assert caught <= bad, out["checks"]


def test_paths_driver_refuses_a_program_without_path_choice(root,
                                                            monkeypatch):
    """A program with no fat-tree constructor fails the cell's set-up
    with a clear error."""
    import repro.collectives as col
    monkeypatch.delattr(col, "fat_tree_fleet")
    with pytest.raises(RuntimeError, match="fat_tree_fleet"):
        L.run(root, "paths", seed=3)
