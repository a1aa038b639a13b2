"""The benchmark's comparison on the CPU, at tiny sizes.

Each cell runs through ``bench.run.run_cell`` (everything a run does but
the check for a chip) and must come out correct; its control must come out
wrong; and with the timed path broken underneath, in each way the cell can
be broken, ``correct`` must come out false.
"""
from __future__ import annotations

import pytest

import bench_testlib as L


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return L.make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("cell", ["batch", "admit"])
def test_cell_is_correct(root, cell):
    out = L.run(root, cell, seed=2**33 + 5)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]
    assert all(v["value"] > 0 for v in out["metrics"].values())


@pytest.mark.parametrize("cell", ["batch", "admit"])
def test_control_is_not_correct(root, cell):
    import jax
    from bench.control import readings
    from bench.registry import Registry
    reg = Registry.load(root, root / "bench")
    out = readings(reg, cell, [1], [3, 4, 5], 0.3, jax.devices()[:1])
    assert all(v == 0 for v in out["lower"].values()), out["lower"]
    assert any(v > 0 for v in out["upper"].values()), out["upper"]
    for row in out["control"].values():
        assert any(row[n] > 0 for n in out["upper"]), row


def _alter_batch_answers(monkeypatch):
    import repro.engine as eng
    orig = eng.solve_batch

    def broken(*a, **kw):
        res = orig(*a, **kw)
        res.costs = res.costs + 1.0
        return res
    monkeypatch.setattr(eng, "solve_batch", broken)


def _half_batch(monkeypatch):
    import repro.engine as eng
    orig = eng.solve_batch

    def broken(trees, loads, k, **kw):
        h = len(trees) // 2
        return orig(trees[:h], loads[:h], k, **kw)
    monkeypatch.setattr(eng, "solve_batch", broken)


def _wave(monkeypatch, after):
    from repro.runtime import Orchestrator
    orig = Orchestrator.begin_workloads

    def broken(self, *a, **kw):
        before = [r.copy() for r in self._residuals]
        progs = orig(self, *a, **kw)
        return after(self, progs, before)
    monkeypatch.setattr(Orchestrator, "begin_workloads", broken)


def _alter_wave_answer(monkeypatch):
    def after(self, progs, before):
        job = max(self.jobs.values(), key=lambda j: j.order)
        job.blue[-1] = not job.blue[-1]
        return progs
    _wave(monkeypatch, after)


def _half_wave(monkeypatch):
    def after(self, progs, before):
        return progs[: len(progs) // 2]
    _wave(monkeypatch, after)


def _ledger_unchanged(monkeypatch):
    def after(self, progs, before):
        for r, b in zip(self._residuals, before):
            r[:] = b
        return progs
    _wave(monkeypatch, after)


def _all_red_wave(monkeypatch):
    """Every tenant answered with an all-red mask, its utilization and the
    ledger kept consistent with it: only the placement's quality is
    wrong."""
    from bench import reference

    def after(self, progs, before):
        for job, prog in zip(sorted(self.jobs.values(),
                                    key=lambda j: j.order), progs):
            topo = self.fleet.topos[job.tree]
            self._residuals[job.tree][job.blue] += 1
            job.blue[:] = False
            prog.utilization = reference.phi(
                topo.tree.parent, topo.tree.rho, topo.load, job.blue)
        return progs
    _wave(monkeypatch, after)


def _loop_one_round(monkeypatch):
    from repro.runtime import Orchestrator
    orig = Orchestrator.begin_workloads

    def broken(self, *a, **kw):
        return orig(self, *a, **dict(kw, max_rounds=1))
    monkeypatch.setattr(Orchestrator, "begin_workloads", broken)


# each way to break the timed path, and the numbers that must catch it
FAULTS = {
    ("batch", "answer_altered"): (_alter_batch_answers, set()),
    ("batch", "half_the_batch"): (_half_batch, set()),
    ("admit", "answer_altered"): (_alter_wave_answer, set()),
    ("admit", "half_the_batch"): (_half_wave, set()),
    ("admit", "state_unchanged"): (_ledger_unchanged, set()),
    # placements that keep every guarantee but are worse than the
    # admission order allows
    ("admit", "all_red"): (_all_red_wave, {"fill_gap"}),
    ("admit", "loop_one_round"): (_loop_one_round, {"fill_gap"}),
}


@pytest.mark.parametrize("cell,fault", sorted(FAULTS))
def test_broken_path_is_not_correct(root, monkeypatch, cell, fault):
    plant, caught = FAULTS[cell, fault]
    plant(monkeypatch)
    out = L.run(root, cell, seed=11)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0
    bad = {n for n, c in out["checks"].items() if c["value"] > c["limit"]}
    assert caught <= bad, out["checks"]
