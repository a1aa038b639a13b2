"""Closed loop of admission waves through the orchestrator.

Configuration keys: ``tree`` (the parent, rate and load lists of every
tree of the fleet), ``fleet`` (``n_trees``, the shared ``core_rho`` links
and each tree's ``core_path`` through them), ``k`` and ``capacity``
(aggregator slots per switch). Traffic keys: ``tenants_per_wave`` (T),
``least_per_tree``, ``max_waves_per_s`` (sizes the table of waves),
``check_waves`` (waves compared after the window, drawn from the seed)
and ``control_waves``.

Each wave admits T tenants with one call of
``Orchestrator.begin_workloads(fleet=counts, congestion_aware=True,
device_admission=True)``: the penalty loop with in-loop admission, the
host re-measure and one ``ReduceProgram`` per tenant. ``counts`` gives
every tree ``least_per_tree`` tenants and spreads the rest at random from
the seed, so no two waves are alike while all share one packed layout. A
call returns with the programs installed. Outside the timed call, the
wave's masks and ledgers are kept and its jobs released, so every wave
meets the same empty fleet.

After the window a sample of the waves, drawn from the seed, is compared
with the plain reference: each tenant is answered; its utilization is
``phi`` of its mask; its mask is within the budget; its utilization is
the least that the switches left free by the tenants admitted before it
allow (``fill_gap``: the reference walks the wave in admission order,
solving each tenant on its tree's remaining capacity and taking its
claims); no switch holds more claims than its capacity; and every tree's
ledger plus its claims equals the capacity.
"""
from __future__ import annotations

import numpy as np

from bench import generator, reference


def fleet_from(config: dict):
    """The program's fleet, built from the configuration: ``n_trees``
    copies of its tree, each over the core links of its ``core_path``."""
    from repro.collectives import ClusterTopology, Fleet
    from repro.core.tree import Tree
    t, fl = config["tree"], config["fleet"]
    load = np.asarray(t["load"], np.int64)

    def topo():
        return ClusterTopology(
            tree=Tree(np.asarray(t["parent"]), np.asarray(t["rho"], float)),
            device_leaf=np.repeat(np.arange(len(load)), load), load=load)
    return Fleet(topos=tuple(topo() for _ in range(int(fl["n_trees"]))),
                 core_rho=np.asarray(fl["core_rho"], np.float64),
                 core_path=tuple(tuple(int(x) for x in p)
                                 for p in fl["core_path"]))


class Driver:
    def __init__(self, config, traffic, seed, seconds, devices):
        from repro.runtime import Orchestrator, OrchestratorConfig
        self.k, self.cap = int(config["k"]), int(config["capacity"])
        t = config["tree"]
        self.parent = np.asarray(t["parent"])
        self.rho = np.asarray(t["rho"], np.float64)
        self.load = np.asarray(t["load"], np.int64)
        self.fleet = fleet_from(config)
        self.orch = Orchestrator(self.fleet, OrchestratorConfig(
            k=self.k, capacity=self.cap))
        N = self.fleet.n_trees
        waves = int(np.ceil(seconds * float(traffic["max_waves_per_s"]))) + 1
        self.counts = generator.split_counts(
            generator.rng(seed, 0), int(traffic["tenants_per_wave"]), N,
            int(traffic["least_per_tree"]), waves)
        self.control_waves = int(traffic["control_waves"])
        self.check_waves = int(traffic["check_waves"])
        self.seed = seed
        self.waves: list[dict] = []
        self._progs = None
        self._ref: dict = {}

    def admit(self, counts):
        return self.orch.begin_workloads(
            fleet=[int(c) for c in counts], congestion_aware=True,
            device_admission=True)

    def warm(self):
        self._progs = self.admit(self.counts[-1])
        self.after(-1)
        self.waves.clear()

    def call(self, i: int) -> int:
        if i >= len(self.counts) - 1:
            raise RuntimeError(f"wave {i} is past the waves drawn; raise "
                               "max_waves_per_s")
        self._progs = self.admit(self.counts[i])
        return len(self._progs)

    def after(self, i: int):
        """Keep the wave's answers, then release its jobs."""
        self.waves.append(_record(self.orch, self.counts[i], self._progs))

    def counters(self) -> dict:
        return {}

    def release(self):
        self.orch = None

    def _control_waves(self) -> tuple[list[dict], list[dict]]:
        """The same waves through the program's two cut-down paths: the
        penalty loop with no admission (no ledger), and the loop with
        in-loop admission stopped after its first round."""
        from repro.collectives.schedule import plan_fleet
        from repro.runtime import Orchestrator, OrchestratorConfig
        free, one = [], []
        orch = Orchestrator(self.fleet, OrchestratorConfig(
            k=self.k, capacity=self.cap))
        for w in self.waves[: self.control_waves]:
            counts = [int(c) for c in w["counts"]]
            tree_of = [g for g, c in enumerate(counts) for _ in range(c)]
            planned, _ = plan_fleet(self.fleet, self.k, counts=counts)
            free.append({"counts": w["counts"], "tree": tree_of,
                         "blue": np.stack([b for b, _ in planned]),
                         "util": [p.utilization for _, p in planned],
                         "residual": None, "own": w["own"]})
            progs = orch.begin_workloads(
                fleet=counts, congestion_aware=True, device_admission=True,
                max_rounds=1)
            one.append(_record(orch, w["counts"], progs))
        return free, one

    def _fill_ref(self, own, tree, blue) -> list[float]:
        """Each tenant's least utilization on the switches that the
        tenants before it, in admission order, left with a free slot."""
        left = np.full((self.fleet.n_trees, len(self.parent)), self.cap,
                       np.int64)
        left[0] -= own
        out = []
        for g, b in zip(tree, blue):
            key = (left[g] > 0).tobytes()
            if key not in self._ref:
                self._ref[key] = reference.optimum(
                    self.parent, self.rho, self.load[None], self.k,
                    avail=left[g] > 0)[0]
            out.append(self._ref[key])
            left[g] -= b
        return out

    def _compare(self, waves) -> tuple[dict, int]:
        N = self.fleet.n_trees
        worst = dict(unanswered=0.0, util_gap=0.0, budget_excess=0.0,
                     fill_gap=0.0, capacity_excess=0.0, ledger_gap=0.0)
        failed = 0
        for w in waves:
            bad = dict.fromkeys(worst, 0.0)
            T = int(np.sum(w["counts"]))
            bad["unanswered"] = float(
                T - min(len(w["util"]), len(w["tree"])))
            claims = np.zeros((N, len(self.parent)), np.int64)
            claims[0] += w["own"]
            ref = self._fill_ref(w["own"], w["tree"], w["blue"])
            for g, blue, u, r in zip(w["tree"], w["blue"], w["util"], ref):
                claims[g] += blue
                bad["util_gap"] = max(bad["util_gap"], abs(
                    u - reference.phi(self.parent, self.rho, self.load,
                                      blue)))
                bad["budget_excess"] = max(bad["budget_excess"],
                                           float(blue.sum() - self.k))
                bad["fill_gap"] = max(bad["fill_gap"], abs(u - r))
            bad["capacity_excess"] = float((claims - self.cap).max())
            if w["residual"] is not None:
                bad["ledger_gap"] = float(np.abs(
                    np.stack(w["residual"]) + claims - self.cap).max())
            bad = {n: max(v, 0.0) for n, v in bad.items()}
            failed += any(v > 0 for v in bad.values())
            worst = {n: max(worst[n], bad[n]) for n in worst}
        return worst, failed

    def check(self, control=False) -> dict:
        """The numbers compared, each with its limit 0. With ``control``,
        each number as the cut-down path that can break it reads it:
        ``fill_gap`` from the loop stopped after one round, the others
        from the loop with no admission."""
        if control:
            free, one = self._control_waves()
            worst, failed = self._compare(free)
            worst_one, failed_one = self._compare(one)
            worst["fill_gap"] = worst_one["fill_gap"]
            names = ["unanswered", "util_gap", "budget_excess", "fill_gap",
                     "capacity_excess"]
            return {"checks": [(n, worst[n], 0.0) for n in names],
                    "failed": failed + failed_one}
        pick = generator.rng(self.seed, 1).choice(
            len(self.waves), min(self.check_waves, len(self.waves)),
            replace=False)
        worst, failed = self._compare([self.waves[j] for j in np.sort(pick)])
        return {"checks": [(n, v, 0.0) for n, v in worst.items()],
                "failed": failed}


def _record(orch, counts, progs) -> dict:
    """A wave's answers as the orchestrator holds them; its jobs are then
    released."""
    jobs = sorted(orch.jobs.values(), key=lambda j: j.order)
    wave = {"counts": counts,
            "tree": [j.tree for j in jobs],
            "blue": (np.stack([j.blue for j in jobs]) if jobs
                     else np.zeros((0, len(orch.blue)), bool)),
            "util": [p.utilization for p in progs],
            "residual": [r.copy() for r in orch._residuals],
            "own": orch.blue.copy()}
    orch.release_workloads([j.job_id for j in jobs])
    return wave
