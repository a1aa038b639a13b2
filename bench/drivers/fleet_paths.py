"""Closed loop of admission waves on a multipath fat-tree: each tenant's
aggregation tree is chosen among its pod's paths.

Configuration keys: ``fabric`` (the fat-tree: ``k``, ``pods``,
``hosts_per_edge``, the link ``rho``), ``k`` and ``capacity`` (aggregator
slots per physical switch). Traffic keys: ``tenants_per_wave`` (T),
``least_per_pod``, ``max_waves_per_s`` (sizes the table of waves),
``check_waves`` (waves compared after the window, drawn from the seed)
and ``control_waves``.

The program's fleet is ``repro.collectives.fat_tree_fleet``: every pod's
(k/2)^2 trees form its candidate group, and the switches they share are
claimed on one physical ledger. Each wave admits T tenants with one call
of ``Orchestrator.begin_workloads(fleet=counts, congestion_aware=True,
device_admission=True)``, ``counts`` per pod: at least ``least_per_pod``
each and the rest spread at random from the seed, the split law of
``fleet_admission``. A call returns with the programs installed. Outside
the timed call, the wave's trees, masks and ledger are kept and its jobs
released, so every wave meets the same empty fabric.

After the window a sample of the waves, drawn from the seed, is compared
with ``bench/ecmp_reference.py`` (numbers with limit 0): each tenant is
answered (``unanswered``); its tree is one of its pod's (``path_invalid``);
its utilization is ``phi`` of its mask (``util_gap``) and within the
budget (``budget_excess``); its utilization is the least that any tree of
its pod allows with the slots that the tenants admitted before it left
(``fill_gap``: the reference walks the wave in admission order, solving
each tenant on every candidate and taking its claims on the physical
switches of the tree it was given); no physical switch holds more claims
than its capacity (``capacity_excess``); and the program's ledger plus the
claims equals the capacity on every physical switch (``ledger_gap``).
"""
from __future__ import annotations

import numpy as np

from bench import ecmp_reference, generator


def fabric_fleet(config: dict, paths: str = "all"):
    """The program's fleet for the configuration's fat-tree."""
    try:
        from repro.collectives import fat_tree_fleet
    except ImportError as e:
        raise RuntimeError(
            "this program has no repro.collectives.fat_tree_fleet: it "
            "cannot build a multipath fabric or choose a tenant's tree, so "
            "the cell cannot run") from e
    f = config["fabric"]
    return fat_tree_fleet(int(f["k"]), pods=int(f["pods"]),
                          hosts_per_edge=int(f["hosts_per_edge"]),
                          rho=float(f["rho"]), paths=paths)


class Driver:
    def __init__(self, config, traffic, seed, seconds, devices):
        from repro.runtime import Orchestrator, OrchestratorConfig
        self.k, self.cap = int(config["k"]), int(config["capacity"])
        self.config = config
        self.fleet = fabric_fleet(config)
        self.orch = Orchestrator(self.fleet, OrchestratorConfig(
            k=self.k, capacity=self.cap))
        self.ref = ecmp_reference.Fabric(config["fabric"], self.k)
        if self.fleet.n_trees != len(self.ref.switches):
            raise RuntimeError(f"the program's fabric has "
                               f"{self.fleet.n_trees} trees, the "
                               f"reference's {len(self.ref.switches)}")
        self.ledger_map = _join(self.ref.switches,
                                np.stack(self.fleet.switch_id))
        G = self.fleet.n_groups
        waves = int(np.ceil(seconds * float(traffic["max_waves_per_s"]))) + 1
        self.counts = generator.split_counts(
            generator.rng(seed, 0), int(traffic["tenants_per_wave"]), G,
            int(traffic["least_per_pod"]), waves)
        self.control_waves = int(traffic["control_waves"])
        self.check_waves = int(traffic["check_waves"])
        self.seed = seed
        self.waves: list[dict] = []
        self._progs = None

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
        """The same waves through the program with each pod pinned to one
        tree (its single path of ``fattree16_cap2``), and with a ledger per
        tree in place of the physical one (the fabric's trees given no
        shared ids)."""
        from repro.collectives import Fleet
        from repro.runtime import Orchestrator, OrchestratorConfig
        pinned = Orchestrator(fabric_fleet(self.config, "pinned"),
                              OrchestratorConfig(k=self.k, capacity=self.cap))
        f = self.fleet
        per_tree = Orchestrator(
            Fleet(topos=f.topos, core_rho=f.core_rho, core_path=f.core_path,
                  groups=f.groups),
            OrchestratorConfig(k=self.k, capacity=self.cap))
        h, per_pod = self.ref.h, self.ref.per_pod
        pin, split = [], []
        for w in self.waves[: self.control_waves]:
            counts = [int(c) for c in w["counts"]]
            for orch, out in ((pinned, pin), (per_tree, split)):
                progs = orch.begin_workloads(
                    fleet=counts, congestion_aware=True,
                    device_admission=True)
                out.append(_record(orch, w["counts"], progs))
                out[-1]["residual"] = None
        for w in pin:
            # pinned tree p is pod p's path through aggregation switch
            # p mod h and core switch (p div h) mod h
            w["tree"] = [p * per_pod + p % h + h * ((p // h) % h)
                         for p in w["tree"]]
        return pin, split

    def _compare(self, waves) -> tuple[dict, int]:
        names = ("unanswered", "util_gap", "budget_excess", "fill_gap",
                 "capacity_excess", "ledger_gap", "path_invalid")
        worst = dict.fromkeys(names, 0.0)
        failed = 0
        ref = self.ref
        for w in waves:
            bad = dict.fromkeys(names, 0.0)
            pods = [p for p, c in enumerate(w["counts"]) for _ in range(c)]
            bad["unanswered"] = float(
                len(pods) - min(len(w["util"]), len(w["tree"])))
            left = np.full(ref.n_switches, self.cap, np.int64)
            left[ref.switches[0][w["own"]]] -= 1
            for p, g, blue, u in zip(pods, w["tree"], w["blue"], w["util"]):
                if ref.pod_of(g) != p:
                    bad["path_invalid"] += 1
                    continue
                bad["util_gap"] = max(bad["util_gap"], abs(u - ref.phi(blue)))
                bad["budget_excess"] = max(bad["budget_excess"],
                                           float(blue.sum() - self.k))
                bad["fill_gap"] = max(bad["fill_gap"],
                                      abs(u - ref.least_phi(p, left)))
                left[ref.switches[g][blue]] -= 1
            bad["capacity_excess"] = float(-left.min())
            if w["residual"] is not None:
                bad["ledger_gap"] = _ledger_gap(
                    w["residual"], left, self.ledger_map, self.cap)
            bad = {n: max(v, 0.0) for n, v in bad.items()}
            failed += any(v > 0 for v in bad.values())
            worst = {n: max(worst[n], bad[n]) for n in worst}
        return worst, failed

    def check(self, control=False) -> dict:
        """The numbers compared, each with its limit 0. With ``control``,
        each number as the cut-down program that can break it reads it:
        ``fill_gap`` from the pods pinned to one tree, the others from the
        ledger per tree."""
        if control:
            pin, split = self._control_waves()
            worst, failed = self._compare(split)
            worst_pin, failed_pin = self._compare(pin)
            worst["fill_gap"] = worst_pin["fill_gap"]
            names = ["unanswered", "util_gap", "budget_excess", "fill_gap",
                     "capacity_excess", "path_invalid"]
            return {"checks": [(n, worst[n], 0.0) for n in names],
                    "failed": failed + failed_pin}
        pick = generator.rng(self.seed, 1).choice(
            len(self.waves), min(self.check_waves, len(self.waves)),
            replace=False)
        worst, failed = self._compare([self.waves[j] for j in np.sort(pick)])
        return {"checks": [(n, v, 0.0) for n, v in worst.items()],
                "failed": failed}


def _join(ref_ids: np.ndarray, prog_ids: np.ndarray) -> np.ndarray | None:
    """The program's switch id of every reference switch, joined through
    the trees (node v of tree g is one switch in both); None unless the
    two namings are one-to-one."""
    pairs = np.unique(np.stack([ref_ids.ravel(), prog_ids.ravel()], 1),
                      axis=0)
    if (len(np.unique(pairs[:, 0])) != len(pairs)
            or len(np.unique(pairs[:, 1])) != len(pairs)
            or len(pairs) != ref_ids.max() + 1):
        return None
    out = np.empty(len(pairs), np.int64)
    out[pairs[:, 0]] = pairs[:, 1]
    return out


def _ledger_gap(residual, left, ledger_map, cap) -> float:
    """Largest difference, over physical switches, between the program's
    ledger and the capacity less the claims the reference counted."""
    residual = np.asarray(residual, np.int64)
    if ledger_map is None or residual.shape != (left.size,):
        return float(cap + 1)
    return float(np.abs(residual[ledger_map] - left).max())


def _record(orch, counts, progs) -> dict:
    """A wave's answers as the orchestrator holds them; its jobs are then
    released."""
    jobs = sorted(orch.jobs.values(), key=lambda j: j.order)
    wave = {"counts": counts,
            "tree": [j.tree for j in jobs],
            "blue": [j.blue.copy() for j in jobs],
            "util": [p.utilization for p in progs],
            "residual": orch.fabric_ledger.copy(),
            "own": orch.blue.copy()}
    orch.release_workloads([j.job_id for j in jobs])
    return wave
