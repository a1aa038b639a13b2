"""Closed loop of batch placement solves (``repro.engine.solve_batch``).

Configuration keys: ``tree`` (``{"kind": "bt", "n_total": N}``, the SOAR
paper's complete binary tree with constant rates) and ``k``. Traffic keys:
``tenants_per_call`` (B), ``tenant_pool`` (tenants drawn before the
window), ``max_calls_per_s`` (sizes the table of batches), ``load`` (the
generator's load spec) and ``check_samples`` (answers compared after the
window).

Every call solves B tenants whose leaf loads come from a pool drawn from the
seed; each call draws its own B pool members, so no two batches of a run
are alike. A call returns once the masks and costs are numpy arrays on the
host. After the window, ``check_samples`` (call, tenant) answers drawn from
the seed, the first and the last call among them, are compared with the
plain reference: the cost equals the least utilization, the mask
re-measures to the cost, and the mask is within the budget.
"""
from __future__ import annotations

import numpy as np

from bench import generator, reference


def build_tree(spec: dict):
    if spec["kind"] != "bt":
        raise ValueError(f"unknown tree kind {spec['kind']!r}")
    return reference.bt_tree(int(spec["n_total"]))


class Driver:
    def __init__(self, config, traffic, seed, seconds, devices):
        from repro.core import bt
        self.k = int(config["k"])
        self.B = int(traffic["tenants_per_call"])
        self.parent, self.rho = build_tree(config["tree"])
        self.tree = bt(int(config["tree"]["n_total"]), "constant")
        if not (np.array_equal(self.tree.parent, self.parent)
                and np.array_equal(self.tree.rho, self.rho)):
            raise RuntimeError("the program's tree differs from the "
                               "configuration's")
        n = len(self.parent)
        is_parent = np.zeros(n, bool)
        is_parent[self.parent[self.parent >= 0]] = True
        self.leaves = np.nonzero(~is_parent)[0]
        g = generator.rng(seed, 0)
        self.pool = generator.draw_loads(
            g, (int(traffic["tenant_pool"]), len(self.leaves)),
            traffic["load"])
        calls = int(np.ceil(seconds * float(traffic["max_calls_per_s"]))) + 1
        self.pick = np.stack([g.choice(len(self.pool), self.B, replace=False)
                              for _ in range(calls)])
        self.samples = int(traffic["check_samples"])
        self.seed = seed
        self.answers: list = []
        self.unanswered = 0

    def loads(self, i: int) -> np.ndarray:
        full = np.zeros((self.B, len(self.parent)), np.int64)
        full[:, self.leaves] = self.pool[self.pick[i % len(self.pick)]]
        return full

    def solve(self, loads):
        from repro.engine import solve_batch
        return solve_batch([self.tree] * self.B, list(loads), self.k)

    def warm(self):
        self.solve(self.loads(len(self.pick) - 1))

    def call(self, i: int) -> int:
        if i >= len(self.pick):
            raise RuntimeError(f"call {i} is past the {len(self.pick)} "
                               "batches drawn; raise max_calls_per_s")
        res = self.solve(self.loads(i))
        blue = np.asarray(res.blue)
        costs = np.asarray(res.costs, np.float64)
        self.answers.append((blue, costs))
        ok = np.isfinite(costs)
        self.unanswered += self.B - int(ok[: self.B].sum())
        return int(ok[: self.B].sum())

    def after(self, i: int):
        pass

    def counters(self) -> dict:
        return {}

    def release(self):
        """The answers are host arrays already; the reference runs on the
        host."""

    def sample(self) -> list[tuple[int, int]]:
        """(call, tenant) pairs compared after the window."""
        g = generator.rng(self.seed, 1)
        n = len(self.answers)
        calls = np.r_[0, n - 1, g.integers(0, n, self.samples - 2)]
        return [(int(c), int(g.integers(0, self.B))) for c in calls]

    def check(self, control=False) -> dict:
        """The numbers compared; with ``control`` the program's costs are
        replaced by the reference's computed in bfloat16, the precision
        below the configuration's float32 tables."""
        picks = self.sample()
        loads = np.stack([self.loads(c)[b] for c, b in picks])
        want = reference.optimum(self.parent, self.rho, loads, self.k)
        cost_gap = phi_gap = excess = 0.0
        wrong = set()
        if control:
            import ml_dtypes
            got = reference.optimum(self.parent, self.rho, loads, self.k,
                                    round_to=ml_dtypes.bfloat16)
            cost_gap = float(np.abs(got - want).max())
            return {"checks": [("cost_gap", cost_gap, 0.0)],
                    "failed": int((got != want).sum())}
        for (c, b), w, ld in zip(picks, want, loads):
            blue, costs = self.answers[c]
            if b >= len(costs):
                wrong.add(c)
                continue
            mask = np.asarray(blue[b, : len(self.parent)], bool)
            gaps = (abs(costs[b] - w),
                    abs(reference.phi(self.parent, self.rho, ld, mask)
                        - costs[b]),
                    max(int(mask.sum()) - self.k, 0))
            cost_gap, phi_gap, excess = (max(a, float(x)) for a, x in zip(
                (cost_gap, phi_gap, excess), gaps))
            if any(x != 0 for x in gaps) or not np.isfinite(costs[b]):
                wrong.add(c)
        return {"checks": [("cost_gap", cost_gap, 0.0),
                           ("phi_gap", phi_gap, 0.0),
                           ("budget_excess", excess, 0.0),
                           ("unanswered", float(self.unanswered), 0.0)],
                "failed": len(wrong)}
