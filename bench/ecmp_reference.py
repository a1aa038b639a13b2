"""Plain reference for admission on a multipath fat-tree, independent of
the program.

The configuration's ``fabric`` is a k-ary fat-tree (Al-Fares et al.,
SIGCOMM 2008, Sec. 3) seen from one destination host: ``pods`` worker
pods of k/2 edge switches (``hosts_per_edge`` hosts each, one message a
host) and k/2 aggregation switches, and (k/2)^2 core switches in k/2
groups, group a reaching aggregation switch a of every pod. A pod's
aggregation tree is one path: its edge switches, aggregation switch a,
then core switch c of group a. Tree ``p * (k/2)^2 + i`` is pod p's path
through aggregation switch ``i mod k/2`` and core switch ``i div k/2``;
every tree of pod p is a candidate of p's tenants.

``Fabric`` names every switch by its role — ("edge", p, e), ("agg", p, a),
("core", a, c) — numbered here, and gives each tree its switches in node
order ``[core, agg, edge 0, ..]``. ``Fabric.least_phi`` is a tenant's
reference value: the least utilization (``reference.phi``, by
``reference.optimum``'s float64 DP) that any candidate tree of its pod
allows with the free slots it is given, one per switch.
"""
from __future__ import annotations

import numpy as np

from bench import reference


class Fabric:
    def __init__(self, fabric: dict, k: int):
        self.arity = int(fabric["k"])
        h = self.h = self.arity // 2
        self.pods = int(fabric.get("pods", self.arity))
        hosts = int(fabric.get("hosts_per_edge", h))
        rho = float(fabric["rho"])
        self.k = int(k)
        self.parent = np.asarray([-1, 0] + [1] * h, np.int64)
        self.rho = np.full(h + 2, rho)
        self.load = np.asarray([0, 0] + [hosts] * h, np.int64)
        self.per_pod = h * h
        names: dict = {}

        def sid(name):
            return names.setdefault(name, len(names))
        trees = []
        for p in range(self.pods):
            for i in range(self.per_pod):
                a, c = i % h, i // h
                trees.append([sid(("core", a, c)), sid(("agg", p, a))]
                             + [sid(("edge", p, e)) for e in range(h)])
        self.switches = np.asarray(trees, np.int64)   # (trees, n)
        self.n_switches = len(names)
        self._phi: dict[bytes, float] = {}

    def pod_of(self, tree: int) -> int:
        """The pod whose path tree ``tree`` is; -1 if there is none."""
        return tree // self.per_pod if 0 <= tree < len(self.switches) \
            else -1

    def least_phi(self, pod: int, left: np.ndarray) -> float:
        """Least utilization over pod ``pod``'s candidate trees, each
        solved with the switches that ``left`` (free slots per switch)
        shows free."""
        cand = self.switches[pod * self.per_pod: (pod + 1) * self.per_pod]
        free = left[cand] > 0
        keys = [row.tobytes() for row in free]
        miss = [j for j, key in enumerate(keys) if key not in self._phi]
        if miss:
            # candidate trees share one shape, so a tree's value depends
            # on which of its switches are free, nothing else
            got = reference.optimum(self.parent, self.rho,
                                    np.repeat(self.load[None], len(miss), 0),
                                    self.k, avail=free[miss])
            for j, v in zip(miss, got):
                self._phi[keys[j]] = float(v)
        return min(self._phi[key] for key in keys)

    def phi(self, blue: np.ndarray) -> float:
        return reference.phi(self.parent, self.rho, self.load, blue)
