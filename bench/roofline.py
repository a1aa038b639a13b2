"""Operations and bytes that the SOAR-Gather level fold needs.

For every depth level ``d`` that has internal nodes, the fold combines the
DP tables of the children (one level down) into the parents' tables:

* a parent at depth d has ``nl = d + 2`` barrier rows; its red chain reads
  each child's rows ``1 .. nl`` and its blue chain each child's row 1, so a
  child's table is read once over ``nl`` rows;
* only ``kd = min(k + 1, s + 1)`` budget columns matter, where ``s`` is the
  largest subtree size at level d (a subtree of s switches holds at most s
  blues);
* the parents' tables, ``nl x kd`` each, are written once; each parent
  also reads its load, send flag, availability and ``nl`` rates.

Each child costs one min-plus convolution per parent row (``nl`` red rows
and one blue row), ``kd (kd + 1) / 2`` additions and as many minimums.

The counts come from the tree's shape alone, not from how a kernel blocks
or pads it, so they measure the same work whatever implements the fold.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def fold_levels(parent: np.ndarray, k: int) -> list[dict]:
    """Per level with internal nodes: its depth, parents, children, nl,
    kd."""
    parent = np.asarray(parent)
    n = len(parent)
    dep = reference.depths(parent)
    size = np.ones(n, np.int64)
    for v in range(n - 1, 0, -1):
        size[parent[v]] += size[v]
    kids = np.bincount(parent[parent >= 0], minlength=n)
    out = []
    for d in range(int(dep.max()) + 1):
        at = dep == d
        internal = int((at & (kids > 0)).sum())
        if internal == 0:
            continue
        out.append({"depth": d, "parents": internal,
                    "children": int(kids[at].sum()), "nl": d + 2,
                    "kd": min(k + 1, int(size[at].max()) + 1)})
    return out


def fold_work(parent: np.ndarray, k: int, batch: int,
              itemsize: int = 4) -> dict:
    """``{"bytes": ..., "ops": ...}`` of one gather's level folds over
    ``batch`` instances of the tree, tables of ``itemsize`` bytes."""
    nbytes = ops = 0
    for lv in fold_levels(parent, k):
        nl, kd = lv["nl"], lv["kd"]
        nbytes += lv["children"] * nl * kd          # child tables read
        nbytes += lv["parents"] * nl * kd           # parent tables written
        nbytes += lv["parents"] * (3 + nl)          # load, send, avail, rho
        ops += lv["children"] * (nl + 1) * kd * (kd + 1)
    return {"bytes": nbytes * itemsize * batch, "ops": ops * batch}
