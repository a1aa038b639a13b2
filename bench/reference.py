"""Plain reference for SOAR placements, independent of the program.

A placement problem is a rooted tree of switches (``parent[v]``, -1 at the
root), the reciprocal rate ``rho[v]`` of each switch's up-link, the load
``load[v]`` (messages that v's own servers send) and a budget ``k`` of
aggregating (blue) switches. Its cost is the utilization

    phi = sum over switches v of msgs(v) * rho[v]

where a red switch forwards every message it receives plus its own load,
and a blue switch sends one message if its subtree holds any load and
nothing otherwise (SOAR, arXiv:2110.14224, Sec. 2 and Algorithm 1).

``optimum`` is the textbook dynamic program of the paper's Sec. 4
(SOAR-Gather): ``X_v(l, i)``, the least cost of v's subtree plus its
outgoing messages charged ``l`` hops up to the closest blue ancestor,
using at most ``i`` blue switches. Children are combined by the min-plus
convolution over the budget split, with the exact identity
``[0, inf, ..., inf]`` for a missing child. It runs level by level, all
nodes of one depth and all instances at once, in float64, where every cost
of these trees is exact. ``round_to`` computes the same recursion with
every sum rounded to a lower precision: the control that the benchmark's
comparison has to reject.
"""
from __future__ import annotations

import numpy as np


def bt_tree(n_total: int) -> tuple[np.ndarray, np.ndarray]:
    """Complete binary tree BT(n_total): n_total - 1 switches, node 0 the
    root, ``parent[v] = (v - 1) // 2``, every rate 1 (the paper's
    "constant" scheme)."""
    n = n_total - 1
    parent = (np.arange(n) - 1) // 2
    parent[0] = -1
    return parent.astype(np.int64), np.ones(n)


def depths(parent: np.ndarray) -> np.ndarray:
    d = np.zeros(len(parent), np.int64)
    for v in range(len(parent)):            # parents precede children
        if parent[v] >= 0:
            if parent[v] >= v:
                raise ValueError("parents must precede their children")
            d[v] = d[parent[v]] + 1
    return d


def messages(parent, load, blue) -> np.ndarray:
    """Messages on every switch's up-link (paper Algorithm 1)."""
    n = len(parent)
    sub = np.asarray(load, np.int64).copy()
    for v in range(n - 1, 0, -1):           # children before parents
        if parent[v] >= 0:
            sub[parent[v]] += sub[v]
    recv = np.zeros(n, np.int64)
    out = np.zeros(n, np.int64)
    for v in range(n - 1, -1, -1):
        out[v] = (1 if sub[v] > 0 else 0) if blue[v] else recv[v] + load[v]
        if parent[v] >= 0:
            recv[parent[v]] += out[v]
    return out


def phi(parent, rho, load, blue) -> float:
    """Utilization of a placement, in float64."""
    return float((messages(parent, load, np.asarray(blue, bool))
                  * np.asarray(rho, np.float64)).sum())


def _minplus(a, b, rnd):
    """C[..., i] = min_j a[..., i - j] + b[..., j], at most-k width."""
    K = a.shape[-1]
    c = np.full(np.broadcast_shapes(a.shape, b.shape), np.inf)
    for j in range(K):
        np.minimum(c[..., j:], rnd(a[..., : K - j] + b[..., j : j + 1]),
                   out=c[..., j:])
    return c


def optimum(parent, rho, loads, k: int, avail=None, round_to=None
            ) -> np.ndarray:
    """Least utilization with at most ``k`` blue switches, per instance.

    ``loads``: (S, n) integer loads of S instances on one tree; ``avail``:
    (S, n) or (n,) switches allowed to aggregate (all by default).
    ``round_to``: a numpy dtype such as ``ml_dtypes.bfloat16``; every sum is
    rounded to it (the control). Returns (S,) costs.
    """
    parent = np.asarray(parent, np.int64)
    rho = np.asarray(rho, np.float64)
    loads = np.atleast_2d(np.asarray(loads, np.float64))
    S, n = loads.shape
    av = np.ones((S, n), bool) if avail is None else np.broadcast_to(
        np.asarray(avail, bool), (S, n))
    if round_to is None:
        def rnd(x):
            return x
    else:
        def rnd(x):
            return x.astype(round_to).astype(np.float64)
    K = k + 1
    dep = depths(parent)
    h = int(dep.max())
    # up[v, l]: rho summed over the l hops above v (l = 0 .. depth + 1)
    up = np.full((n, h + 2), np.inf)
    up[:, 0] = 0.0
    cur, acc = np.arange(n), np.zeros(n)
    for ell in range(1, h + 2):
        live = cur >= 0
        acc = acc + np.where(live, rho[np.maximum(cur, 0)], 0.0)
        up[live, ell] = acc[live]
        cur = np.where(live, parent[np.maximum(cur, 0)], -1)
    sub = loads.copy()
    for v in range(n - 1, 0, -1):
        sub[:, parent[v]] += sub[:, v]
    send = (sub > 0).astype(np.float64)
    kids = [[] for _ in range(n)]
    for v in range(1, n):
        kids[parent[v]].append(v)
    X: dict[int, np.ndarray] = {}           # node -> (S, depth + 2, K)
    ident = np.full(K, np.inf)
    ident[0] = 0.0
    size = np.ones(n, np.int64)
    for v in range(n - 1, 0, -1):
        size[parent[v]] += size[v]
    for d in range(h, -1, -1):
        nodes = np.nonzero(dep == d)[0]
        nl = d + 2
        # a subtree of s switches holds at most s blues, so its table is
        # flat from column s on: fold the first kd columns, pad the rest
        kd = min(K, int(size[nodes].max()) + 1)
        rl = up[nodes, :nl][None, :, :, None]             # (1, W, nl, 1)
        own = rnd(loads[:, nodes, None, None] * rl)       # (S, W, nl, 1)
        out_send = rnd(send[:, nodes, None, None] * rl)
        m = max(len(kids[v]) for v in nodes)
        red = np.broadcast_to(ident[:kd], (S, len(nodes), nl, kd)).copy()
        blu = np.broadcast_to(ident[:kd], (S, len(nodes), kd)).copy()
        for j in range(m):
            cr = np.broadcast_to(ident[:kd], red.shape).copy()
            cb = np.broadcast_to(ident[:kd], blu.shape).copy()
            for w, v in enumerate(nodes):
                if j < len(kids[v]):
                    c = X[kids[v][j]]
                    cr[:, w] = c[:, 1 : nl + 1, :kd]
                    cb[:, w] = c[:, 1, :kd]
            red = _minplus(red, cr, rnd)
            blu = _minplus(blu, cb, rnd)
        red = rnd(red + own)
        blue = np.full_like(red, np.inf)
        blue[..., 1:] = rnd(blu[:, :, None, :-1] + out_send)
        blue = np.where(av[:, nodes, None, None], blue, np.inf)
        x = np.minimum.accumulate(np.minimum(red, blue), axis=-1)
        x = np.concatenate(
            [x, np.repeat(x[..., -1:], K - kd, axis=-1)], axis=-1)
        for w, v in enumerate(nodes):
            X[int(v)] = x[:, w]
        for v in nodes:                     # children are no longer read
            for c in kids[v]:
                X.pop(c, None)
    root = int(np.nonzero(parent < 0)[0][0])
    return X[root][:, 1, k].copy()
