"""The cached load-independent layout of ``build_forest``: a hit packs the
same Forest as a fresh build, bit for bit, and the cache is bounded, dies
with its trees and hands out read-only arrays."""
from __future__ import annotations

import dataclasses
import gc

import numpy as np
import pytest

from repro import telemetry
from repro.core import bt, random_tree, rpa, sample_load
from repro.core import forest as forest_mod
from repro.core.forest import Forest, build_forest
from repro.core.reduce import phi
from repro.core.soar import soar
from repro.core.tree import DEST, Tree
from repro.engine import solve_batch

PER_CALL = {"load", "avail", "send", "pk_load", "pk_send", "pk_avail"}


def _ragged_trees():
    """Different sizes and heights, one tree twice, a single node."""
    t8 = bt(8, "constant")
    return [t8, bt(32, "constant"), rpa(20, seed=1), t8,
            random_tree(13, seed=2), Tree(np.array([DEST]), np.array([1.0]))]


def _draw(rng, trees, avail):
    loads = [rng.integers(0, 4, t.n) * (rng.random(t.n) < 0.5)
             for t in trees]
    if avail == "none":
        return loads, None
    masks = [rng.random(t.n) < 0.6 for t in trees]
    if avail == "mixed":
        masks[::2] = [None] * len(masks[::2])
    return loads, masks


def _same(a, b) -> bool:
    if isinstance(a, tuple):
        return len(a) == len(b) and all(_same(x, y) for x, y in zip(a, b))
    if isinstance(a, np.ndarray):
        return (a.dtype == b.dtype and a.shape == b.shape
                and np.array_equal(a, b))
    return a == b


def _reference_packed(f: Forest, trees, loads, avail) -> dict:
    """``send`` by the node-space ``np.add.at`` sweep over each tree's own
    parents, and the slot-packed loads, sends and availability gathered
    through ``slot_node``."""
    B, n_max = f.mask.shape
    load = np.zeros((B, n_max), np.int64)
    av = np.zeros((B, n_max), bool)
    sub = np.zeros((B, n_max), np.int64)
    for b, t in enumerate(trees):
        load[b, : t.n] = loads[b]
        av[b, : t.n] = (True if avail is None or avail[b] is None
                        else avail[b])
        s = load[b, : t.n].copy()
        for d in range(t.height, 0, -1):
            v = np.nonzero(t.depth == d)[0]
            np.add.at(s, t.parent[v], s[v])
        sub[b, : t.n] = s
    send = (sub > 0).astype(np.int64)
    real = f.slot_node >= 0
    src = np.where(real, f.slot_node, 0)
    bix = np.arange(B)[:, None]
    return {"send": send,
            "pk_load": np.where(real, load[bix, src], 0),
            "pk_send": np.where(real, send[bix, src], 0),
            "pk_avail": np.where(real, av[bix, src], False)}


@pytest.mark.parametrize("avail", ["none", "mask", "mixed"])
@pytest.mark.parametrize("bucket", [True, False])
def test_hit_packs_the_forest_a_fresh_build_packs(bucket, avail):
    rng = np.random.default_rng(7)
    trees = _ragged_trees()
    build_forest(trees, *_draw(rng, trees, avail), bucket=bucket)
    for _ in range(3):                       # several loads, one layout
        loads, masks = _draw(rng, trees, avail)
        h0 = telemetry.get("engine.pack_hits")
        hit = build_forest(trees, loads, masks, bucket=bucket)
        assert telemetry.get("engine.pack_hits") == h0 + 1
        forest_mod._LAYOUT_CACHE.clear()
        fresh = build_forest(trees, loads, masks, bucket=bucket)
        assert telemetry.get("engine.pack_hits") == h0 + 1
        for fld in dataclasses.fields(Forest):
            assert _same(getattr(hit, fld.name), getattr(fresh, fld.name)), \
                fld.name
        want = _reference_packed(fresh, trees, loads, masks)
        for name, ref in want.items():
            assert _same(getattr(hit, name), ref), name


def test_pack_hits_count_reuse_of_one_tree_tuple_only():
    t = bt(16, "constant")
    u = bt(16, "constant")                   # same shape, another object
    load = sample_load(t, "power-law", seed=0)
    build_forest([t, t], [load, load])
    h0 = telemetry.get("engine.pack_hits")
    build_forest([t, t], [load, load])
    assert telemetry.get("engine.pack_hits") == h0 + 1
    build_forest([t, u], [load, load])
    build_forest([t, t], [load, load], bucket=False)
    assert telemetry.get("engine.pack_hits") == h0 + 1
    assert "engine.pack_hits" in telemetry.counters()


def test_layout_entry_dies_with_its_tree():
    t = bt(16, "constant")
    keep = bt(16, "constant")
    f = build_forest([keep, t], [np.zeros(15, np.int64)] * 2)
    key = ((id(keep), id(t)), True)
    assert key in forest_mod._LAYOUT_CACHE
    del t, f
    gc.collect()
    assert key not in forest_mod._LAYOUT_CACHE


def test_layout_cache_keeps_its_bound_and_the_recently_used():
    size = forest_mod._LAYOUT_CACHE_SIZE
    trees = [bt(8, "constant") for _ in range(size + 3)]
    load = np.ones(7, np.int64)
    build_forest([trees[0]], [load])
    for t in trees[1:]:
        build_forest([t], [load])
        h0 = telemetry.get("engine.pack_hits")
        build_forest([trees[0]], [load])     # in use, so never evicted
        assert telemetry.get("engine.pack_hits") == h0 + 1
        assert len(forest_mod._LAYOUT_CACHE) <= size
    assert ((id(trees[1]),), True) not in forest_mod._LAYOUT_CACHE
    assert ((id(trees[-1]),), True) in forest_mod._LAYOUT_CACHE


def test_cached_structural_arrays_are_read_only():
    trees = _ragged_trees()
    loads = [np.ones(t.n, np.int64) for t in trees]
    build_forest(trees, loads)
    f = build_forest(trees, loads)
    arrays = [getattr(f, fld.name) for fld in dataclasses.fields(Forest)
              if fld.name not in PER_CALL]
    arrays = [a for a in (*arrays, *f.levels) if isinstance(a, np.ndarray)]
    assert len(arrays) >= 17
    for a in arrays:
        with pytest.raises(ValueError, match="read-only"):
            a[(0,) * a.ndim] = a[(0,) * a.ndim]
    f.load[0, 0] += 1                        # per-call arrays are its own


def test_solve_batch_on_a_hit_equals_soar():
    t = bt(64, "constant")
    k = 5
    rng = np.random.default_rng(3)
    trees = [t] * 4
    solve_batch(trees, [sample_load(t, "power-law", seed=s)
                        for s in range(4)], k)
    loads = [sample_load(t, "power-law", seed=10 + s) for s in range(4)]
    avails = [rng.random(t.n) < 0.8 for _ in range(4)]
    h0 = telemetry.get("engine.pack_hits")
    res = solve_batch(trees, loads, k, avails)
    assert telemetry.get("engine.pack_hits") == h0 + 1
    for b in range(4):
        want = soar(t, loads[b], k, avail=avails[b]).cost
        blue = res.blue_of(b)
        assert res.costs[b] == want
        assert phi(t, loads[b], blue) == want
        assert blue.sum() <= k and not np.any(blue & ~avails[b])
