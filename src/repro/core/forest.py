"""Padded batch representation of many phi-BIC instances (a *forest*).

The multi-tenant setting (paper Sec. 5.2) solves one placement instance per
workload; a production engine solves B of them at once. ``Forest`` stacks B
trees of varying shape into dense ``(B, n_max)`` node-indexed arrays with
validity masks, plus a **level-packed slot layout** that the batched JAX
gather in ``repro.engine`` consumes:

  * slots are grouped by depth — every level is one contiguous block, so
    the level-synchronous sweep writes its results with *static* slice
    updates instead of scatters (the difference between a fused memcpy and
    a general scatter op on CPU/TPU);
  * within a level block, internal nodes come first and leaves last: the
    expensive child-fold (the mCost tropical convolution) only runs over
    the internal sub-block, leaves are pure elementwise;
  * missing children point at an *identity* slot (index ``n_slots``) whose
    table is all zeros — for monotone (at-most-k) DP tables the all-zeros
    vector is a min-plus identity, so folding a missing child is a no-op;
  * padded slots inside a block fold only identities and carry zero
    load / BIG rho, so their garbage stays finite and is never read.

Everything here is host-side numpy, packed in two parts:

  * the load-independent layout — every node-indexed structural array,
    the level blocks, the slot maps, the packed children, parent pointers
    and rho-up table — is cached per (tuple of tree identities,
    ``bucket``) in a small LRU that drops an entry when one of its trees
    dies (the per-tree children, depth buckets and rho-up table beneath it
    are cached per tree likewise). Its arrays are read-only, and every
    Forest built from one entry shares them: an in-place write raises;
  * the loads and availability (``load``, ``avail``, ``send`` and their
    packed twins) are packed per call through the cached slot maps.

So a caller re-solving one tree list with fresh loads — the common serving
pattern — pays for the layout once. Batches of *similar* shapes share one
compiled executable in the engine (the jit key is the packed layout +
``k``), so group instances by size when throughput matters.
"""
from __future__ import annotations

import dataclasses
import itertools
import weakref
from collections import OrderedDict
from typing import Sequence

import numpy as np

from .. import telemetry
from .tree import DEST, Tree


@dataclasses.dataclass(frozen=True)
class _TreeStruct:
    """Load-independent per-tree arrays (cached by tree identity)."""

    max_c: int
    kid: np.ndarray                 # (n, max(max_c, 1)) int32; -1 sentinel
    rho_up: np.ndarray              # (n, height+2) float64; inf invalid
    internal: tuple[np.ndarray, ...]  # node ids with children, per depth
    leaf: tuple[np.ndarray, ...]      # leaf node ids, per depth
    sub: np.ndarray                 # (n,) int64 subtree sizes
    ni: tuple[int, ...]             # len(internal[d]) per depth
    nl: tuple[int, ...]             # len(leaf[d]) per depth
    submax: tuple[int, ...]         # max subtree size at depth d


_STRUCT_CACHE: dict[int, tuple] = {}


def _tree_struct(t: Tree) -> _TreeStruct:
    key = id(t)
    hit = _STRUCT_CACHE.get(key)
    if hit is not None and hit[0]() is t:
        return hit[1]
    n, h = t.n, t.height
    max_c = max((len(t.children[v]) for v in range(n)), default=0)
    kid = np.full((n, max(max_c, 1)), -1, np.int32)
    internal: list[list[int]] = [[] for _ in range(h + 1)]
    leaf: list[list[int]] = [[] for _ in range(h + 1)]
    for v in range(n):
        ch = t.children[v]
        if ch:
            kid[v, : len(ch)] = ch
            internal[t.depth[v]].append(v)
        else:
            leaf[t.depth[v]].append(v)
    sub = t.subtree_sizes()
    s = _TreeStruct(
        max_c=max_c, kid=kid, rho_up=t.rho_up_table(),
        internal=tuple(np.asarray(l, np.int32) for l in internal),
        leaf=tuple(np.asarray(l, np.int32) for l in leaf),
        sub=sub,
        ni=tuple(len(l) for l in internal),
        nl=tuple(len(l) for l in leaf),
        submax=tuple(
            int(sub[internal[d] + leaf[d]].max())
            if internal[d] or leaf[d] else 0
            for d in range(h + 1)))
    _STRUCT_CACHE[key] = (weakref.ref(t, lambda _, k=key:
                                      _STRUCT_CACHE.pop(k, None)), s)
    return s


@dataclasses.dataclass(frozen=True)
class Forest:
    """B phi-BIC instances padded into dense arrays (see module docstring)."""

    # -- node-indexed (original per-tree node ids, padded to n_max) ----------
    trees: tuple[Tree, ...]        # originals (for unpacking / debugging)
    parent: np.ndarray             # (B, n_max) int32; -1 root, -2 padding
    rho: np.ndarray                # (B, n_max) float64; 1.0 padding
    load: np.ndarray               # (B, n_max) int64; 0 padding
    avail: np.ndarray              # (B, n_max) bool; False padding
    mask: np.ndarray               # (B, n_max) bool; True at real nodes
    depth: np.ndarray              # (B, n_max) int32; -1 padding
    root: np.ndarray               # (B,) int32
    n: np.ndarray                  # (B,) int64 — real node counts
    height: np.ndarray             # (B,) int32
    kid: np.ndarray                # (B, n_max, max_c) int32; sentinel n_max
    rho_up: np.ndarray             # (B, n_max, h_max+2) float64; inf invalid
    send: np.ndarray               # (B, n_max) int64; 1 iff subtree load > 0
    sub_size: np.ndarray           # (B, n_max) int64 subtree sizes; 0 padding
    levels: tuple[np.ndarray, ...]  # levels[d]: (B, W_d) int32 node ids at
                                    # depth d, padded with n_max
    # -- level-packed (slot-indexed) layout for the batched gather ----------
    slot_of: np.ndarray            # (B, n_max) int32 node -> slot; n_slots pad
    slot_node: np.ndarray          # (B, n_slots) int32 slot -> node; -1 pad
    pk_kid: np.ndarray             # (B, n_slots, max_c) int32 child slots;
                                   #   sentinel n_slots (the identity slot)
    pk_par: np.ndarray             # (B, n_slots) int32: parent's index
                                   #   *within its own level block* (0 for
                                   #   roots/padding) — the on-device color
                                   #   gathers its budget from here
    pk_cidx: np.ndarray            # (B, n_slots) int32: this slot's index in
                                   #   its parent's child list (0 roots/pad)
    pk_load: np.ndarray            # (B, n_slots) int64
    pk_send: np.ndarray            # (B, n_slots) int64
    pk_avail: np.ndarray           # (B, n_slots) bool
    pk_rho_up: np.ndarray          # (B, n_slots, h_max+2) float64; inf pad
    lvl_off: tuple[int, ...]       # level d block = slots [lvl_off[d],
    lvl_width: tuple[int, ...]     #   lvl_off[d] + lvl_width[d])
    lvl_internal: tuple[int, ...]  # first lvl_internal[d] slots of the block
                                   #   are internal nodes, the rest leaves
    lvl_sub: tuple[int, ...]       # max subtree size of any node at level d
                                   #   (static knapsack bound: a level-d table
                                   #   never needs more than min(k, lvl_sub[d])
                                   #   + 1 budget columns)

    @property
    def batch(self) -> int:
        return len(self.trees)

    @property
    def n_max(self) -> int:
        return int(self.parent.shape[1])

    @property
    def n_slots(self) -> int:
        return int(self.slot_node.shape[1])

    @property
    def h_max(self) -> int:
        return int(self.rho_up.shape[2] - 2)

    @property
    def max_children(self) -> int:
        return int(self.kid.shape[2])


def _bucket_up(x: int) -> int:
    """Round up to the next power of two (0 and 1 are their own buckets)."""
    return x if x <= 1 else 1 << (x - 1).bit_length()


def layout_key(f: Forest) -> tuple:
    """The static part of the engine's jit key for this forest.

    Two forests with equal layout keys (and equal budget k / dtype / flags)
    reuse one compiled executable in ``repro.engine``.
    """
    return (f.batch, f.n_max, f.n_slots, f.h_max, f.max_children,
            f.lvl_off, f.lvl_width, f.lvl_internal, f.lvl_sub)


def layout_stats() -> dict:
    """Packing-side cache telemetry: forests built vs distinct jit layouts
    (the ``engine.forests_built`` and ``engine.layouts`` counters)."""
    return {"forests_built": int(telemetry.get("engine.forests_built")),
            "distinct_layouts": int(telemetry.get("engine.layouts"))}


@dataclasses.dataclass(frozen=True)
class _Layout:
    """The load-independent part of a Forest, with the gather indices the
    per-call pack reads; cached per tree tuple, every array read-only."""

    fields: dict                    # the Forest's structural fields
    real: np.ndarray                # (B, S) bool: the slot holds a node
    node_at: np.ndarray             # (B, S) flat index of each slot's node
                                    #   into (B, n_max); 0 at padding
    slot_at: np.ndarray             # (B, n_max) flat index of each node's
                                    #   slot into (B, S+1); S at padding
    sweep: tuple[tuple[int, int, np.ndarray], ...]
    # bottom-up per level: (lvl_off, lvl_internal, (B, wi, max_c) flat
    # index of each internal slot's children into (B, S+1)); the identity
    # slot S reads 0


# Load-independent layouts by (tree identities, bucket), least recently
# used first. One BT(4096) x 64 entry holds about 70 MB; admission waves
# bring a new tree tuple almost every wave, so the cache stays small.
_LAYOUT_CACHE_SIZE = 4
_LAYOUT_CACHE: OrderedDict[tuple, tuple] = OrderedDict()


def _layout(trees: Sequence[Tree], bucket: bool) -> _Layout:
    """The cached layout of ``trees``; counts ``engine.pack_hits``."""
    key = (tuple(map(id, trees)), bool(bucket))
    by_id = {id(t): t for t in trees}
    hit = _LAYOUT_CACHE.get(key)
    if hit is not None and all(hit[0][i]() is t for i, t in by_id.items()):
        _LAYOUT_CACHE.move_to_end(key)
        telemetry.count("engine.pack_hits")
        return hit[1]
    telemetry.count("engine.pack_hits", 0)
    lay = _build_layout(trees, bucket)
    refs = {i: weakref.ref(t, lambda _, k=key: _LAYOUT_CACHE.pop(k, None))
            for i, t in by_id.items()}
    _LAYOUT_CACHE[key] = (refs, lay)
    _LAYOUT_CACHE.move_to_end(key)
    while len(_LAYOUT_CACHE) > _LAYOUT_CACHE_SIZE:
        _LAYOUT_CACHE.popitem(last=False)
    return lay


def _build_layout(trees: Sequence[Tree], bucket: bool) -> _Layout:
    B = len(trees)
    structs = [_tree_struct(t) for t in trees]
    n_max = max(t.n for t in trees)
    h_max = max(t.height for t in trees)
    max_c = max(max(s.max_c for s in structs), 1)
    if bucket:
        n_max = _bucket_up(n_max)
        h_max += h_max & 1           # next even height
        max_c = _bucket_up(max_c)
    H2 = h_max + 2

    parent = np.full((B, n_max), -2, np.int32)
    rho = np.ones((B, n_max), np.float64)
    mask = np.zeros((B, n_max), bool)
    depth = np.full((B, n_max), -1, np.int32)
    root = np.zeros(B, np.int32)
    nn = np.zeros(B, np.int64)
    height = np.zeros(B, np.int32)
    kid = np.full((B, n_max, max_c), n_max, np.int32)   # identity sentinel
    rho_up = np.full((B, n_max, H2), np.inf, np.float64)
    sub_size = np.zeros((B, n_max), np.int64)

    for b, (t, s) in enumerate(zip(trees, structs)):
        n = t.n
        parent[b, :n] = t.parent
        rho[b, :n] = t.rho
        mask[b, :n] = True
        depth[b, :n] = t.depth
        root[b] = t.root
        nn[b] = n
        height[b] = t.height
        mc = s.kid.shape[1]
        kid[b, :n, :mc] = np.where(s.kid >= 0, s.kid, n_max)
        rho_up[b, :n, : t.height + 2] = s.rho_up
        sub_size[b, :n] = s.sub

    heights = [int(h) for h in height]
    levels = []
    for d in range(h_max + 1):
        W = max(max((s.ni[d] + s.nl[d] if d <= h else 0
                     for h, s in zip(heights, structs)), default=0), 1)
        lvl = np.full((B, W), n_max, np.int32)
        for b, (h, s) in enumerate(zip(heights, structs)):
            if d > h:
                continue
            ni = s.ni[d]
            lvl[b, :ni] = s.internal[d]
            lvl[b, ni : ni + s.nl[d]] = s.leaf[d]
        levels.append(lvl)

    # ---- level-packed slot layout -----------------------------------------
    lvl_off, lvl_width, lvl_internal, lvl_sub = [], [], [], []
    S = 0
    for d in range(h_max + 1):
        wi = max((s.ni[d] for h, s in zip(heights, structs) if d <= h),
                 default=0)
        wl = max((s.nl[d] for h, s in zip(heights, structs) if d <= h),
                 default=0)
        sub_d = max((s.submax[d] for h, s in zip(heights, structs)
                     if d <= h), default=0)
        if bucket:
            wi, wl, sub_d = _bucket_up(wi), _bucket_up(wl), _bucket_up(sub_d)
        lvl_off.append(S)
        lvl_internal.append(wi)
        lvl_width.append(wi + wl)
        lvl_sub.append(sub_d)
        S += wi + wl
    slot_of = np.full((B, n_max), S, np.int32)
    slot_node = np.full((B, S), -1, np.int32)
    for b, (h, s) in enumerate(zip(heights, structs)):
        for d in range(h + 1):
            o, wi = lvl_off[d], lvl_internal[d]
            vi, vl = s.internal[d], s.leaf[d]
            slot_of[b, vi] = o + np.arange(len(vi), dtype=np.int32)
            slot_node[b, o : o + len(vi)] = vi
            slot_of[b, vl] = o + wi + np.arange(len(vl), dtype=np.int32)
            slot_node[b, o + wi : o + wi + len(vl)] = vl
    real = slot_node >= 0
    src = np.where(real, slot_node, 0)
    bix = np.arange(B)[:, None]
    pk_rho_up = np.where(real[:, :, None], rho_up[bix, src], np.inf)
    ch = kid[bix, src]                                  # (B, S, max_c)
    ch_slot = np.where(
        ch < n_max,
        slot_of[bix[:, :, None], np.minimum(ch, n_max - 1)], S)
    pk_kid = np.where(real[:, :, None], ch_slot, S).astype(np.int32)

    # inverse child pointers: each slot's parent position (local to the
    # parent's level block) and its own index in the parent's child list —
    # the top-down color sweep *gathers* its budget/distance through these
    # instead of scattering parent -> child (scatter-free jit graphs).
    off_of_slot = np.zeros(S, np.int64)
    for d in range(h_max + 1):
        off_of_slot[lvl_off[d] : lvl_off[d] + lvl_width[d]] = lvl_off[d]
    pk_par = np.zeros((B, S), np.int32)
    pk_cidx = np.zeros((B, S), np.int32)
    bs, ss, ms = np.nonzero(pk_kid < S)
    cs = pk_kid[bs, ss, ms]
    pk_par[bs, cs] = (ss - off_of_slot[ss]).astype(np.int32)
    pk_cidx[bs, cs] = ms.astype(np.int32)

    # flat gather indices for the per-call pack: slot space is (B, S+1),
    # its last column the identity slot S
    row = bix * (S + 1)
    sweep = tuple(
        (lvl_off[d], lvl_internal[d],
         row[:, :, None] + pk_kid[:, lvl_off[d] : lvl_off[d]
                                  + lvl_internal[d]])
        for d in range(h_max, -1, -1) if lvl_internal[d])
    lay = _Layout(
        fields=dict(parent=parent, rho=rho, mask=mask, depth=depth,
                    root=root, n=nn, height=height, kid=kid, rho_up=rho_up,
                    sub_size=sub_size, levels=tuple(levels),
                    slot_of=slot_of, slot_node=slot_node, pk_kid=pk_kid,
                    pk_par=pk_par, pk_cidx=pk_cidx, pk_rho_up=pk_rho_up,
                    lvl_off=tuple(lvl_off), lvl_width=tuple(lvl_width),
                    lvl_internal=tuple(lvl_internal),
                    lvl_sub=tuple(lvl_sub)),
        real=real, node_at=bix * n_max + src, slot_at=row + slot_of,
        sweep=sweep)
    for a in (*lay.fields.values(), *levels, lay.real, lay.node_at,
              lay.slot_at, *(idx for _, _, idx in sweep)):
        if isinstance(a, np.ndarray):
            a.flags.writeable = False
    return lay


def _pack_loads(lay: _Layout, trees: Sequence[Tree],
                loads: Sequence[np.ndarray],
                avail: Sequence[np.ndarray] | None) -> dict:
    """The Forest's load-dependent fields over the cached layout ``lay``."""
    B, n_max = lay.fields["mask"].shape
    load_a = np.zeros((B, n_max), np.int64)
    avail_a = (lay.fields["mask"].copy() if avail is None
               else np.zeros((B, n_max), bool))
    ns = lay.fields["n"]
    stacked = (isinstance(loads, np.ndarray) and ns.min() == ns.max()
               and (avail is None or isinstance(avail, np.ndarray)))
    if stacked:
        # one (B, n) array for rows of one size: pack it whole
        n = int(ns[0])
        if loads.shape != (B, n):
            raise ValueError(f"loads shape {loads.shape} != ({B}, {n})")
        load_a[:, :n] = loads
        if avail is not None:
            avail_a[:, :n] = np.broadcast_to(np.asarray(avail, bool), (B, n))
    for b, t in enumerate(() if stacked else trees):
        n = t.n
        L = np.asarray(loads[b], np.int64)
        if L.shape != (n,):
            raise ValueError(f"load {b} shape {L.shape} != ({n},)")
        load_a[b, :n] = L
        if avail is not None:
            avail_a[b, :n] = (True if avail[b] is None
                              else np.asarray(avail[b], bool))
    real = lay.real
    S = real.shape[1]
    pk_load = np.where(real, np.take(load_a, lay.node_at), 0)
    # send(v) = 1 iff subtree load positive: bottom-up level sweep in slot
    # space, each internal block adding its children's subtree loads
    sub = np.zeros((B, S + 1), np.int64)
    sub[:, :S] = pk_load
    for off, wi, idx in lay.sweep:
        sub[:, off : off + wi] += np.take(sub, idx).sum(axis=2)
    pos = sub > 0
    return dict(
        load=load_a, avail=avail_a,
        send=np.take(pos, lay.slot_at).astype(np.int64),
        pk_load=pk_load, pk_send=pos[:, :S].astype(np.int64),
        pk_avail=(real.copy() if avail is None
                  else np.where(real, np.take(avail_a, lay.node_at), False)))


@telemetry.traced("engine.pack")
def build_forest(
    trees: Sequence[Tree],
    loads: Sequence[np.ndarray],
    avail: Sequence[np.ndarray] | None = None,
    *,
    bucket: bool = True,
) -> Forest:
    """Stack B (tree, load[, avail]) instances into one padded Forest.

    ``bucket=True`` (default) rounds the layout dimensions that feed the
    engine's jit key — per-level internal/leaf widths, ``max_children``,
    the per-level subtree-size caps, and ``h_max`` (to the next even
    height) — up to bucket boundaries (powers of two). Ragged multi-tenant
    batches whose exact shapes differ then collapse onto a handful of
    compiled executables instead of recompiling per layout; the extra slots
    are ordinary padded slots (identity children, zero load) that the
    sweep already tolerates. ``bucket=False`` packs exact shapes.

    The load-independent layout is cached per (tree identities,
    ``bucket``): a second call with the same tree objects packs only the
    loads and availability, and shares the read-only structural arrays of
    the first.
    """
    if len(trees) == 0:
        raise ValueError("empty forest")
    if len(loads) != len(trees):
        raise ValueError(f"{len(loads)} loads for {len(trees)} trees")
    if avail is not None and len(avail) != len(trees):
        raise ValueError(f"{len(avail)} avail masks for {len(trees)} trees")
    lay = _layout(trees, bucket)
    f = Forest(trees=tuple(trees), **lay.fields,
               **_pack_loads(lay, trees, loads, avail))
    telemetry.count("engine.forests_built")
    telemetry.count_distinct("engine.layouts", layout_key(f))
    return f


@dataclasses.dataclass(frozen=True)
class FleetLayout:
    """Per-row tree, candidate and shared-link index maps for a fleet forest.

    ``build_fleet_forest`` packs one batch row per (tenant, candidate
    tree) pair — row ``t * P + c`` is tenant t on tree ``cand[t, c]`` —
    through the ordinary :func:`build_forest` path (a single-tree fleet
    therefore produces a bit-identical ``Forest`` to today's
    ``build_forest``), and this side table records how the rows map back
    onto the fleet: each tenant's home tree, its candidates, and the
    shared-core links each row crosses. With one candidate per tenant
    (``P == 1``) rows are tenants.
    """

    tree_of: np.ndarray            # (T,) int32 tenant -> home tree
    n_trees: int
    tree_n: np.ndarray             # (N,) int64 real node count per tree
    core_rho: np.ndarray           # (C,) float64; C may be 0
    core_path: tuple[tuple[int, ...], ...]  # per tree: core links crossed
    core_inc: np.ndarray           # (R, C) bool — row r crosses core c
    cand: np.ndarray | None = None  # (T, P) int32 candidate trees; None =
                                    #   tree_of, one candidate each

    @property
    def n_core(self) -> int:
        return int(self.core_rho.size)

    @property
    def paths(self) -> int:
        """Candidates per tenant (P)."""
        return 1 if self.cand is None else int(self.cand.shape[1])

    @property
    def row_tree(self) -> np.ndarray:
        """(R,) tree of each batch row."""
        return self.tree_of if self.cand is None else self.cand.reshape(-1)


@telemetry.traced("engine.pack")
def build_fleet_forest(
    trees: Sequence[Tree],
    loads: Sequence[np.ndarray],
    tree_of: Sequence[int],
    avail: Sequence[np.ndarray] | None = None,
    *,
    core_rho: np.ndarray | None = None,
    core_path: Sequence[Sequence[int]] | None = None,
    candidates: np.ndarray | None = None,
    bucket: bool = True,
) -> tuple[Forest, FleetLayout]:
    """Pack T tenants living on N distinct trees into one Forest + layout.

    ``trees`` holds the N trees; ``tree_of[t]`` names tenant t's home
    tree. The Forest itself is built by replicating each tenant's tree
    into the batch — exactly ``build_forest([trees[g] for g in tree_of],
    ...)`` — so for ``tree_of == [0]*T`` the packed layout is
    bit-identical to the single-tree call it refactors.

    ``candidates`` (T, P) lists every tenant's candidate trees, node-aligned
    with its home tree: the batch then holds T * P rows, row ``t * P + c``
    tenant t's load on tree ``candidates[t, c]``, and ``avail`` holds one
    mask per row. Candidate trees that share one :class:`Tree` object pack
    through one cached layout.
    """
    N = len(trees)
    if N == 0:
        raise ValueError("empty fleet")
    tid = np.asarray(list(tree_of), np.int32)
    T = tid.size
    if T == 0:
        raise ValueError("no tenants")
    if len(loads) != T:
        raise ValueError(f"{len(loads)} loads for {T} tenants")
    if tid.min() < 0 or tid.max() >= N:
        raise ValueError(f"tree_of entries must lie in [0, {N})")
    tree_n = np.asarray([t.n for t in trees], np.int64)
    cand = None
    rows_tree, rows_load = tid, list(loads)
    if candidates is not None:
        cand = np.asarray(candidates, np.int32)
        if cand.ndim != 2 or cand.shape[0] != T or cand.shape[1] < 1:
            raise ValueError(f"candidates shape {cand.shape} is not "
                             f"({T}, P)")
        if cand.min() < 0 or cand.max() >= N:
            raise ValueError(f"candidate trees must lie in [0, {N})")
        off = np.nonzero((tree_n[cand] != tree_n[tid][:, None]).any(1))[0]
        if off.size:
            raise ValueError(f"tenant {int(off[0])}'s candidate trees are "
                             "not node-aligned with its home tree")
        rows_tree = cand.reshape(-1)
        P = cand.shape[1]
        rows_load = np.repeat(
            np.stack([np.asarray(L, np.int64) for L in loads]), P, axis=0)
    crho = (np.zeros(0, np.float64) if core_rho is None
            else np.asarray(core_rho, np.float64))
    C = crho.size
    if crho.ndim != 1:
        raise ValueError(f"core_rho must be 1-D, got shape {crho.shape}")
    path = (((),) * N if core_path is None
            else tuple(tuple(map(int, p)) for p in core_path))
    if len(path) != N:
        raise ValueError(f"{len(path)} core paths for {N} trees")
    lens = np.fromiter(map(len, path), np.int64, N)
    flat = np.fromiter(itertools.chain.from_iterable(path), np.int64,
                       int(lens.sum()))
    owner = np.repeat(np.arange(N), lens)
    bad = np.nonzero((flat < 0) | (flat >= C))[0]
    if bad.size:
        raise ValueError(f"core link {int(flat[bad[0]])} on tree "
                         f"{int(owner[bad[0]])}'s path out of range "
                         f"[0, {C})")
    inc = np.zeros((N, C), bool)
    inc[owner, flat] = True
    core_inc = inc[rows_tree]
    f = build_forest([trees[g] for g in rows_tree], rows_load, avail,
                     bucket=bucket)
    lay = FleetLayout(tree_of=tid, n_trees=N, tree_n=tree_n, core_rho=crho,
                      core_path=path, core_inc=core_inc, cand=cand)
    return f, lay
