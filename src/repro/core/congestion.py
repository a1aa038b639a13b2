"""Per-link traffic and congestion for multi-tenant placements.

SOAR minimizes each tenant's *total* utilization; when T tenants share one
reduction tree their placements can pile messages onto the same links. The
congestion objective (Segal et al. 2022, *Constrained In-network Computing
with Low Congestion in Datacenter Networks*) is the *max-link* traffic:

    congestion(e) = sum_t msg_e^t        (optionally time-weighted by rho_e)

This module provides the measurement half of that objective:

  * :func:`messages_up_batch` — host-numpy reference: per-tenant
    ``messages_up`` stacked over the batch;
  * :func:`messages_up_forest` — the batched device kernel over the
    level-packed :class:`~repro.core.forest.Forest` layout: a bottom-up
    level-synchronous sweep (one fused gather+sum per level, no scatters)
    that is **bit-identical** to the host reference (pure int32 arithmetic,
    same per-node child sums);
  * :func:`congestion_profile` — per-link totals across tenants.

The iterative re-solve driver that *optimizes* the objective lives in
``repro.engine.congestion``; it calls :func:`messages_up_forest` on the
same Forest it just solved, so the traffic measurement reuses the packed
arrays already on the accelerator.
"""
from __future__ import annotations

import functools
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .forest import Forest
from .reduce import messages_up
from .tree import Tree


def messages_up_batch(trees, loads, blues) -> np.ndarray:
    """Host reference: stacked :func:`~repro.core.reduce.messages_up`.

    ``trees``/``loads``/``blues`` are per-tenant sequences; returns the
    ``(T, n)`` int64 per-edge message counts (edge e = (v, parent(v))).
    """
    return np.stack([messages_up(t, L, U)
                     for t, L, U in zip(trees, loads, blues, strict=True)])


def _messages_body(
    pk_kid: jax.Array,     # (B, S, max_c) int32 child slots, sentinel S
    pk_load: jax.Array,    # (B, S) int
    pk_send: jax.Array,    # (B, S) int
    blue_slot: jax.Array,  # (B, S) bool
    *,
    lvl_off: tuple,
    lvl_width: tuple,
    lvl_internal: tuple,
) -> jax.Array:
    """Bottom-up level-synchronous message sweep over the packed layout.

    Mirrors the host recurrence exactly: a blue switch emits ``send(v)``
    (1 iff its subtree holds load), a red switch forwards its own load
    plus every child's messages. Children live one level down, so each
    level is one gather + sum; results land as contiguous level blocks
    (no scatters). Integer arithmetic throughout — bit-identical to
    :func:`messages_up_batch` by construction.

    Plain traceable function returning the ``(B, S)`` *slot-indexed*
    counts (level blocks are contiguous, so the concat IS slot order);
    jitted callers: :func:`_messages_packed` for the node-indexed public
    result, and the device-resident congestion loop, which feeds the
    color sweep's slot-indexed masks straight in and keeps the counts on
    the accelerator.
    """
    B, S, max_c = pk_kid.shape
    h_max = len(lvl_off) - 1
    msgs_lvl: list = [None] * (h_max + 1)
    for d in range(h_max, -1, -1):
        o, W, Wi = lvl_off[d], lvl_width[d], lvl_internal[d]
        if W == 0:                                     # bucketed tail level
            msgs_lvl[d] = jnp.zeros((B, 0), jnp.int32)
            continue
        acc = pk_load[:, o : o + W].astype(jnp.int32)
        if Wi > 0:
            # red child sum: address children level-locally, with a zero
            # appended at index W1 where sentinel (missing) children land.
            o1, W1 = lvl_off[d + 1], lvl_width[d + 1]
            ch = jnp.concatenate(
                [msgs_lvl[d + 1], jnp.zeros((B, 1), jnp.int32)], axis=1)
            kidl = jnp.minimum(pk_kid[:, o : o + Wi] - o1, W1)
            childsum = jnp.take_along_axis(
                ch, kidl.reshape(B, Wi * max_c), axis=1
            ).reshape(B, Wi, max_c).sum(axis=2)
            acc = jnp.concatenate([acc[:, :Wi] + childsum, acc[:, Wi:]],
                                  axis=1)
        msgs_lvl[d] = jnp.where(blue_slot[:, o : o + W],
                                pk_send[:, o : o + W].astype(jnp.int32), acc)
    return jnp.concatenate([m for m in msgs_lvl if m.shape[1]], axis=1)


@functools.partial(jax.jit,
                   static_argnames=("lvl_off", "lvl_width", "lvl_internal"))
def _messages_packed(
    pk_kid: jax.Array,     # (B, S, max_c) int32 child slots, sentinel S
    pk_load: jax.Array,    # (B, S) int
    pk_send: jax.Array,    # (B, S) int
    blue_slot: jax.Array,  # (B, S) bool
    slot_of: jax.Array,    # (B, n_max) int32 node -> slot (S at padding)
    *,
    lvl_off: tuple,
    lvl_width: tuple,
    lvl_internal: tuple,
) -> jax.Array:
    """Jitted :func:`_messages_body`, gathered back to node indexing."""
    B = pk_kid.shape[0]
    flat = _messages_body(pk_kid, pk_load, pk_send, blue_slot,
                          lvl_off=lvl_off, lvl_width=lvl_width,
                          lvl_internal=lvl_internal)
    pad = jnp.concatenate([flat, jnp.zeros((B, 1), jnp.int32)], axis=1)
    return jnp.take_along_axis(pad, slot_of, axis=1)


_MSG_INPUT_CACHE: dict[int, tuple] = {}


def _msg_device_inputs(f: Forest) -> tuple:
    """One host->device upload of the sweep's static arrays per Forest.

    Same discipline (and caveat) as the engine's ``_device_inputs``: keyed
    on Forest identity via weakref, so a driver loop measuring the same
    built Forest every round uploads ``pk_kid``/``pk_load``/``pk_send``/
    ``slot_of`` once, not per call. Built Forests are treated as
    immutable — rebuild instead of mutating in place.
    """
    key = id(f)
    hit = _MSG_INPUT_CACHE.get(key)
    if hit is not None and hit[0]() is f:
        return hit[1]
    inputs = (jnp.asarray(f.pk_kid), jnp.asarray(f.pk_load),
              jnp.asarray(f.pk_send), jnp.asarray(f.slot_of))
    _MSG_INPUT_CACHE[key] = (weakref.ref(f, lambda _, k=key:
                                         _MSG_INPUT_CACHE.pop(k, None)),
                             inputs)
    return inputs


def messages_up_forest(f: Forest, blue: np.ndarray) -> np.ndarray:
    """Batched per-edge message counts on device, node-indexed.

    ``blue``: the ``(B, n_max)`` node-indexed masks exactly as
    :func:`repro.engine.solve_forest` returns them (False at padding).
    Returns ``(B, n_max)`` int64 message counts, zero at padded nodes —
    bit-identical to the host :func:`messages_up_batch` on the real nodes.
    The device sweep accumulates in int32 (jax keeps 64-bit ints only
    under ``jax_enable_x64``), so instances whose total load reaches 2**31
    — beyond any real fleet — are rejected rather than silently wrapped.
    """
    B, n_max = f.mask.shape
    if blue.shape != (B, n_max):
        raise ValueError(f"blue shape {blue.shape} != {(B, n_max)}")
    # no edge carries more messages than its instance's total load
    peak = int(f.pk_load.sum(axis=1).max()) if f.pk_load.size else 0
    if peak >= 2 ** 31:
        raise ValueError(f"total load {peak} overflows the device sweep's "
                         "int32 accumulator; use messages_up_batch")
    # slot-indexed blue: padded slots (slot_node < 0) are never blue
    src = np.where(f.slot_node >= 0, f.slot_node, 0)
    blue_slot = np.take_along_axis(np.asarray(blue, bool), src, axis=1)
    blue_slot &= f.slot_node >= 0
    kid, load, send, slot_of = _msg_device_inputs(f)
    out = _messages_packed(
        kid, load, send, jnp.asarray(blue_slot), slot_of,
        lvl_off=f.lvl_off, lvl_width=f.lvl_width,
        lvl_internal=f.lvl_internal)
    return np.asarray(out, np.int64)


def congestion_profile(msgs: np.ndarray,
                       rho: np.ndarray | None = None) -> np.ndarray:
    """Per-link congestion across tenants: ``sum_t msg_e^t [* rho_e]``.

    ``msgs``: (T, n) per-tenant message counts on a *shared* tree (so link
    e of every tenant is the same physical link). ``rho`` switches from
    message-count congestion (the default, Segal et al.'s objective) to
    time-weighted congestion (transmission seconds per link).
    """
    c = np.asarray(msgs, np.int64).sum(axis=0)
    return c * np.asarray(rho) if rho is not None else c


class FleetMeasurement(NamedTuple):
    """Congestion measurement of T placements on one shared tree."""

    msgs: np.ndarray            # (T, n) per-tenant per-link message counts
    congestion: np.ndarray      # (n,) per-link totals (count or time)
    max_congestion: float
    mean_congestion: float      # mean over links carrying traffic
    costs: np.ndarray           # (T,) per-tenant utilization on t.rho


def measure_fleet(t: Tree, loads, blues,
                  rho_weighted: bool = False) -> FleetMeasurement:
    """Host-side fleet measurement — the single definition of the reported
    congestion statistics. Both the driver's result tail
    (``repro.engine.congestion``) and the orchestrator's post-admission
    re-measure report exactly these semantics: max over all links, mean
    over links that carry traffic, utilization on the *original* rho."""
    msgs = messages_up_batch([t] * len(loads), loads, blues)
    prof = congestion_profile(msgs, t.rho if rho_weighted else None)
    carrying = prof[prof > 0]
    return FleetMeasurement(
        msgs=msgs, congestion=prof,
        max_congestion=float(prof.max()),
        mean_congestion=float(carrying.mean()) if carrying.size else 0.0,
        costs=(msgs * t.rho).sum(axis=1).astype(np.float64))


def max_congestion(t: Tree, loads, blues,
                   rho_weighted: bool = False) -> float:
    """Convenience: max-link congestion of per-tenant placements on ``t``."""
    return measure_fleet(t, loads, blues, rho_weighted).max_congestion


class MultiFleetMeasurement(NamedTuple):
    """Congestion measurement of T placements across N trees + shared core.

    Link ids follow the fleet's global link-id space: tree g's up-links
    occupy ``[link_off[g], link_off[g] + n_g)`` in ``congestion``, the
    shared-core links fill the final ``C`` entries (also broken out as
    ``core_congestion``). ``msgs`` rows are tree-local (tenant t's counts
    on its own tree, zero-padded to the widest tree); ``costs`` stay
    tree-local utilization on each tree's original rho — identical
    semantics to :func:`measure_fleet` for the N=1 fleet.
    """

    msgs: np.ndarray            # (T, max_g n_g) tree-local message counts
    congestion: np.ndarray      # (sum n_g + C,) global per-link profile
    core_congestion: np.ndarray  # (C,)
    max_congestion: float
    mean_congestion: float      # mean over links carrying traffic
    costs: np.ndarray           # (T,) per-tenant utilization on own tree
    link_off: np.ndarray        # (N,) global segment start per tree


def measure_fleet_multi(trees, tree_of, loads, blues, core_rho=None,
                        core_path=None, rho_weighted: bool = False,
                        link_id=None,
                        n_links: int | None = None) -> MultiFleetMeasurement:
    """Host-side measurement for a multi-tree fleet sharing a core.

    ``trees``: the N distinct trees; ``tree_of[t]`` names tenant t's tree;
    ``core_rho`` (C,) / ``core_path`` (per tree, core link ids crossed)
    describe the shared core — a tenant's root-crossing messages (the
    count on its root's up-edge) transit every core link on its tree's
    path, which is where tenants on different trees meet. Congestion on a
    core link is the sum of those root counts over the tenants crossing
    it (times ``core_rho`` when ``rho_weighted``). For ``N=1, C=0`` this
    reduces exactly to :func:`measure_fleet` — same sums, same casts.

    ``link_id[g]`` (optional, (n_g,) ints) names the physical link of each
    node's up-link when trees share links (a multi-rooted fabric): the
    tree part of ``congestion`` is then one entry per physical link, each
    summing every tenant that crosses it, whichever tree it came through;
    ``n_links`` (optional) is the count of physical links.
    """
    trees = list(trees)
    tid = np.asarray(list(tree_of), np.int64)
    T = tid.size
    crho = (np.zeros(0, np.float64) if core_rho is None
            else np.asarray(core_rho, np.float64))
    C = crho.size
    crosses = np.zeros((T, C), bool)         # tenant t crosses core link c
    if core_path is not None:
        for t in range(T):
            crosses[t, [int(c) for c in core_path[int(tid[t])]]] = True
    tree_n = np.asarray([t.n for t in trees], np.int64)
    link_off = np.concatenate([[0], np.cumsum(tree_n)[:-1]]).astype(np.int64)
    n_big = int(tree_n.max())
    msgs = np.zeros((T, n_big), np.int64)
    costs = np.zeros(T, np.float64)
    for t in range(T):
        g = int(tid[t])
        tr = trees[g]
        m = messages_up(tr, loads[t], blues[t])
        msgs[t, : tr.n] = m
        costs[t] = (m * tr.rho).sum()
    segs = []
    if link_id is not None:
        if n_links is None:
            n_links = 1 + max(int(np.max(x)) for x in link_id)
        seg = np.zeros(n_links, np.float64 if rho_weighted else np.int64)
        for t in range(T):
            tr = trees[int(tid[t])]
            m = msgs[t, : tr.n]
            np.add.at(seg, link_id[int(tid[t])],
                      m * tr.rho if rho_weighted else m)
        segs.append(seg)
    else:
        for g, tr in enumerate(trees):
            rows = msgs[tid == g][:, : tr.n]
            segs.append(congestion_profile(
                rows, tr.rho if rho_weighted else None))
    root_msgs = np.asarray(
        [msgs[t, trees[int(tid[t])].root] for t in range(T)], np.int64)
    core = np.zeros(C, np.float64 if rho_weighted else np.int64)
    for c in range(C):
        cnt = root_msgs[crosses[:, c]].sum()
        core[c] = cnt * crho[c] if rho_weighted else cnt
    prof = np.concatenate(segs + [core]) if C else np.concatenate(segs)
    carrying = prof[prof > 0]
    return MultiFleetMeasurement(
        msgs=msgs, congestion=prof, core_congestion=core,
        max_congestion=float(prof.max()),
        mean_congestion=float(carrying.mean()) if carrying.size else 0.0,
        costs=costs, link_off=link_off)
