"""Batched multi-tenant SOAR placement engine (JAX), device-resident.

Solves B phi-BIC instances at once over the level-packed
:class:`repro.core.forest.Forest` layout. Both halves of SOAR now run on
the accelerator, and only the answers cross the host/device boundary:

  * **Gather** — a level-synchronous sweep (deepest level first) where all
    nodes of a depth level, across *all* instances, are processed
    together. The budget-split min over children (the mCost tropical
    convolution of Algorithm 3) runs through the **fused level-fold**
    in ``repro.kernels.minplus.levelfold``: one launch per level that
    gathers every child's rows and chains the convolutions in-register
    (Pallas kernel on TPU, fused jnp elsewhere). Convolution widths are
    truncated per level to the ``min(k, subtree size)`` knapsack bound
    (``Forest.lvl_sub``) and flat-padded back — exact for the monotone
    at-most-k tables, and most of a tree's nodes sit in deep levels with
    tiny subtrees. Because each level is a contiguous slot block, results
    land via static slice updates — no scatter ops.
  * **Color** — the traceback also runs on device: a top-down
    level-synchronous sweep over the same packed layout replays each
    node's budget split against the resident DP tables with the serial
    solver's exact tie-breaking (blue iff strictly better; first
    minimizer per child split). The sweep is scatter-free: each level
    publishes its split matrix and the next level *gathers* its budget
    and barrier distance through inverse parent pointers. No
    backpointers are stored — splits are re-derived from the tables,
    which are already in device memory.

Only the ``(B, n_max)`` blue masks and ``(B,)`` costs are pulled back to
the host (``BatchResult.bytes_to_host`` reports the traffic); the full
``(B, S+1, h_max+2, k+1)`` table pullback plus host-numpy
:func:`color_batch` replay of PR 1 survives behind the
``debug_tables=True`` escape hatch.

Numerics: the DP runs on the finite ``BIG`` sentinel
(``repro.core.tropical.BIG``) instead of ``inf`` so that ``0 * BIG``
stays finite. Tables are float32 by default; instances whose rho values
are exactly representable (dyadic rates — every paper topology and the
fleet trees) reproduce the float64 reference *bit-exactly*; arbitrary
rates match to float32 eps. Pass ``dtype=jnp.float64`` under
``jax_enable_x64`` for exactness on arbitrary rates.

The min-plus identity here is the all-zeros vector, not ``[0, inf, ...]``:
DP tables are monotone non-increasing in the budget (at-most-k), and for
monotone A, ``minplus(A, 0)[i] = min_{j<=i} A[i-j] = A[i]`` — so missing
children (the identity slot) fold as no-ops while leaf and padded slots
stay finite.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..core.forest import Forest, build_forest, layout_stats
from ..core.tree import Tree
from ..core.tropical import BIG, minplus_batch
from ..kernels.minplus.levelfold import (chain_fold, level_fold,
                                         minplus_fused, rho_up_from_edges,
                                         scaled_edges)
from .options import EngineOptions, pallas_fold, resolve_options

# back-compat alias: the engine's fused convolution now lives with the
# level-fold kernel so both backends share one bit-exact implementation
_minplus_fused = minplus_fused


@functools.partial(
    jax.jit,
    static_argnames=("lvl_off", "lvl_width", "lvl_internal", "lvl_sub", "k",
                     "cap", "use_pallas", "interpret"))
def _gather_packed(
    pk_kid: jax.Array,     # (B, S, max_c) int32 child slots, sentinel S
    pk_load: jax.Array,    # (B, S)
    pk_send: jax.Array,    # (B, S)
    pk_avail: jax.Array,   # (B, S) bool
    pk_rho_up: jax.Array,  # (B, S, h_max+2), BIG at invalid ell
    *,
    lvl_off: tuple,
    lvl_width: tuple,
    lvl_internal: tuple,
    lvl_sub: tuple,
    k: int,
    cap: bool,
    use_pallas: bool,
    interpret: bool,
) -> tuple:
    """Level-synchronous batched SOAR-Gather over the packed slot layout.

    Returns the DP tables as a tuple of per-level **blocks**
    ``blocks[d]`` of shape ``(B, W_d, d+2, k+1)`` (level d's slots, their
    valid barrier rows 0..d+1) rather than one monolithic slot array: a
    node's children live exactly one level down, so each fold only ever
    reads the adjacent block — and the sweep never pays a functional
    whole-table update per level. Padded slots hold finite garbage that
    is never read back. With ``cap=True`` each level's fold runs at the
    truncated width ``min(k, lvl_sub[d]) + 1`` and is flat-padded to k+1
    (exact: monotone tables are constant beyond their subtree's budget).
    """
    B, S, max_c = pk_kid.shape
    H2 = pk_rho_up.shape[2]
    h_max = H2 - 2
    K = k + 1
    dt = pk_rho_up.dtype
    loadf = pk_load.astype(dt)
    sendf = pk_send.astype(dt)

    blocks: list = [None] * (h_max + 1)
    for d in range(h_max, -1, -1):
        o, W, Wi = lvl_off[d], lvl_width[d], lvl_internal[d]
        nl = d + 2                                     # valid rows 0..d+1
        if W == 0:                                     # bucketed tail level
            blocks[d] = jnp.zeros((B, 0, nl, K), dt)
            continue
        Kd = min(K, lvl_sub[d] + 1) if cap else K
        rl = pk_rho_up[:, o : o + W, :nl, None]        # (B, W, nl, 1)
        parts = []
        if Wi > 0:
            # red chain: children see the barrier one hop further -> child
            # rows 1..nl+1 align with our rows 0..nl (they fit: the child
            # block has nl+1 rows). Children are addressed level-locally,
            # with the all-zeros min-plus identity appended at index W1.
            o1, W1 = lvl_off[d + 1], lvl_width[d + 1]
            ch = blocks[d + 1]
            xs = jnp.concatenate(
                [ch[:, :, 1 : nl + 1, :Kd],
                 jnp.zeros((B, 1, nl, Kd), dt)], axis=1)
            xb = jnp.concatenate(
                [ch[:, :, 1, :Kd], jnp.zeros((B, 1, Kd), dt)], axis=1)
            kid_local = jnp.minimum(pk_kid[:, o : o + Wi] - o1, W1)
            with jax.named_scope("levelfold"):
                out = level_fold(
                    xs, xb, kid_local, loadf[:, o : o + Wi],
                    sendf[:, o : o + Wi], pk_avail[:, o : o + Wi],
                    pk_rho_up[:, o : o + Wi, :nl], nl=nl, kcap=Kd,
                    use_pallas=use_pallas, interpret=interpret)
            if Kd < K:                                 # flat-pad (monotone)
                out = jnp.concatenate(
                    [out, jnp.broadcast_to(out[..., -1:],
                                           (B, Wi, nl, K - Kd))], axis=-1)
            parts.append(out)
        if W - Wi > 0:
            # leaves: X_v(l, 0) = L(v) rho; X_v(l, i>=1) also allows blue
            lo = o + Wi
            rll = rl[:, Wi:]
            lr = loadf[:, lo : o + W, None, None] * rll    # (B, Wl, nl, 1)
            sr = sendf[:, lo : o + W, None, None] * rll
            rest = jnp.where(pk_avail[:, lo : o + W, None, None],
                             jnp.minimum(lr, sr), lr)
            parts.append(jnp.concatenate(
                [lr, jnp.broadcast_to(rest, (*rest.shape[:3], K - 1))],
                axis=-1))
        blocks[d] = parts[0] if len(parts) == 1 else jnp.concatenate(
            parts, axis=1)
    return tuple(blocks)


@jax.named_scope("color")
def _color_body(
    blocks: tuple,         # per-level gather blocks, see _gather_packed
    pk_kid: jax.Array,     # (B, S, max_c) int32 child slots, sentinel S
    pk_par: jax.Array,     # (B, S) int32 parent's index in *its* level block
    pk_cidx: jax.Array,    # (B, S) int32 own index in parent's child list
    pk_load: jax.Array,    # (B, S)
    pk_send: jax.Array,    # (B, S)
    pk_avail: jax.Array,   # (B, S) bool
    pk_rho_up: jax.Array,  # (B, S, H2), BIG at invalid ell
    root_slot: jax.Array,  # (B,) int32
    *,
    lvl_off: tuple,
    lvl_width: tuple,
    lvl_internal: tuple,
    lvl_sub: tuple,
    k: int,
    cap: bool,
) -> tuple[jax.Array, jax.Array]:
    """On-device SOAR-Color: top-down level-synchronous traceback.

    Plain traceable function (jitted callers: :func:`_color_packed` for the
    node-indexed public result, the device-resident congestion loop for the
    slot-indexed masks its message sweep consumes directly). Returns the
    ``(B, n_slots)`` *slot-indexed* blue mask plus the ``(B,)`` costs.

    Replays Algorithm 4's budget split against the resident per-level
    table blocks with the exact tie-breaking of the serial ``soar_color``
    (blue iff *strictly* better; *first* minimizer of each child split —
    both ``jnp.argmin`` semantics). The sweep is **scatter-free**:
    instead of parents scattering budgets down to child slots, each level
    stores its internal nodes' split matrix and the next level *gathers*
    its budget and barrier distance through the inverse pointers
    ``pk_par`` / ``pk_cidx`` (XLA:CPU compiles gathers orders of
    magnitude faster than the equivalent scatter chain). Like the gather,
    the replayed chains run at the level's ``min(k, lvl_sub[d]) + 1``
    truncated width: a level-d node can never hold more budget than its
    subtree (the root may, when k > n — all its reads then land in the
    flat region of the monotone tables, where clipped indexing is exact,
    and the first-minimizer split provably stays below the cap). Leaves
    (the back of each level block) skip chains and splits entirely —
    their blue test is elementwise.
    """
    B, _, max_c = pk_kid.shape
    K = k + 1
    dt = blocks[0].dtype
    loadf = pk_load.astype(dt)
    sendf = pk_send.astype(dt)

    blue_parts = []
    prev_split = prev_lc = None      # prev level's child budgets / barrier
    for d, (o, W, Wi) in enumerate(zip(lvl_off, lvl_width, lvl_internal)):
        if W == 0:
            continue                 # bucketed heights: only trailing levels
        if d == 0:
            ids = o + jnp.arange(W, dtype=jnp.int32)[None, :]
            i = jnp.where(ids == root_slot[:, None], k, 0).astype(jnp.int32)
            el = jnp.ones((B, W), jnp.int32)
        else:
            pl = pk_par[:, o : o + W]
            i = jnp.take_along_axis(
                prev_split, pl * max_c + pk_cidx[:, o : o + W], axis=1)
            el = jnp.take_along_axis(prev_lc, pl, axis=1)
        rl = jnp.take_along_axis(pk_rho_up[:, o : o + W], el[:, :, None],
                                 axis=2)[..., 0]
        can_blue = pk_avail[:, o : o + W] & (i >= 1)
        if Wi < W:
            # leaves: no children to chain or split — elementwise test
            red_l = loadf[:, o + Wi : o + W] * rl[:, Wi:]
            blue_l = jnp.where(can_blue[:, Wi:],
                               sendf[:, o + Wi : o + W] * rl[:, Wi:],
                               jnp.inf)
            leaf_blue = blue_l < red_l
        if Wi == 0:
            blue_parts.append(leaf_blue)
            continue                 # leaf-only level: nothing deeper
        Kc = min(K, lvl_sub[d] + 1) if cap else K
        jj = jnp.arange(Kc)[None, None, :]
        i_in, el_in = i[:, :Wi], el[:, :Wi]
        o1, W1 = lvl_off[d + 1], lvl_width[d + 1]
        nl1 = d + 3                  # rows of the child level's block
        ch = jnp.concatenate(
            [blocks[d + 1][..., :Kc],
             jnp.zeros((B, 1, nl1, Kc), dt)], axis=1)  # + identity
        chf = ch.reshape(B, (W1 + 1) * nl1, Kc)
        kidl = jnp.minimum(pk_kid[:, o : o + Wi] - o1, W1)

        def slot_rows(row, kidl=kidl, chf=chf, nl1=nl1, Kc=Kc):
            """All children's tables at per-node row: (B, Wi, max_c, Kc)."""
            idx = (kidl * nl1 + row[:, :, None]).reshape(B, Wi * max_c)
            return jnp.take_along_axis(
                chf, idx[:, :, None], axis=1).reshape(B, Wi, max_c, Kc)

        # partial min-plus chains over children, red (row ell+1) and blue
        # (row 1) variants; sentinel children hit the appended identity.
        # chain_fold is the same fold the gather ran, so replayed values
        # match the tables bit-for-bit.
        er = el_in + 1               # <= d+2: always inside the child block
        row1 = jnp.ones_like(er)
        st_r = jnp.moveaxis(slot_rows(er), 2, 0).reshape(max_c, B * Wi, Kc)
        st_b = jnp.moveaxis(slot_rows(row1), 2, 0).reshape(max_c, B * Wi, Kc)
        st = jnp.concatenate([st_r, st_b], axis=1)     # (max_c, 2BWi, Kc)
        _, parts = chain_fold(st, collect=True)
        ch_r = parts[:, : B * Wi].reshape(max_c, B, Wi, Kc)
        ch_b = parts[:, B * Wi :].reshape(max_c, B, Wi, Kc)
        ic = jnp.minimum(i_in, Kc - 1)                 # flat-region clip
        red_val = jnp.take_along_axis(ch_r[-1], ic[..., None],
                                      axis=2)[..., 0] + loadf[:, o : o + Wi] * rl[:, :Wi]
        ib = jnp.clip(i_in - 1, 0, Kc - 1)
        blue_val = jnp.where(
            can_blue[:, :Wi],
            jnp.take_along_axis(ch_b[-1], ib[..., None], axis=2)[..., 0]
            + sendf[:, o : o + Wi] * rl[:, :Wi],
            jnp.inf)
        isblue = blue_val < red_val                    # strict, as in serial
        blue_parts.append(isblue if Wi == W else
                          jnp.concatenate([isblue, leaf_blue], axis=1))
        bud = i_in - isblue.astype(jnp.int32)
        lc = jnp.where(isblue, 1, el_in + 1)
        # split the budget among children, last child first (mSplit
        # replay), again as a scan over the child index. Sentinel children
        # read the identity's zero table: their vals are the (monotone
        # non-increasing) partial chain at bud - j, which is non-decreasing
        # in j, so the first minimizer is j = 0 and the running budget
        # passes through untouched — no masking needed.
        chain = jnp.where(isblue[None, :, :, None], ch_b, ch_r)
        # children see the barrier at row lc = isblue ? 1 : ell+1 — both
        # variants were already gathered (st_b at row 1, st_r at ell+1),
        # so select instead of gathering a third time
        xc = jnp.where(isblue[None, :, :, None],
                       st_b.reshape(max_c, B, Wi, Kc),
                       st_r.reshape(max_c, B, Wi, Kc))
        xc_rev = xc[::-1][:-1]                         # m desc
        prev_rev = chain[:-1][::-1]                    # chain[m-1], m desc

        def split_step(bud, inp, jj=jj, Kc=Kc):
            xc, prev = inp
            feas = jj <= bud[..., None]
            vals = jnp.take_along_axis(
                prev, jnp.clip(bud[..., None] - jj, 0, Kc - 1), axis=2)
            vals = jnp.where(feas, vals + xc, jnp.inf)
            best_j = jnp.argmin(vals, axis=2).astype(jnp.int32)
            return bud - best_j, best_j

        bud, best_rev = jax.lax.scan(split_step, bud, (xc_rev, prev_rev))
        split = jnp.concatenate([bud[None], best_rev[::-1]], axis=0)
        prev_split = jnp.moveaxis(split, 0, 2).reshape(B, Wi * max_c)
        prev_lc = lc

    costs = blocks[0][jnp.arange(B), root_slot - lvl_off[0], 1, k]
    blue_slots = jnp.concatenate(blue_parts, axis=1)   # blocks are ordered
    return blue_slots, costs


def slots_to_nodes(blue_slots: jax.Array, slot_of: jax.Array) -> jax.Array:
    """Slot-indexed per-node values -> node-indexed, False/0 at padding.

    ``slot_of`` maps node -> slot with ``n_slots`` at padded nodes; one
    zero row is appended so padded nodes read the neutral element.
    """
    B = blue_slots.shape[0]
    pad = jnp.concatenate(
        [blue_slots, jnp.zeros((B, 1), blue_slots.dtype)], axis=1)
    return jnp.take_along_axis(pad, slot_of, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("lvl_off", "lvl_width", "lvl_internal", "lvl_sub", "k",
                     "cap"))
def _color_packed(
    blocks: tuple,
    pk_kid: jax.Array,
    pk_par: jax.Array,
    pk_cidx: jax.Array,
    pk_load: jax.Array,
    pk_send: jax.Array,
    pk_avail: jax.Array,
    pk_rho_up: jax.Array,
    root_slot: jax.Array,
    slot_of: jax.Array,    # (B, n_max) int32 node -> slot (S at padding)
    *,
    lvl_off: tuple,
    lvl_width: tuple,
    lvl_internal: tuple,
    lvl_sub: tuple,
    k: int,
    cap: bool,
) -> tuple[jax.Array, jax.Array]:
    """Jitted :func:`_color_body` returning the node-indexed ``(B, n_max)``
    blue mask and the ``(B,)`` optimal costs — the only arrays a caller
    needs to pull off-device."""
    blue_slots, costs = _color_body(
        blocks, pk_kid, pk_par, pk_cidx, pk_load, pk_send, pk_avail,
        pk_rho_up, root_slot, lvl_off=lvl_off, lvl_width=lvl_width,
        lvl_internal=lvl_internal, lvl_sub=lvl_sub, k=k, cap=cap)
    return slots_to_nodes(blue_slots, slot_of), costs


_INPUT_CACHE: dict[tuple, tuple] = {}


@telemetry.traced("engine.upload")
def _device_inputs(f: Forest, dtype) -> tuple:
    """One host->device upload of the packed arrays (shared gather/color).

    Returns ``(kid, load, send, avail, rho, par, cidx, slot_of,
    root_slot)`` device arrays — the first five feed the gather, the rest
    the color sweep. Cached per (Forest identity, dtype): a serving loop
    re-solving one built Forest (the orchestrator replanning pattern)
    sanitizes and uploads the byte-identical arrays once, not per solve.
    The cache assumes built Forests are immutable. Their structural arrays
    are read-only (shared through ``build_forest``'s layout cache); the
    load-dependent ones (``pk_load``, ``pk_send``, ``pk_avail``) are not,
    and mutating them in place after a solve would silently reuse the
    stale device copies — rebuild via :func:`build_forest` instead (cheap
    for the same trees: only the loads are packed again). Counts
    ``engine.upload_hits``, and on a miss the device bytes written in
    ``engine.upload_bytes``.
    """
    key = (id(f), np.dtype(dtype).str)
    hit = _INPUT_CACHE.get(key)
    if hit is not None and hit[0]() is f:
        telemetry.count("engine.upload_hits")
        return hit[1]
    R = jnp.asarray(np.where(np.isfinite(f.pk_rho_up), f.pk_rho_up, BIG),
                    dtype)
    inputs = (jnp.asarray(f.pk_kid), jnp.asarray(f.pk_load),
              jnp.asarray(f.pk_send), jnp.asarray(f.pk_avail), R,
              jnp.asarray(f.pk_par), jnp.asarray(f.pk_cidx),
              jnp.asarray(f.slot_of),
              jnp.asarray(f.slot_of[np.arange(f.batch), f.root]))
    telemetry.count("engine.upload_bytes", sum(x.nbytes for x in inputs))
    _INPUT_CACHE[key] = (weakref.ref(f, lambda _, k=key:
                                     _INPUT_CACHE.pop(k, None)), inputs)
    return inputs


_OVERRIDE_CACHE: dict[tuple, tuple] = {}


@telemetry.traced("engine.upload")
def _override_inputs(f: Forest, dtype) -> tuple:
    """Device arrays for re-solving ``f`` under effective-rho overrides.

    Returns ``(base_edge, anc, valid, sn, real)``:

      * ``base_edge`` (B, S): each slot's own up-edge rho (the base rates
        the override scales), finite everywhere — 0 at padded slots;
      * ``anc`` (B, S, h_max+1) int32: ``anc[b, s, j]`` = slot of the
        j-th ancestor of slot s (j=0 is s itself; slot 0 past the root);
      * ``valid`` (B, S, h_max+2) bool: where ``pk_rho_up`` is finite;
      * ``sn`` / ``real`` (B, S): clipped ``slot_node`` + its validity
        mask, for gathering node-indexed scale factors into slot order.

    Together with :func:`repro.kernels.minplus.levelfold.rho_up_from_edges`
    these rebuild the packed rho-up table *on device* from scaled edge
    rates — no repacking, and the gather/color jit keys don't change, so
    one compiled executable serves every override (the congestion loop's
    whole point). Cached per (Forest identity, dtype) like
    :func:`_device_inputs`; same immutability caveat.
    """
    key = (id(f), np.dtype(dtype).str)
    hit = _OVERRIDE_CACHE.get(key)
    if hit is not None and hit[0]() is f:
        return hit[1]
    B, S = f.slot_node.shape
    bix = np.arange(B)[:, None]
    valid = np.isfinite(f.pk_rho_up)
    anc = np.zeros((B, S, f.h_max + 1), np.int32)
    cur = f.slot_node.copy()                      # node id walk, -1 done
    for j in range(f.h_max + 1):
        alive = cur >= 0
        idx = np.maximum(cur, 0)
        anc[:, :, j] = np.where(alive, f.slot_of[bix, idx], 0)
        cur = np.where(alive, f.parent[bix, idx], -1)
    inputs = (jnp.asarray(np.where(valid[:, :, 1], f.pk_rho_up[:, :, 1],
                                   0.0), dtype),
              jnp.asarray(anc), jnp.asarray(valid),
              jnp.asarray(np.maximum(f.slot_node, 0)),
              jnp.asarray(f.slot_node >= 0))
    _OVERRIDE_CACHE[key] = (weakref.ref(f, lambda _, k=key:
                                        _OVERRIDE_CACHE.pop(k, None)), inputs)
    return inputs


@jax.jit
def _override_rho(base_edge: jax.Array, anc: jax.Array, valid: jax.Array,
                  sn: jax.Array, real: jax.Array,
                  scale: jax.Array) -> jax.Array:
    """Effective packed rho-up table for a node-indexed scale factor."""
    s_slot = jnp.where(real, jnp.take_along_axis(
        scale.astype(base_edge.dtype), sn, axis=1), 1)
    return rho_up_from_edges(scaled_edges(base_edge, s_slot), anc, valid)


@jax.jit
def _override_rho_add(base_edge: jax.Array, anc: jax.Array, valid: jax.Array,
                      sn: jax.Array, real: jax.Array, scale: jax.Array,
                      extra: jax.Array, root_slot: jax.Array) -> jax.Array:
    """:func:`_override_rho` plus a per-instance additive root-edge term.

    ``extra``: (B,) — the fleet driver's shared-core transit extension on
    each instance's root up-edge (see
    :func:`~repro.kernels.minplus.levelfold.scaled_edges`); ``root_slot``:
    (B,) int32 root slot per instance.
    """
    s_slot = jnp.where(real, jnp.take_along_axis(
        scale.astype(base_edge.dtype), sn, axis=1), 1)
    edges = scaled_edges(base_edge, s_slot, extra.astype(base_edge.dtype),
                         root_slot)
    return rho_up_from_edges(edges, anc, valid)


def _gather_device(f: Forest, k: int, dtype, use_pallas: bool,
                   interpret: bool, cap: bool = True,
                   inputs: tuple | None = None) -> tuple:
    """Run the resident gather; returns the per-level device table blocks."""
    kid, load, send, avail, R = (
        _device_inputs(f, dtype) if inputs is None else inputs)[:5]
    return _gather_packed(
        kid, load, send, avail, R,
        lvl_off=f.lvl_off, lvl_width=f.lvl_width,
        lvl_internal=f.lvl_internal, lvl_sub=f.lvl_sub,
        k=k, cap=bool(cap), use_pallas=bool(use_pallas),
        interpret=bool(interpret))


def _unpack_tables(f: Forest, blocks: tuple) -> np.ndarray:
    """Per-level device blocks -> node-indexed host float64 tables.

    Debug escape hatch (``debug_tables=True``): pulls the *entire* DP
    table off-device. The default solve path never calls this. Rows
    beyond a level's ``depth+1`` are BIG (never read); index ``n_max`` is
    the all-zeros identity table sentinel children point at.
    """
    B, S = f.batch, f.n_slots
    H2 = f.h_max + 2
    K = blocks[0].shape[-1]
    Xh = np.full((B, S + 1, H2, K), BIG, np.float64)
    for d, blk in enumerate(blocks):
        o, W = f.lvl_off[d], f.lvl_width[d]
        if W:
            Xh[:, o : o + W, : d + 2] = np.asarray(blk, np.float64)
    Xh[:, S] = 0.0
    # node v of instance b lives at slot slot_of[b, v]; padded nodes point
    # at the identity slot, which is exactly the zero table color_batch
    # expects at index n_max.
    idx = np.concatenate(
        [f.slot_of, np.full((B, 1), S, np.int32)], axis=1)
    return Xh[np.arange(B)[:, None], idx]


def gather_batch(f: Forest, k: int, *, dtype=jnp.float32,
                 use_pallas: bool = False, interpret: bool = False,
                 cap: bool = True) -> np.ndarray:
    """Batched SOAR-Gather; returns *node-indexed* DP tables.

    Shape ``(B, n_max+1, h_max+2, k+1)`` float64 on host; index ``n_max``
    is the all-zeros identity slot (what sentinel children point at).
    Debug/inspection API — the solve path keeps tables on device.
    """
    return _unpack_tables(
        f, _gather_device(f, k, dtype, use_pallas, interpret, cap))


def color_batch(f: Forest, X: np.ndarray, k: int) -> np.ndarray:
    """Host-numpy SOAR-Color over *node-indexed* gathered tables.

    PR 1's traceback, kept as the ``debug_tables=True`` escape hatch and
    as the parity oracle for the on-device color: level-synchronous
    replay of Algorithm 4's budget split with the exact tie-breaking of
    the serial ``soar_color`` (blue iff strictly better; first minimizer
    of each child split), vectorized over every node of a level across
    the batch. ``X`` as produced by :func:`gather_batch` (host, float64).
    """
    B, n_max = f.mask.shape
    K = k + 1
    R = np.where(np.isfinite(f.rho_up), f.rho_up, BIG)
    blue = np.zeros((B, n_max), bool)
    budget_at = np.zeros((B, n_max), np.int64)   # budget i for T_v
    ell_at = np.ones((B, n_max), np.int64)       # dist to closest blue anc/d
    budget_at[np.arange(B), f.root] = k
    jj = np.arange(K)[None, :]

    for d, nd in enumerate(f.levels):
        valid = nd < n_max                           # real nodes only
        bv, wv = np.nonzero(valid)
        if len(bv) == 0:
            continue
        vv = nd[bv, wv]
        rows = len(vv)
        ar = np.arange(rows)
        i = budget_at[bv, vv]
        ell = ell_at[bv, vv]
        rl = R[bv, vv, ell]
        kids = f.kid[bv, vv]                         # (rows, max_c)
        # partial min-plus chains over children, red (row ell+1) and blue
        # (row 1) variants; sentinel children hit the zero identity slot.
        # Clip the red row: it only saturates for deepest-level leaves,
        # whose children are all sentinel (zero at every row).
        er = np.minimum(ell + 1, X.shape[2] - 1)
        ch_r = np.empty((rows, f.max_children, K))
        ch_b = np.empty((rows, f.max_children, K))
        ch_r[:, 0] = X[bv, kids[:, 0], er]
        ch_b[:, 0] = X[bv, kids[:, 0], 1]
        for m in range(1, f.max_children):
            ch_r[:, m] = minplus_batch(ch_r[:, m - 1], X[bv, kids[:, m], er])
            ch_b[:, m] = minplus_batch(ch_b[:, m - 1], X[bv, kids[:, m], 1])
        red_val = ch_r[ar, -1, i] + f.load[bv, vv] * rl
        can_blue = f.avail[bv, vv] & (i >= 1)
        blue_val = np.where(
            can_blue,
            ch_b[ar, -1, np.clip(i - 1, 0, K - 1)] + f.send[bv, vv] * rl,
            np.inf)
        isblue = blue_val < red_val                  # strict, as in serial
        blue[bv, vv] = isblue
        budget = i - isblue.astype(np.int64)
        lc = np.where(isblue, 1, ell + 1)
        lcc = np.minimum(lc, X.shape[2] - 1)         # saturates only for
        chain = np.where(isblue[:, None, None], ch_b, ch_r)  # sentinel reads
        # split the budget among children, last child first (mSplit replay)
        for m in range(f.max_children - 1, 0, -1):
            c = kids[:, m]
            real = c < n_max
            Xc = X[bv, c, lcc]                       # (rows, K)
            prev = chain[:, m - 1]
            feas = jj <= budget[:, None]
            vals = prev[ar[:, None], np.clip(budget[:, None] - jj, 0, K - 1)]
            vals = np.where(feas, vals + Xc, np.inf)
            best_j = np.argmin(vals, axis=1)         # first minimizer
            budget_at[bv[real], c[real]] = best_j[real]
            ell_at[bv[real], c[real]] = lc[real]
            budget = budget - np.where(real, best_j, 0)
        c = kids[:, 0]
        real = c < n_max
        budget_at[bv[real], c[real]] = budget[real]
        ell_at[bv[real], c[real]] = lc[real]
    return blue


@dataclasses.dataclass
class BatchResult:
    """Output of :func:`solve_batch` for B padded instances."""

    blue: np.ndarray | None   # (B, n_max) bool, False at padding; None
                              # in costs-only mode (color=False)
    costs: np.ndarray         # (B,) float64 — optimal phi per instance
    n: np.ndarray             # (B,) real node counts (mask key for blue)
    bytes_to_host: int = 0    # device->host traffic this solve actually paid
    tables: np.ndarray | None = None   # node-indexed DP tables; only under
                                       # the debug_tables=True escape hatch

    def blue_of(self, b: int) -> np.ndarray:
        """Unpadded blue mask of instance b."""
        if self.blue is None:
            raise ValueError("solve_batch ran with color=False")
        return self.blue[b, : int(self.n[b])]


def cache_stats() -> dict:
    """Engine packing-cache telemetry: ``forests_built`` /
    ``distinct_layouts`` from :func:`repro.core.forest.layout_stats` — with
    layout bucketing on, ``distinct_layouts`` (and hence the compiled
    executables) stays far below ``forests_built`` on ragged fleets. Whether
    serving recompiles is what
    :func:`repro.launch.compile_cache.compile_stats` counts.
    """
    return layout_stats()


def solve_forest(
    f: Forest,
    k: int,
    *,
    options: EngineOptions | None = None,
    rho_scale: np.ndarray | jax.Array | None = None,
    rho_root_add: np.ndarray | jax.Array | None = None,
    **engine_kw,
) -> BatchResult:
    """:func:`solve_batch` for a pre-built Forest (amortizes packing).

    Default path is fully device-resident: gather and color both run on
    the accelerator and only the ``(B, n_max)`` blue masks plus ``(B,)``
    costs are transferred. Engine behavior is configured through
    ``options`` (:class:`~repro.engine.options.EngineOptions`); the old
    keyword spelling (``color=False``, ``debug_tables=True``, …) is
    removed — stray kwargs raise ``TypeError`` with the migration.

    ``rho_scale`` — a ``(B, n_max)`` node-indexed multiplier on each
    instance's *edge* rates — re-solves the prebuilt Forest under
    effective rho ``rho[v] * rho_scale[b, v]`` without repacking or
    recompiling: the packed rho-up table is rebuilt on device from the
    scaled edges (:func:`_override_rho`), every other packed array and
    the gather/color jit keys are untouched, so one cached executable
    serves all overrides. This is the congestion driver's re-solve
    primitive. Incompatible with ``debug_tables`` (the host replay reads
    the unscaled ``Forest.rho_up``).

    ``rho_root_add`` — a ``(B,)`` *additive* extension of each instance's
    root up-edge rate, applied on top of ``rho_scale`` (which it
    requires): the fleet congestion driver's shared-core transit term —
    core hops are in series with the root hop, so their penalty-weighted
    rates extend the root edge additively rather than multiplicatively.
    """
    opts = resolve_options(options, engine_kw, "solve_forest")
    with telemetry.span("engine.solve", call=telemetry.count("engine.solves")):
        return _solve_forest(f, k, opts, rho_scale, rho_root_add)


def _solve_forest(f: Forest, k: int, opts: EngineOptions,
                  rho_scale=None, rho_root_add=None) -> BatchResult:
    """:func:`solve_forest` with resolved options and no span of its own."""
    if k < 0:
        raise ValueError("budget k must be non-negative")
    use_pallas = pallas_fold(opts)
    inputs = _device_inputs(f, opts.dtype)
    if rho_root_add is not None and rho_scale is None:
        raise ValueError("rho_root_add extends a rho_scale re-solve; pass "
                         "rho_scale (ones for a pure additive override)")
    if rho_scale is not None:
        if opts.debug_tables:
            raise ValueError("rho_scale re-solves on device-side effective "
                             "rho; the debug_tables host replay reads the "
                             "unscaled Forest tables — pick one")
        if tuple(np.shape(rho_scale)) != (f.batch, f.n_max):
            raise ValueError(f"rho_scale shape {np.shape(rho_scale)} != "
                             f"{(f.batch, f.n_max)} (node-indexed, padded)")
        base, anc, valid, sn, real = _override_inputs(f, opts.dtype)
        if rho_root_add is None:
            R = _override_rho(base, anc, valid, sn, real,
                              jnp.asarray(rho_scale))
        else:
            if tuple(np.shape(rho_root_add)) != (f.batch,):
                raise ValueError(
                    f"rho_root_add shape {np.shape(rho_root_add)} != "
                    f"({f.batch},) (one root extension per instance)")
            R = _override_rho_add(base, anc, valid, sn, real,
                                  jnp.asarray(rho_scale),
                                  jnp.asarray(rho_root_add), inputs[8])
        inputs = inputs[:4] + (R,) + inputs[5:]
    blocks = _gather_device(f, k, opts.dtype, use_pallas, opts.interpret,
                            opts.cap, inputs)
    kid_d, load_d, send_d, avail_d, R, par_d, cidx_d, slot_d, root_d = inputs
    if not opts.color:
        # costs-only planning mode: pull back B scalars, not the tables
        with telemetry.span("engine.readback"):
            roots = np.asarray(
                blocks[0][jnp.arange(f.batch), root_d - f.lvl_off[0], 1, k])
        return BatchResult(blue=None, costs=roots.astype(np.float64),
                           n=f.n.copy(), bytes_to_host=int(roots.nbytes))
    if opts.debug_tables:
        Xn = _unpack_tables(f, blocks)
        costs = Xn[np.arange(f.batch), f.root, 1, k]
        return BatchResult(blue=color_batch(f, Xn, k), costs=costs,
                           n=f.n.copy(), tables=Xn,
                           bytes_to_host=sum(int(b.nbytes) for b in blocks))
    with telemetry.span("engine.readback"):
        blue_dev, costs_dev = _color_packed(
            blocks, kid_d, par_d, cidx_d, load_d, send_d, avail_d, R,
            root_d, slot_d,
            lvl_off=f.lvl_off, lvl_width=f.lvl_width,
            lvl_internal=f.lvl_internal, lvl_sub=f.lvl_sub, k=k,
            cap=bool(opts.cap))
        blue = np.asarray(blue_dev)
        costs = np.asarray(costs_dev)
    return BatchResult(blue=blue, costs=costs.astype(np.float64),
                       n=f.n.copy(),
                       bytes_to_host=int(blue.nbytes + costs.nbytes))


def solve_batch(
    trees: Sequence[Tree],
    loads: Sequence[np.ndarray],
    k: int,
    avail: Sequence[np.ndarray] | None = None,
    *,
    options: EngineOptions | None = None,
    **engine_kw,
) -> BatchResult:
    """Solve B phi-BIC instances at once; per-instance output contract of
    :func:`repro.core.soar.soar` (optimal costs, at-most-k blue masks).

    Instances may be ragged (different n, height, children); the packed
    layout is bucketed (see :func:`repro.core.forest.build_forest`), so
    batches of similar shape share one compiled executable. Pass engine
    behavior as ``options=EngineOptions(...)`` — ``use_pallas=None``
    (the default) auto-dispatches: fused level-fold Pallas kernel on
    TPU, fused jnp elsewhere. Everything stays on device; see
    :func:`solve_forest`.
    """
    opts = resolve_options(options, engine_kw, "solve_batch")
    with telemetry.span("engine.solve", call=telemetry.count("engine.solves")):
        return _solve_forest(build_forest(trees, loads, avail), k, opts)
