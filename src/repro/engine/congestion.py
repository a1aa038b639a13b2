"""Congestion-aware multi-tenant placement: a device-resident penalty loop.

SOAR (and :func:`repro.engine.solve_batch`) minimizes each tenant's *own*
utilization; with T tenants sharing reduction trees the independently
optimal placements pile messages onto the same links. Following the
congestion objective of Segal et al. 2022 (*Constrained In-network
Computing with Low Congestion in Datacenter Networks*), this driver
minimizes the **max-link congestion**

    C_max = max_e sum_t msg_e^t        (optionally time-weighted by rho_e)

by iterated penalty reweighting of the engine's effective link rates:

  1. solve all T tenants batched against the current per-tenant effective
     rho — the packed rho-up table is rebuilt *on device* from the scaled
     edge rates (:func:`~repro.kernels.minplus.levelfold.rho_up_from_edges`),
     so every round reuses one prebuilt Forest and one compiled gather /
     color executable;
  2. measure per-link traffic from the blue masks with the batched level
     sweep (``repro.core.congestion``) — still on device;
  3. multiplicatively boost each tenant's effective rho on overloaded
     links, proportionally to that tenant's own contribution — the tenants
     responsible for a hotspot are the ones re-routed away from it; a
     deterministic per-tenant penalty gradient (``alpha_t`` ramps with the
     tenant index) breaks ties between look-alike tenants, so identical
     workloads spread instead of migrating in lockstep. With per-switch
     ``capacity`` given, links whose switch is near its capacity claim are
     priced up jointly with hot links (capacity pricing);
  4. re-solve on the reweighted rho and keep the best (strictly lowest
     C_max) placement seen — the loop is monotone-best, never worse than
     the utilization-only baseline (round 0).

**Fleet-native.** The driver is :func:`solve_fleet`: T tenants spread over
N aggregation trees that hang off a shared core of C extra links
(:class:`repro.collectives.topology.Fleet`). Every round profiles and
reweights over the *union* of tree-local and shared-core links inside the
same loop: per-tree profiles come from a tenant->tree scatter-add, core
profiles from each tenant's root-crossing count summed over the tenants
whose core path includes the link, and the core penalty weights feed back
into the DP as an *additive* extension of each tenant's root up-edge
(core hops are in series with the root hop — see
:func:`~repro.kernels.minplus.levelfold.scaled_edges`). That is how
tenants on *different* trees get congestion-coupled: a hot shared core
link raises every crossing tenant's effective root rate, and the DP pulls
their aggregation points rootward until the core cools.
:func:`solve_congestion` is the single-tree entry — structurally the
degenerate ``N=1, C=0`` fleet (one tree, no core), not a parallel code
path, which is what keeps it bit-identical to the fleet machinery.

**Device-resident loop (default).** ``device_loop=True`` runs the whole
round loop as one jitted ``lax.while_loop``: fused level-fold gather →
on-device color → messages-up sweep → penalty reweight → monotone-best
tracking, with nothing leaving the accelerator between rounds. Only the
best round's masks, the scalar congestion history, and the round-0 profile
transfer at the end (``CongestionResult.bytes_to_host`` reports the
traffic). ``device_loop=False`` keeps the host-driven reference: the same
jitted round pieces called one round at a time through the public
:func:`~repro.engine.solve_forest` ``rho_scale`` / ``rho_root_add``
API, with masks, counts and the profile pulled to the host every round
(PR 3's transfer pattern).

**In-loop hard admission.** ``residual=`` hands the driver per-tree
residual-capacity vectors (the orchestrator's integer claim ledgers) and
turns capacity from a *price* into a *constraint inside the loop*: every
round, each tenant's candidate blue set is truncated to the claims the
residual actually covers (claims are ranked per switch in tenant order —
exactly the order a host ledger would replay them) and the rejected
(tenant, switch) pairs are *banned* through the existing ``avail``
mechanics, so the next round's DP routes those tenants elsewhere. The
loop therefore converges directly to placements a per-switch ledger can
admit wholesale — no host round-trip per admission, no post-hoc
eviction. The device loop computes claim ranks with an exact integer
one-hot cumsum; the host reference replays a literal sequential numpy
ledger per round — integer arithmetic both ways, so the two paths stay
round-for-round bit-identical (``tests/test_admission_device.py``).

**Parity.** Both paths run the *identical* float32 update arithmetic —
the shared :func:`_round_penalty` body (profiles + reweights for tree and
core links), the shared
:func:`~repro.kernels.minplus.levelfold.scaled_edges` effective-edge
recipe and the shared device rho-up recompute — so with
``record_rounds=True`` the two paths are round-for-round bit-identical:
same effective rho, same masks, same history (asserted in
``tests/test_congestion_device.py`` and ``tests/test_fleet.py``). Weights
are quantized to a dyadic grid (multiples of ``1/1024``), so on
dyadic-rho trees every round's effective rho stays exactly representable
in float32 and the batched solve is also bit-identical to the serial
:func:`repro.core.soar.soar` on the same reweighted instance (asserted in
``tests/test_congestion.py``). Utilization and congestion are always
reported against the *original* rho — the penalties shape the search, not
the objective.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..core.congestion import _messages_body, measure_fleet_multi
from ..core.forest import build_fleet_forest, build_forest
from ..core.tree import Tree
from ..kernels.minplus.levelfold import rho_up_from_edges, scaled_edges
from .batched import (_color_body, _device_inputs, _gather_packed,
                      _override_inputs)
from .options import EngineOptions, pallas_fold, resolve_options

#: weights are rounded to this dyadic grid so effective rho stays exactly
#: float32-representable on dyadic-rho trees (bit-identical engine/serial)
W_QUANTUM = 1.0 / 1024.0


@dataclasses.dataclass
class CongestionResult:
    """Best placement found by :func:`solve_fleet` plus diagnostics.

    Per-link arrays use the fleet's **global link-id space**: tree g's
    up-links occupy ``[off_g, off_g + n_g)`` of ``congestion`` (offsets
    in tree order), the C shared-core links fill the final entries (also
    broken out as ``core_congestion``). For the single-tree
    :func:`solve_congestion` entry that is simply the familiar ``(n,)``
    per-link profile.
    """

    blue: np.ndarray          # (T, max_g n_g) bool — best per-tenant masks,
                              # each row valid on its own tree's prefix
    costs: np.ndarray         # (T,) float64 — utilization on the ORIGINAL rho
    msgs: np.ndarray          # (T, max_g n_g) int64 tree-local messages
    congestion: np.ndarray    # (sum n_g + C,) global per-link profile of
                              # the best round
    max_congestion: float     # C_max of the best round (incl. core links)
    mean_congestion: float    # mean over links carrying traffic
    baseline_max: float       # round 0 = utilization-only solve_batch
    baseline_mean: float
    rounds: int               # solve rounds actually run (incl. round 0)
    best_round: int
    history: list             # per-round C_max
    rounds_log: list | None = None   # [(rho_eff (T,n), blue (T,n))] when
                                     # record_rounds=True (parity testing)
    bytes_to_host: int = 0    # device->host traffic the driver actually paid
    tree_of: np.ndarray | None = None    # (T,) tenant -> tree index
    core_congestion: np.ndarray | None = None  # (C,) shared-core profile
    # -- hard admission (residual=...) only --
    admission_dropped: np.ndarray | None = None  # (T,) int64 claims the
                                                 # best round could not admit
    residual_after: list | None = None   # per-tree int64 residual ledgers
                                         # after the best round's claims
    admission_log: list | None = None    # per-round (T,) dropped-claim
                                         # counts when record_rounds=True

    @property
    def improvement(self) -> float:
        """Relative max-congestion reduction vs the utilization-only plan."""
        if self.baseline_max <= 0:
            return 0.0
        return 1.0 - self.max_congestion / self.baseline_max


# ---------------------------------------------------------------------------
# shared round arithmetic — the single definition BOTH loop flavors run.
# The device while_loop inlines these; the host reference calls the jitted
# _penalty_step wrapper below. Same traced op sequence -> same float32
# results (XLA does not contract or reassociate elementwise float ops),
# which is what makes the two paths round-for-round bit-identical. Keep it
# that way.
# ---------------------------------------------------------------------------

def _profile(msgs: jax.Array, link_w: jax.Array, tree_id: jax.Array,
             *, n_trees: int) -> jax.Array:
    """Per-tree per-link congestion: int32 counts scatter-added over each
    tree's tenants, then weighted (``link_w`` is (N, links) — the original
    per-link rho when rho_weighted, else 1). Integer scatter-add is exact
    and order-free, so the N=1 case equals the plain tenant sum bitwise."""
    counts = jnp.zeros((n_trees, msgs.shape[1]),
                       msgs.dtype).at[tree_id].add(msgs)
    return counts.astype(link_w.dtype) * link_w


def _crowding(blue: jax.Array, tree_id: jax.Array, capacity: jax.Array,
              cap_frac, *, n_trees: int) -> jax.Array:
    """Capacity-pricing term: per-tenant (T, links) pressure on crowded
    switches of the tenant's own tree (zero elsewhere)."""
    counts = jnp.zeros((n_trees, blue.shape[1]),
                       jnp.int32).at[tree_id].add(blue.astype(jnp.int32))
    usage = jnp.take(counts, tree_id, axis=0).astype(capacity.dtype)
    pressure = usage / jnp.maximum(jnp.take(capacity, tree_id, axis=0), 1e-6)
    crowded = (pressure >= cap_frac) & blue
    return jnp.where(crowded, pressure, 0.0)


def _reweight(w, msgs, prof_t, cmax, alpha_t, ramp_t, hot_frac, w_cap,
              link_w_t, crowd, cap_beta, *, priced: bool):
    """One penalty update of a (T, links) weight matrix.

    Hot links (``prof_t >= hot_frac * cmax`` — C_max is the *global* max,
    over tree and core links jointly) boost each tenant's weight in
    proportion to that tenant's own traffic share; ``crowd`` carries the
    capacity-pricing pressure (:func:`_crowding`) when ``priced``. One
    dyadic quantization after the joint boost keeps the effective rho
    exactly float32-representable on dyadic trees.
    """
    hot = prof_t >= hot_frac * cmax
    contrib = msgs.astype(w.dtype) * link_w_t / cmax
    boost = 1.0 + alpha_t * jnp.where(hot, contrib, 0.0)
    if priced:
        boost = boost * (1.0 + cap_beta * ramp_t * crowd)
    q = jnp.round(w * boost / W_QUANTUM) * W_QUANTUM
    return jnp.minimum(q, w_cap)


def _core_extra(core_base: jax.Array, wc: jax.Array,
                core_onf: jax.Array) -> jax.Array:
    """Per-tenant additive root-edge extension from shared-core transit:
    each core link on the tenant's path contributes its penalty-weighted
    rate. ``core_base``: (C,) core rho; ``wc``: (T, C) weights;
    ``core_onf``: (T, C) float incidence. Returns (T,)."""
    return (core_base[None, :] * wc * core_onf).sum(axis=1)


def _admit_ranked(blue, tree_id, residual, *, n_trees: int):
    """Hard-admission truncation of one round's candidate blue sets.

    A claim by tenant t on switch s is admitted iff fewer than
    ``residual[tree_of[t], s]`` lower-indexed tenants of the same tree
    also claim s this round — the exact set a sequential per-tree ledger
    replay in tenant order admits, computed in one shot as an integer
    one-hot cumsum (exact and order-free per element, so the device loop
    and the host ledger reference agree bitwise). Returns
    ``(admitted, rejected)`` bool (T, links) masks.
    """
    oh = (tree_id[:, None] == jnp.arange(n_trees)[None, :]).astype(jnp.int32)
    cum = jnp.cumsum(blue.astype(jnp.int32)[:, None, :] * oh[:, :, None],
                     axis=0)                       # (T, N, links)
    rank = (cum * oh[:, :, None]).sum(axis=1)      # own-tree row, (T, links)
    res_t = jnp.take(residual, tree_id, axis=0)
    admitted = blue & (rank <= res_t)
    return admitted, blue & ~admitted


def _round_penalty(w, wc, msgs, blue, root_idx, tree_id, link_w,
                   core_link_w, core_on, capacity, alpha_t, ramp_t,
                   hot_frac, w_cap, cap_beta, cap_frac, *,
                   n_trees: int, priced: bool):
    """Profile the union of tree-local and shared-core links, then apply
    one penalty update to both weight matrices.

    ``msgs``: (T, links) int32 per-tenant counts on the tenant's own tree;
    ``root_idx``: (T,) column of each tenant's root link (its root-crossing
    count is the core transit); ``core_on``: (T, C) bool incidence.
    Returns ``(prof_tree (N, links), prof_core (C,), cmax, w', wc')`` —
    C_max is the max over *all* links, tree and core jointly, so a hot
    shared core link dominates the stop/best tracking and the hot-link
    threshold exactly like a hot tree link.
    """
    prof_tree = _profile(msgs, link_w, tree_id, n_trees=n_trees)
    cmax = prof_tree.max()
    C = wc.shape[1]
    if C:
        root_msgs = jnp.take_along_axis(msgs, root_idx[:, None], axis=1)
        core_msgs = root_msgs * core_on.astype(msgs.dtype)      # (T, C)
        prof_core = (core_msgs.sum(axis=0).astype(core_link_w.dtype)
                     * core_link_w)
        cmax = jnp.maximum(cmax, prof_core.max())
    else:
        prof_core = jnp.zeros((0,), w.dtype)
    prof_t = jnp.take(prof_tree, tree_id, axis=0)               # (T, links)
    link_w_t = jnp.take(link_w, tree_id, axis=0)
    crowd = (_crowding(blue, tree_id, capacity, cap_frac, n_trees=n_trees)
             if priced else jnp.zeros_like(w))
    w2 = _reweight(w, msgs, prof_t, cmax, alpha_t, ramp_t, hot_frac, w_cap,
                   link_w_t, crowd, cap_beta, priced=priced)
    if C:
        # the core links have no per-switch capacity claim — pricing is a
        # tree-link concept — so their reweight is never priced
        wc2 = _reweight(wc, core_msgs,
                        jnp.broadcast_to(prof_core[None, :], wc.shape),
                        cmax, alpha_t, ramp_t, hot_frac, w_cap,
                        jnp.broadcast_to(core_link_w[None, :], wc.shape),
                        jnp.zeros_like(wc), cap_beta, priced=False)
    else:
        wc2 = wc
    return prof_tree, prof_core, cmax, w2, wc2


_penalty_step = functools.partial(
    jax.jit, static_argnames=("n_trees", "priced"))(_round_penalty)

_core_extra_step = jax.jit(_core_extra)


@jax.jit
def _edge_scale(base_edge: jax.Array, w: jax.Array) -> jax.Array:
    """Effective per-edge rates (the quantity ``record_rounds`` logs)."""
    return scaled_edges(base_edge, w)


@jax.jit
def _edge_scale_core(base_edge: jax.Array, w: jax.Array, extra: jax.Array,
                     root_idx: jax.Array) -> jax.Array:
    """:func:`_edge_scale` with the shared-core root extension applied."""
    return scaled_edges(base_edge, w, extra, root_idx)


# ---------------------------------------------------------------------------
# the device-resident loop
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("lvl_off", "lvl_width", "lvl_internal", "lvl_sub", "k",
                     "cap", "use_pallas", "interpret", "max_rounds",
                     "record", "priced", "admit", "n_trees"))
def _device_driver(
    kid, load, send, avail, par, cidx, root_slot,     # packed solve inputs
    base_edge, anc, valid,                            # rho-override inputs
    tree_id, link_w, capacity,                        # (T,), (N,S), (N,S)
    residual,                                         # (N,S) int32 ledgers
    core_base, core_on, core_link_w,                  # (C,), (T,C), (C,)
    alpha_t, ramp_t,                                  # (T, 1) tenant ramps
    hot_frac, w_cap, cap_beta, cap_frac, patience,    # scalars
    *,
    lvl_off, lvl_width, lvl_internal, lvl_sub, k, cap, use_pallas,
    interpret, max_rounds: int, record: bool, priced: bool, admit: bool,
    n_trees: int,
):
    """The whole penalty loop as one ``lax.while_loop`` on the accelerator.

    Per round: shared-core root extension + device rho-up recompute ->
    fused level-fold gather -> on-device color (slot-indexed masks, no
    node gather) -> messages-up sweep -> shared profile/reweight over the
    union of tree and core links -> monotone-best tracking. The carry
    holds both weight matrices (tree links and core links), best-so-far
    masks, the scalar history and (when ``record``) the per-round logs;
    nothing crosses the host boundary until the caller pulls the final
    tuple.

    With ``admit`` the carry also owns the availability masks: each
    round's candidate blues are truncated to what ``residual`` covers
    (:func:`_admit_ranked`) and rejected claims ban their (tenant,
    switch) pair from every later round, so the loop converges to
    placements the per-switch ledgers admit outright. A round that
    banned something never triggers the patience stop — the search
    landscape just changed under it.
    """
    T, S, _ = kid.shape
    dt = base_edge.dtype
    C = core_base.shape[0]

    @jax.named_scope("penalty_round")
    def body(carry):
        (r, w, wc, avail, stale, stop, best_cmax, best_blue, best_round,
         best_drop, history, prof0, prof0c, log_rho, log_blue,
         log_drop) = carry
        if C:
            extra = _core_extra(core_base, wc, core_on.astype(dt))
            edges = scaled_edges(base_edge, w, extra, root_slot)
        else:
            edges = scaled_edges(base_edge, w)
        R = rho_up_from_edges(edges, anc, valid)
        blocks = _gather_packed(
            kid, load, send, avail, R,
            lvl_off=lvl_off, lvl_width=lvl_width,
            lvl_internal=lvl_internal, lvl_sub=lvl_sub,
            k=k, cap=cap, use_pallas=use_pallas, interpret=interpret)
        blue, _ = _color_body(
            blocks, kid, par, cidx, load, send, avail, R, root_slot,
            lvl_off=lvl_off, lvl_width=lvl_width,
            lvl_internal=lvl_internal, lvl_sub=lvl_sub, k=k, cap=cap)
        if admit:
            blue, rejected = _admit_ranked(blue, tree_id, residual,
                                           n_trees=n_trees)
            avail = avail & ~rejected              # persistent in-loop ban
            banned = rejected.any()
            drop = rejected.sum(axis=1).astype(jnp.int32)
        else:
            banned = jnp.asarray(False)
            drop = jnp.zeros((T,), jnp.int32)
        msgs = _messages_body(
            kid, load, send, blue,
            lvl_off=lvl_off, lvl_width=lvl_width, lvl_internal=lvl_internal)
        prof_tree, prof_core, cmax, w2, wc2 = _round_penalty(
            w, wc, msgs, blue, root_slot, tree_id, link_w, core_link_w,
            core_on, capacity, alpha_t, ramp_t, hot_frac, w_cap, cap_beta,
            cap_frac, n_trees=n_trees, priced=priced)
        history = history.at[r].set(cmax)
        prof0 = jnp.where(r == 0, prof_tree, prof0)
        prof0c = jnp.where(r == 0, prof_core, prof0c)
        if record:
            log_rho = log_rho.at[r].set(edges)
            log_blue = log_blue.at[r].set(blue)
            log_drop = log_drop.at[r].set(drop)
        better = cmax < best_cmax                    # strict: earliest wins
        best_blue = jnp.where(better, blue, best_blue)
        best_round = jnp.where(better, r, best_round)
        best_cmax = jnp.where(better, cmax, best_cmax)
        best_drop = jnp.where(better, drop, best_drop)
        stale = jnp.where(better, 0, stale + 1)
        if admit:
            stop = (cmax == 0.0) | ((stale >= patience) & ~banned)
        else:
            stop = (cmax == 0.0) | (stale >= patience)
        return (r + 1, w2, wc2, avail, stale, stop, best_cmax, best_blue,
                best_round, best_drop, history, prof0, prof0c, log_rho,
                log_blue, log_drop)

    def cond(carry):
        return (carry[0] < max_rounds) & ~carry[5]

    Rl = max_rounds if record else 0
    init = (jnp.int32(0), jnp.ones((T, S), dt), jnp.ones((T, C), dt),
            avail, jnp.int32(0), jnp.asarray(False),
            jnp.asarray(jnp.inf, dt),
            jnp.zeros((T, S), bool), jnp.int32(0), jnp.zeros((T,), jnp.int32),
            jnp.full((max_rounds,), -1.0, dt), jnp.zeros((n_trees, S), dt),
            jnp.zeros((C,), dt),
            jnp.zeros((Rl, T, S), dt), jnp.zeros((Rl, T, S), bool),
            jnp.zeros((Rl, T), jnp.int32))
    out = jax.lax.while_loop(cond, body, init)
    (r, _, _, _, _, _, best_cmax, best_blue, best_round, best_drop, history,
     prof0, prof0c, log_rho, log_blue, log_drop) = out
    return best_blue, best_round, r, history, prof0, prof0c, best_drop, \
        log_rho, log_blue, log_drop


# ---------------------------------------------------------------------------
# the public drivers
# ---------------------------------------------------------------------------

def solve_fleet(
    trees: Sequence[Tree],
    loads: Sequence[np.ndarray],
    tree_of: Sequence[int],
    k: int,
    avail: Sequence[np.ndarray | None] | None = None,
    *,
    core_rho: np.ndarray | None = None,
    core_path: Sequence[Sequence[int]] | None = None,
    max_rounds: int = 8,
    patience: int = 2,
    alpha: float = 2.0,
    hot_frac: float = 0.75,
    w_cap: float = 8.0,
    rho_weighted: bool = False,
    capacity: Sequence[np.ndarray] | None = None,
    cap_beta: float = 1.0,
    cap_frac: float = 0.75,
    residual: Sequence[np.ndarray] | None = None,
    record_rounds: bool = False,
    device_loop: bool = True,
    options: EngineOptions | None = None,
    **engine_kw,
) -> CongestionResult:
    """Minimize max-link congestion for T tenants across a multi-tree fleet.

    ``trees``: the N distinct aggregation trees; ``tree_of[t]`` names
    tenant t's tree (every tree needs at least one tenant); ``loads``:
    one load vector per tenant, shaped for its own tree. ``core_rho`` /
    ``core_path`` describe the shared core (see
    :class:`repro.collectives.topology.Fleet`): a tenant's root-crossing
    messages transit every core link on its tree's path, the per-link
    profile spans the union of tree-local and core links, and core
    penalties feed back as additive root-edge extensions — tenants on
    different trees trade placements through the shared links.

    ``avail``: a per-tenant sequence of masks (or None). ``capacity``:
    per-*tree* capacity vectors (len N) switching on capacity pricing for
    tree links. ``residual``: per-*tree* integer residual-capacity
    ledgers (len N) switching on **hard in-loop admission** — every
    round's candidate blues are truncated to the claims the ledger
    covers, rejected claims ban their (tenant, switch) pair via the
    ``avail`` mechanics, and the returned placements are feasible against
    the ledgers wholesale (``admission_dropped`` / ``residual_after`` on
    the result report the best round's shortfall and remaining
    capacity). Zero-residual and zero-capacity switches leave every
    affected tenant's candidate set up front. All other knobs as
    :func:`solve_congestion`, which is the degenerate ``N=1, C=0`` call
    of this driver.
    """
    telemetry.count("engine.solves")
    with telemetry.span("engine.prepare"):
        T = len(loads)
        if T == 0:
            raise ValueError("solve_fleet needs at least one tenant")
        if max_rounds < 1:
            raise ValueError("max_rounds must be >= 1")
        opts = resolve_options(options, engine_kw, "solve_fleet")
        if not opts.color:
            raise ValueError("solve_fleet needs blue masks; color=False "
                             "(costs-only mode) is not usable here")
        if opts.debug_tables:
            raise ValueError("solve_fleet re-solves on device-side effective "
                             "rho; the debug_tables host replay is not usable "
                             "here")
        # capacity-knob boundary validation: _crowding clamps capacity with
        # 1e-6 (a numerical guard, not a semantics), so malformed knobs must
        # die here, not price a zero-capacity switch as admittable
        if not (np.isfinite(cap_frac) and 0.0 < cap_frac <= 1.0):
            raise ValueError(f"cap_frac must be in (0, 1], got {cap_frac}")
        if not (np.isfinite(cap_beta) and cap_beta >= 0.0):
            raise ValueError(f"cap_beta must be finite and >= 0, "
                             f"got {cap_beta}")
        trees = list(trees)
        N = len(trees)
        tid_np = np.asarray(list(tree_of), np.int32)
        if tid_np.shape != (T,):
            raise ValueError(f"tree_of shape {tid_np.shape} != ({T},)")
        if avail is None:
            avails = [None] * T
        else:
            avails = list(avail)
            if len(avails) != T:
                raise ValueError(f"{len(avails)} avail masks for {T} tenants")
        priced = capacity is not None
        if priced:
            capacity = [np.asarray(c, np.float64) for c in capacity]
            if len(capacity) != N:
                raise ValueError(f"{len(capacity)} capacity vectors for "
                                 f"{N} trees")
            for g, c in enumerate(capacity):
                if c.shape != (trees[g].n,):
                    raise ValueError(f"capacity shape {c.shape} != "
                                     f"({trees[g].n},)")
                if not np.all(np.isfinite(c)) or np.any(c < 0):
                    raise ValueError(f"capacity vector for tree {g} must be "
                                     "finite and non-negative")
        admit = residual is not None
        if admit:
            residual = [np.asarray(rg) for rg in residual]
            if len(residual) != N:
                raise ValueError(f"{len(residual)} residual ledgers for "
                                 f"{N} trees")
            checked = []
            for g, rg in enumerate(residual):
                if rg.shape != (trees[g].n,):
                    raise ValueError(f"residual shape {rg.shape} != "
                                     f"({trees[g].n},) for tree {g}")
                if (not np.all(np.isfinite(rg.astype(np.float64)))
                        or np.any(rg.astype(np.float64)
                                  != np.floor(rg.astype(np.float64)))):
                    raise ValueError(f"residual ledger for tree {g} must be "
                                     "integer-valued")
                if np.any(rg.astype(np.int64) < 0):
                    raise ValueError(f"residual ledger for tree {g} must be "
                                     "non-negative")
                checked.append(rg.astype(np.int64))
            residual = checked
        if admit or priced:
            # hard-unavailability flows through the avail mechanics: switches
            # with no residual (or no capacity at all) leave their tree's
            # tenants' candidate sets before the first solve
            hard = [np.ones(tr.n, bool) for tr in trees]
            for g in range(N):
                if admit:
                    hard[g] &= residual[g] > 0
                if priced:
                    hard[g] &= capacity[g] > 0
            if not all(h.all() for h in hard):
                avails = [
                    (hard[g].copy() if a is None
                     else np.asarray(a, bool) & hard[g])
                    for a, g in zip(avails, tid_np)]
        if admit:
            # the host ledger replay mutates its per-tenant masks (persistent
            # bans) — every tenant needs its own materialized copy
            avails = [np.ones(trees[g].n, bool) if a is None
                      else np.array(a, dtype=bool, copy=True)
                      for a, g in zip(avails, tid_np)]
        use_pallas = pallas_fold(opts)

    # one Forest, one packing, one compiled executable for the whole loop
    f, lay = build_fleet_forest(trees, list(loads), tid_np, avails,
                                core_rho=core_rho, core_path=core_path)
    with telemetry.span("engine.upload"):
        C = lay.n_core
        dt = opts.dtype
        kid, load, send, avail_d, _, par, cidx, slot_d, root_d = \
            _device_inputs(f, dt)
        base_edge, anc, valid, _, _ = _override_inputs(f, dt)
        rep = lay.rep

        # per-tenant penalty ramp: deterministic symmetry breaker
        ramp_t = jnp.asarray(
            (1.0 + np.arange(T) / max(1, T - 1))[:, None], dt)
        alpha_t = jnp.asarray(alpha, dt) * ramp_t
        scal = dict(hot_frac=jnp.asarray(hot_frac, dt),
                    w_cap=jnp.asarray(w_cap, dt),
                    cap_beta=jnp.asarray(cap_beta, dt),
                    cap_frac=jnp.asarray(cap_frac, dt))
        # per-tree node-indexed per-link constants (host reference) and their
        # slot-indexed twins (device loop) — same value per real link, so the
        # two paths' elementwise updates agree bitwise
        if rho_weighted:
            link_w_node = np.zeros((N, f.n_max))
            for g, tr in enumerate(trees):
                link_w_node[g, : tr.n] = tr.rho
            link_w_node = jnp.asarray(link_w_node, dt)
            link_w_slot = base_edge[jnp.asarray(rep)]          # (N, S)
            core_link_w = jnp.asarray(lay.core_rho, dt)
        else:
            link_w_node = jnp.ones((N, f.n_max), dt)
            link_w_slot = jnp.ones((N, f.n_slots), dt)
            core_link_w = jnp.ones((C,), dt)
        cap_node = np.ones((N, f.n_max))
        cap_slot = np.ones((N, f.n_slots))
        if priced:
            for g in range(N):
                cap_node[g, : trees[g].n] = capacity[g]
                sn_g = f.slot_node[rep[g]]
                cap_slot[g] = np.where(sn_g >= 0,
                                       cap_node[g][np.maximum(sn_g, 0)], 1.0)
        cap_node = jnp.asarray(cap_node, dt)
        cap_slot = jnp.asarray(cap_slot, dt)
        # residual ledger twins (node for the host replay, slot for the device
        # rank truncation) — padding slots read T so they can never reject
        res_slot_np = np.full((N, f.n_slots), T, np.int64)
        if admit:
            res_node_np = np.zeros((N, f.n_max), np.int64)
            for g in range(N):
                res_node_np[g, : trees[g].n] = residual[g]
                sn_g = f.slot_node[rep[g]]
                res_slot_np[g] = np.where(
                    sn_g >= 0, res_node_np[g][np.maximum(sn_g, 0)], T)
        res_slot = jnp.asarray(res_slot_np, jnp.int32)
        tree_id = jnp.asarray(lay.tree_of)
        core_base = jnp.asarray(lay.core_rho, dt)              # (C,)
        core_on = jnp.asarray(lay.core_inc)                    # (T, C) bool

    if device_loop:
        with telemetry.span("engine.loop"):
            state = _run_device(f, lay, k, opts, use_pallas, kid, load,
                                send, avail_d, par, cidx, root_d, base_edge,
                                anc, valid, tree_id, link_w_slot, cap_slot,
                                res_slot, core_base, core_on, core_link_w,
                                alpha_t, ramp_t, scal, patience, max_rounds,
                                record_rounds, priced, admit)
    else:
        state = _run_host(trees, loads, tid_np, avails, f, lay, k, opts,
                          link_w_node, cap_node, residual, core_base,
                          core_on, core_link_w, alpha_t, ramp_t, scal,
                          patience, max_rounds, record_rounds, priced,
                          admit)
    (blue_node, best_round, rounds, history, prof0_node, prof0_core,
     rounds_log, bytes_to_host, best_drop, admission_log) = state

    with telemetry.span("engine.remeasure"):
        n_big = int(lay.tree_n.max())
        blue = blue_node[:, :n_big]
        # the reported statistics come from the one shared measurement recipe
        # (measure_fleet_multi — same code path the orchestrator's
        # post-admission re-measure uses); its host sweep is bit-identical to
        # the device messages the loop tracked, so nothing shifts in the
        # hand-off
        m = measure_fleet_multi(
            trees, tid_np, list(loads),
            [blue[t, : trees[int(tid_np[t])].n] for t in range(T)],
            core_rho=lay.core_rho if C else None,
            core_path=lay.core_path if C else None,
            rho_weighted=rho_weighted)
        parts = [prof0_node[g, : trees[g].n] for g in range(N)]
        if C:
            parts.append(prof0_core)
        base0 = np.concatenate(parts)
        base0 = base0[base0 > 0]
        admission_dropped = residual_after = None
        if admit:
            admission_dropped = np.asarray(best_drop, np.int64)
            residual_after = []
            for g in range(N):
                claims = np.zeros(trees[g].n, np.int64)
                for t in range(T):
                    if int(tid_np[t]) == g:
                        claims += blue[t, : trees[g].n].astype(np.int64)
                residual_after.append(residual[g] - claims)
    return CongestionResult(
        blue=blue, costs=m.costs, msgs=m.msgs, congestion=m.congestion,
        max_congestion=m.max_congestion,
        mean_congestion=m.mean_congestion,
        baseline_max=float(history[0]),
        baseline_mean=float(base0.astype(np.float64).mean())
        if base0.size else 0.0,
        rounds=rounds, best_round=best_round, history=history,
        rounds_log=rounds_log, bytes_to_host=bytes_to_host,
        tree_of=tid_np.copy(), core_congestion=m.core_congestion,
        admission_dropped=admission_dropped, residual_after=residual_after,
        admission_log=admission_log)


def solve_congestion(
    tree: Tree,
    loads: Sequence[np.ndarray],
    k: int,
    avail: Sequence[np.ndarray | None] | np.ndarray | None = None,
    *,
    max_rounds: int = 8,
    patience: int = 2,
    alpha: float = 2.0,
    hot_frac: float = 0.75,
    w_cap: float = 8.0,
    rho_weighted: bool = False,
    capacity: np.ndarray | None = None,
    cap_beta: float = 1.0,
    cap_frac: float = 0.75,
    residual: np.ndarray | None = None,
    record_rounds: bool = False,
    device_loop: bool = True,
    options: EngineOptions | None = None,
    **engine_kw,
) -> CongestionResult:
    """Minimize max-link congestion for T tenants sharing ``tree``.

    ``loads``: one (n,) load vector per tenant. ``avail``: a single mask
    shared by all tenants, a per-tenant sequence, or None. ``alpha``
    scales the penalty (each tenant t uses a deterministic ramp
    ``alpha * (1 + t/(T-1))`` — the symmetry breaker for identical
    tenants); links hotter than ``hot_frac * C_max`` are penalized;
    per-link weights are capped at ``w_cap`` and quantized to
    :data:`W_QUANTUM`. ``rho_weighted=True`` measures congestion in
    transmission time (``msg * rho``) instead of raw message counts.

    ``capacity`` (n,) switches on *capacity pricing*: links whose switch
    has blue claims from at least ``cap_frac`` of its per-switch capacity
    this round are priced up (factor ``1 + cap_beta * ramp_t *
    usage/capacity``) jointly with the hot-link boost, for the tenants
    sitting on them — steering the fleet away from switches the
    orchestrator is about to run out of.

    ``residual`` (n,) switches on **hard in-loop admission**: an integer
    per-switch claim ledger the returned placements are guaranteed
    feasible against — every round's candidate blues are truncated to the
    claims the ledger covers (in tenant order, exactly a sequential
    ledger replay) and rejected (tenant, switch) pairs are banned for the
    rest of the loop. ``admission_dropped`` / ``residual_after`` on the
    result report the best round's shortfall and remaining capacity.

    ``device_loop=True`` (default) runs the whole loop on the
    accelerator (one jitted ``lax.while_loop``; O(1) host transfer
    total); ``device_loop=False`` is the host-driven parity reference —
    identical arithmetic, per-round transfers (see module docstring).
    Engine behavior comes from ``options=EngineOptions(...)``;
    ``color=False`` and ``debug_tables=True`` are rejected — the driver
    needs on-device masks. Runs at most ``max_rounds`` solves, stopping
    early after ``patience`` rounds without improvement; the returned
    placement is the best round seen, so the result is never worse than
    the utilization-only baseline (round 0).

    This IS the fleet driver: structurally the degenerate single-tree,
    no-core call of :func:`solve_fleet` — same packing, same loop, same
    arithmetic — which is what keeps the two bit-identical.
    """
    T = len(loads)
    if T == 0:
        raise ValueError("solve_congestion needs at least one tenant")
    # resolve here so errors cite the entry point the caller actually used
    opts = resolve_options(options, engine_kw, "solve_congestion")
    n = tree.n
    if avail is None or isinstance(avail, np.ndarray):
        avails = [avail] * T
    else:
        avails = list(avail)
        if len(avails) != T:
            raise ValueError(f"{len(avails)} avail masks for {T} tenants")
    if capacity is not None:
        capacity = np.asarray(capacity, np.float64)
        if capacity.shape != (n,):
            raise ValueError(f"capacity shape {capacity.shape} != ({n},)")
        capacity = [capacity]
    if residual is not None:
        residual = np.asarray(residual)
        if residual.shape != (n,):
            raise ValueError(f"residual shape {residual.shape} != ({n},)")
        residual = [residual]
    return solve_fleet(
        [tree], loads, [0] * T, k, avails,
        max_rounds=max_rounds, patience=patience, alpha=alpha,
        hot_frac=hot_frac, w_cap=w_cap, rho_weighted=rho_weighted,
        capacity=capacity, cap_beta=cap_beta, cap_frac=cap_frac,
        residual=residual, record_rounds=record_rounds,
        device_loop=device_loop, options=opts)


def _slots_to_nodes_np(x_slot: np.ndarray, f, rows=None) -> np.ndarray:
    """Host twin of the engine's slot->node gather (padding reads 0).

    ``rows`` selects which batch rows' ``slot_of`` maps apply — the fleet
    driver maps its (N, S) per-tree profiles through each tree's
    representative tenant row.
    """
    slot_of = f.slot_of if rows is None else f.slot_of[rows]
    B = x_slot.shape[0]
    pad = np.concatenate(
        [x_slot, np.zeros((B, 1), x_slot.dtype)], axis=1)
    return np.take_along_axis(pad, slot_of, axis=1)


def _run_device(f, lay, k, opts, use_pallas, kid, load, send, avail_d, par,
                cidx, root_d, base_edge, anc, valid, tree_id, link_w_slot,
                cap_slot, res_slot, core_base, core_on, core_link_w, alpha_t,
                ramp_t, scal, patience, max_rounds, record_rounds, priced,
                admit):
    """Dispatch the resident loop; pull the final state once. Counts the
    loop in ``penalty.loops`` and its rounds in ``penalty.rounds``."""
    n_big = int(lay.tree_n.max())
    out = _device_driver(
        kid, load, send, avail_d, par, cidx, root_d,
        base_edge, anc, valid, tree_id, link_w_slot, cap_slot, res_slot,
        core_base, core_on, core_link_w, alpha_t, ramp_t,
        scal["hot_frac"], scal["w_cap"], scal["cap_beta"], scal["cap_frac"],
        jnp.int32(patience),
        lvl_off=f.lvl_off, lvl_width=f.lvl_width,
        lvl_internal=f.lvl_internal, lvl_sub=f.lvl_sub,
        k=k, cap=bool(opts.cap), use_pallas=bool(use_pallas),
        interpret=bool(opts.interpret), max_rounds=int(max_rounds),
        record=bool(record_rounds), priced=priced, admit=admit,
        n_trees=int(lay.n_trees))
    (best_blue_s, best_round_d, rounds_d, hist_d, prof0_s, prof0c_d,
     best_drop_d, log_rho, log_blue, log_drop) = \
        (np.asarray(x) for x in out)
    bytes_to_host = sum(int(x.nbytes) for x in
                        (best_blue_s, best_round_d, rounds_d, hist_d,
                         prof0_s, prof0c_d, best_drop_d, log_rho, log_blue,
                         log_drop))
    rounds = int(rounds_d)
    telemetry.count("penalty.loops")
    telemetry.count("penalty.rounds", rounds)
    best_round = int(best_round_d)
    history = [float(c) for c in hist_d[:rounds]]
    blue_node = _slots_to_nodes_np(best_blue_s, f)
    prof0_node = _slots_to_nodes_np(prof0_s, f, rows=lay.rep)
    rounds_log = None
    if record_rounds:
        rounds_log = []
        for r in range(rounds):
            rho_eff = _slots_to_nodes_np(
                log_rho[r], f).astype(np.float64)[:, :n_big]
            rounds_log.append(
                (rho_eff, _slots_to_nodes_np(log_blue[r], f)[:, :n_big]))
    admission_log = None
    if admit and record_rounds:
        admission_log = [log_drop[r].astype(np.int64) for r in range(rounds)]
    return (blue_node, best_round, rounds, history, prof0_node, prof0c_d,
            rounds_log, bytes_to_host, best_drop_d.astype(np.int64),
            admission_log)


def _run_host(trees, loads, tid_np, avails, f, lay, k, opts, link_w_node,
              cap_node, residual, core_base, core_on, core_link_w, alpha_t,
              ramp_t, scal, patience, max_rounds, record_rounds, priced,
              admit):
    """Host-driven parity reference: one round per step, everything pulled.

    Runs the *same* jitted round arithmetic as the device loop — the
    solve goes through the public :func:`~repro.engine.solve_forest`
    ``rho_scale`` / ``rho_root_add`` overrides (node-indexed weights plus
    the shared-core root extension), measurement and reweight through the
    shared jitted :func:`_round_penalty` — but the loop control, best
    tracking and history live on the host, and each round retains the
    PR 3 driver's serving pattern: re-pack the Forest, re-upload the
    packed arrays, pull the masks, message counts and C_max back down
    (the transfer/packing bill the device loop exists to eliminate; the
    rebuilt arrays are bit-identical, so parity is unaffected).

    With ``admit`` each round replays a literal sequential per-tree
    ledger in tenant order — the admission the device loop's one-hot
    cumsum rank computes in one shot — and persists rejections into
    ``avails`` so the next round's rebuilt Forest excludes them.
    """
    from ..core.congestion import messages_up_forest
    from .batched import solve_forest

    T, n_max = f.mask.shape
    N = int(lay.n_trees)
    C = int(lay.n_core)
    n_big = int(lay.tree_n.max())
    dt = np.dtype(opts.dtype)
    base_edge_node = jnp.asarray(
        np.where(np.isfinite(f.rho_up[:, :, 1]), f.rho_up[:, :, 1], 0.0), dt)
    root_idx = jnp.asarray(f.root)
    tree_id = jnp.asarray(lay.tree_of)
    w = jnp.ones((T, n_max), dt)
    wc = jnp.ones((T, C), dt)
    best = None                     # (cmax, round, blue, drop)
    history: list[float] = []
    rounds_log: list | None = [] if record_rounds else None
    admission_log: list | None = \
        [] if (admit and record_rounds) else None
    prof0_node = prof0_core = None
    bytes_to_host = 0
    stale = 0
    rounds = 0
    for r in range(max_rounds):
        fr = build_forest([trees[g] for g in tid_np], list(loads),
                          avails)                           # PR 3: per round
        if C:
            extra = _core_extra_step(core_base, wc, core_on.astype(dt))
            res = solve_forest(fr, k, options=opts, rho_scale=w,
                               rho_root_add=extra)
        else:
            extra = None
            res = solve_forest(fr, k, options=opts, rho_scale=w)
        blue = res.blue
        bytes_to_host += res.bytes_to_host
        drop = np.zeros(T, np.int64)
        banned = False
        if admit:
            # the sequential ledger the device one-hot cumsum reproduces:
            # claims replayed in tenant order against a fresh per-round
            # copy of the residual; rejections ban the (tenant, switch)
            # pair from every later round via the avail masks
            blue = blue.copy()
            ledger = [rg.copy() for rg in residual]
            for t in range(T):
                g = int(tid_np[t])
                led = ledger[g]
                for v in np.nonzero(blue[t, : trees[g].n])[0]:
                    if led[v] > 0:
                        led[v] -= 1
                    else:
                        blue[t, v] = False
                        avails[t][v] = False
                        drop[t] += 1
                        banned = True
        msgs64 = messages_up_forest(fr, blue)
        msgs = jnp.asarray(msgs64.astype(np.int32))
        bytes_to_host += msgs.nbytes
        prof_tree, prof_core, cmax_d, w2, wc2 = _penalty_step(
            w, wc, msgs, jnp.asarray(blue), root_idx, tree_id, link_w_node,
            core_link_w, core_on, cap_node, alpha_t, ramp_t,
            scal["hot_frac"], scal["w_cap"], scal["cap_beta"],
            scal["cap_frac"], n_trees=N, priced=priced)
        cmax = float(cmax_d)
        bytes_to_host += 4
        history.append(cmax)
        rounds = r + 1
        if r == 0:
            prof0_node = np.asarray(prof_tree)
            prof0_core = np.asarray(prof_core)
            bytes_to_host += prof0_node.nbytes + prof0_core.nbytes
        if record_rounds:
            rho_eff = np.asarray(
                _edge_scale_core(base_edge_node, w, extra, root_idx)
                if C else _edge_scale(base_edge_node, w))
            bytes_to_host += rho_eff.nbytes
            rounds_log.append((rho_eff.astype(np.float64)[:, :n_big],
                               blue[:, :n_big].copy()))
        if admission_log is not None:
            admission_log.append(drop.copy())
        if best is None or cmax < best[0]:           # strict: earliest wins
            best = (cmax, r, blue, drop)
            stale = 0
        else:
            stale += 1
        # a round that banned something changed the search landscape under
        # the loop — it never counts toward the patience stop
        if cmax == 0 or (stale >= patience and not banned):
            break
        w, wc = w2, wc2
    _, best_round, blue_node, best_drop = best
    return (blue_node, best_round, rounds, history, prof0_node, prof0_core,
            rounds_log, bytes_to_host, best_drop, admission_log)
