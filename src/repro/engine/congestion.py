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
reweights over the *union* of tree and shared-core links inside the
same loop: tree-link profiles come from a scatter-add of every tenant's
counts onto *physical* link ids (trees of a multi-rooted fabric share
switches and links, see ``Fleet.switch_id``), core profiles from each tenant's root-crossing count summed over the tenants
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

**Path choice.** With ``candidates=`` each tenant may aggregate on any of
several node-aligned trees (a pod's paths through a fat-tree), and the
loop chooses: :func:`_device_paths` solves every (tenant, candidate) row
in one batched fold per round and iterates the sequential admission to
its fixed point (see there). One candidate per tenant is
:func:`_device_driver`, the loop described here.

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

**In-loop hard admission.** ``residual=`` hands the driver the
orchestrator's integer claim ledger (per physical switch, or per tree) and
turns capacity from a *price* into a *constraint inside the loop*: every
round, each tenant's candidate blue set is truncated to the claims the
residual actually covers (claims are ranked per switch in tenant order —
exactly the order a host ledger would replay them) and the rejected
(tenant, switch) pairs are *banned* through the existing ``avail``
mechanics, so the next round's DP routes those tenants elsewhere. The
loop therefore converges directly to placements a per-switch ledger can
admit wholesale — no host round-trip per admission, no post-hoc
eviction. The device loop computes claim ranks with an exact integer
cumsum over a (tenants, switches) claim matrix; the host reference replays a literal sequential numpy
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
    tree_of: np.ndarray | None = None    # (T,) tenant -> the tree it was
                                         # given (its choice, with paths)
    core_congestion: np.ndarray | None = None  # (C,) shared-core profile
    # -- hard admission (residual=...) only --
    admission_dropped: np.ndarray | None = None  # (T,) int64 claims the
                                                 # best round could not admit
                                                 # (path choice: the first)
    residual_after: list | np.ndarray | None = None  # int64 residual
                                         # ledgers after the best round's
                                         # claims: per tree, or per
                                         # physical switch
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

def _profile(msgs: jax.Array, link_w: jax.Array, ln: jax.Array) -> jax.Array:
    """Per-link congestion: int32 counts scatter-added onto physical link
    ids, then weighted (``link_w`` is (links + 1,) — the original per-link
    rho when rho_weighted, else 1 — its last entry the dummy link every
    padding slot maps to). Integer scatter-add is exact and order-free,
    so the result does not depend on which trees carried the counts."""
    counts = jnp.zeros(link_w.shape, msgs.dtype).at[ln].add(msgs)
    return counts.astype(link_w.dtype) * link_w


def _crowding(blue: jax.Array, sw: jax.Array, blue_c: jax.Array,
              sw_c: jax.Array, capacity: jax.Array, cap_frac) -> jax.Array:
    """Capacity-pricing term: per-row (R, slots) pressure on crowded
    physical switches (zero elsewhere). Usage counts the tenants' chosen
    claims (``blue_c`` at physical switches ``sw_c``); every row whose
    mask sits on a crowded switch is priced."""
    counts = jnp.zeros(capacity.shape, jnp.int32).at[sw_c].add(
        blue_c.astype(jnp.int32))
    usage = counts[sw].astype(capacity.dtype)
    pressure = usage / jnp.maximum(capacity[sw], 1e-6)
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


def _admit_ranked(blue, sw, residual):
    """Hard-admission truncation of one round's candidate blue sets.

    A claim by tenant t on physical switch ``sw[t, s]`` is admitted iff
    fewer than ``residual[sw[t, s]]`` lower-indexed tenants also claim
    that switch this round, whichever tree they claim it through — the
    exact set a sequential ledger replay in tenant order admits. Ranks
    come from an integer cumsum over a (T, switches) claim matrix:
    O(T * switches), exact and order-free per element, so the device loop
    and the host ledger reference agree bitwise. Padding slots map to the
    ledger's last entry, which holds T and never rejects. Returns
    ``(admitted, rejected)`` bool (T, slots) masks.
    """
    T = blue.shape[0]
    claims = jnp.zeros((T, residual.shape[0]), jnp.int32).at[
        jnp.arange(T)[:, None], sw].add(blue.astype(jnp.int32))
    rank = jnp.take_along_axis(jnp.cumsum(claims, axis=0), sw, axis=1)
    admitted = blue & (rank <= residual[sw])
    return admitted, blue & ~admitted


def _round_penalty(w, wc, msgs, blue, root_idx, ln, sw, core_on, msgs_c,
                   blue_c, root_c, ln_c, sw_c, core_on_c, link_w,
                   core_link_w, capacity, alpha_t, ramp_t, hot_frac, w_cap,
                   cap_beta, cap_frac, *, priced: bool):
    """Profile the union of physical tree links and shared-core links,
    then apply one penalty update to both weight matrices.

    The profile sums the tenants' *chosen* trees (``msgs_c``, ``blue_c``
    and the ``_c`` maps, one row per tenant); the update prices every
    solved row (``msgs``, ``blue``: a tenant's candidate trees, or just
    its tree when it has one), so a candidate that would cross a hot link
    costs more next round. ``ln`` / ``sw``: physical link / switch of
    each row's slots; ``root_idx``: each row's root column (its
    root-crossing count is the core transit); ``core_on``: (rows, C) bool
    incidence. Returns ``(prof (links + 1,), prof_core (C,), cmax, w',
    wc')`` — C_max is the max over *all* links, tree and core jointly, so
    a hot shared core link dominates the stop/best tracking and the
    hot-link threshold exactly like a hot tree link.
    """
    prof = _profile(msgs_c, link_w, ln_c)
    cmax = prof.max()
    C = wc.shape[1]
    if C:
        root_c_msgs = jnp.take_along_axis(msgs_c, root_c[:, None], axis=1)
        core_c_msgs = root_c_msgs * core_on_c.astype(msgs_c.dtype)
        prof_core = (core_c_msgs.sum(axis=0).astype(core_link_w.dtype)
                     * core_link_w)
        cmax = jnp.maximum(cmax, prof_core.max())
        if msgs is msgs_c:
            core_msgs = core_c_msgs
        else:
            root_msgs = jnp.take_along_axis(msgs, root_idx[:, None], axis=1)
            core_msgs = root_msgs * core_on.astype(msgs.dtype)  # (rows, C)
    else:
        prof_core = jnp.zeros((0,), w.dtype)
    prof_t = prof[ln]                                     # (rows, slots)
    link_w_t = link_w[ln]
    crowd = (_crowding(blue, sw, blue_c, sw_c, capacity, cap_frac)
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
    return prof, prof_core, cmax, w2, wc2


def _choose(phi: jax.Array, eff: jax.Array, rot: jax.Array) -> jax.Array:
    """Each tenant's candidate: least utilization ``phi`` (T, P), then
    least penalized cost ``eff``, then the first in the tenant's rotated
    order (candidate ``rot[t]`` first). Returns (T,) int32."""
    P = phi.shape[1]
    least = phi == phi.min(axis=1, keepdims=True)
    e = jnp.where(least, eff, jnp.inf)
    tie = least & (e == e.min(axis=1, keepdims=True))
    order = (jnp.arange(P)[None, :] - rot[:, None]) % P
    return jnp.where(tie, order, P).argmin(axis=1).astype(jnp.int32)


_penalty_step = functools.partial(
    jax.jit, static_argnames=("priced",))(_round_penalty)

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

_LOOP_STATIC = ("lvl_off", "lvl_width", "lvl_internal", "lvl_sub", "k",
                "cap", "use_pallas", "interpret", "max_rounds", "record",
                "priced", "admit")


@functools.partial(jax.jit, static_argnames=_LOOP_STATIC)
def _device_driver(
    kid, load, send, avail, par, cidx, root_slot,     # packed solve inputs
    base_edge, anc, valid,                            # rho-override inputs
    ln, sw,                                           # (T,S) physical ids
    link_w, capacity,                                 # (L+1,), (W+1,)
    residual,                                         # (W+1,) int32 ledger
    core_base, core_on, core_link_w,                  # (C,), (T,C), (C,)
    alpha_t, ramp_t,                                  # (T, 1) tenant ramps
    hot_frac, w_cap, cap_beta, cap_frac, patience,    # scalars
    *,
    lvl_off, lvl_width, lvl_internal, lvl_sub, k, cap, use_pallas,
    interpret, max_rounds: int, record: bool, priced: bool, admit: bool,
):
    """The whole penalty loop as one ``lax.while_loop`` on the accelerator,
    for tenants with one tree each.

    Per round: shared-core root extension + device rho-up recompute ->
    fused level-fold gather -> on-device color (slot-indexed masks, no
    node gather) -> messages-up sweep -> shared profile/reweight over the
    union of physical tree links and core links -> monotone-best
    tracking. The carry holds both weight matrices (tree links and core
    links), best-so-far masks, the scalar history and (when ``record``)
    the per-round logs; nothing crosses the host boundary until the
    caller pulls the final tuple.

    With ``admit`` the carry also owns the availability masks: each
    round's candidate blues are truncated to what the physical ledger
    ``residual`` covers (:func:`_admit_ranked`) and rejected claims ban
    their (tenant, switch) pair from every later round, so the loop
    converges to placements the ledger admits outright. A round that
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
            blue, rejected = _admit_ranked(blue, sw, residual)
            avail = avail & ~rejected              # persistent in-loop ban
            banned = rejected.any()
            drop = rejected.sum(axis=1).astype(jnp.int32)
        else:
            banned = jnp.asarray(False)
            drop = jnp.zeros((T,), jnp.int32)
        msgs = _messages_body(
            kid, load, send, blue,
            lvl_off=lvl_off, lvl_width=lvl_width, lvl_internal=lvl_internal)
        prof, prof_core, cmax, w2, wc2 = _round_penalty(
            w, wc, msgs, blue, root_slot, ln, sw, core_on, msgs, blue,
            root_slot, ln, sw, core_on, link_w, core_link_w, capacity,
            alpha_t, ramp_t, hot_frac, w_cap, cap_beta, cap_frac,
            priced=priced)
        history = history.at[r].set(cmax)
        prof0 = jnp.where(r == 0, prof, prof0)
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
            jnp.full((max_rounds,), -1.0, dt), jnp.zeros(link_w.shape, dt),
            jnp.zeros((C,), dt),
            jnp.zeros((Rl, T, S), dt), jnp.zeros((Rl, T, S), bool),
            jnp.zeros((Rl, T), jnp.int32))
    out = jax.lax.while_loop(cond, body, init)
    (r, _, _, _, _, _, best_cmax, best_blue, best_round, best_drop, history,
     prof0, prof0c, log_rho, log_blue, log_drop) = out
    return best_blue, best_round, r, history, prof0, prof0c, best_drop, \
        log_rho, log_blue, log_drop


@functools.partial(jax.jit, static_argnames=_LOOP_STATIC)
def _device_paths(
    kid, load, send, avail, par, cidx, root_slot,     # (R = T*P) rows
    rho0, base_edge, anc, valid,                      # original + override
    ln, sw,                                           # (R,S) physical ids
    link_w, capacity, residual,                       # physical, as above
    core_base, core_on, core_link_w,                  # (C,), (R,C), (C,)
    alpha_t, ramp_t,                                  # (R, 1) tenant ramps
    rot,                                              # (T,) first candidate
    hot_frac, w_cap, cap_beta, cap_frac, patience,    # scalars
    *,
    lvl_off, lvl_width, lvl_internal, lvl_sub, k, cap, use_pallas,
    interpret, max_rounds: int, record: bool, priced: bool, admit: bool,
):
    """The path-choice loop: T tenants, P candidate trees each, rows
    ``t * P + c``, as one ``lax.while_loop`` on the accelerator.

    Each round is one step of the sequential admission, for every tenant
    at once: a tenant sees the physical slots that the *previous* round's
    admitted claims of the tenants before it left, solves all its
    candidates on the original rates (one batched level fold over every
    row), and takes the candidate of least utilization, then least
    penalized cost, then first in its rotated order (:func:`_choose`).
    Claims are ranked per physical switch in tenant order
    (:func:`_admit_ranked`), so every round's placements are feasible.
    Penalty weights price the rows as in :func:`_device_driver` until
    ``patience`` rounds pass without a lower C_max (or ``max_rounds``
    have run), then freeze. The loop stops at the first round that repeats
    the last one's choices and masks with nothing rejected: a fixed point,
    where each tenant's utilization is the least that any of its
    candidates allows given what the tenants before it claimed. Once the
    weights are frozen, round r fixes tenants 0..r-f, so the fixed point
    comes within ``max_rounds + T + 1`` rounds, the loop's bound.

    The fixed point rejects nothing, so the shortfall it reports is the
    first round's: the claims each tenant's first choice could not get,
    every tenant having seen the whole ledger (what preemption frees).
    """
    Rn, S, _ = kid.shape
    T = rot.shape[0]
    P = Rn // T
    dt = base_edge.dtype
    C = core_base.shape[0]
    W = residual.shape[0]
    bound = max_rounds + T + 1
    ten = jnp.arange(T)
    row_t = jnp.repeat(ten, P)

    def body(carry):
        (r, w, wc, stale, frozen, stop, best_cmax, claims, choice, blue_c,
         drop0, choice0, history, prof0, prof0c, log_rho, log_blue,
         log_drop) = carry
        with jax.named_scope("paths"):
            # slots left to each tenant by the tenants before it
            left = residual[None, :] - (jnp.cumsum(claims, axis=0) - claims)
            av = avail & (left[row_t[:, None], sw] > 0)
            blocks = _gather_packed(
                kid, load, send, av, rho0,
                lvl_off=lvl_off, lvl_width=lvl_width,
                lvl_internal=lvl_internal, lvl_sub=lvl_sub,
                k=k, cap=cap, use_pallas=use_pallas, interpret=interpret)
            blue, phi = _color_body(
                blocks, kid, par, cidx, load, send, av, rho0, root_slot,
                lvl_off=lvl_off, lvl_width=lvl_width,
                lvl_internal=lvl_internal, lvl_sub=lvl_sub, k=k, cap=cap)
            msgs = _messages_body(
                kid, load, send, blue, lvl_off=lvl_off, lvl_width=lvl_width,
                lvl_internal=lvl_internal)
            if C:
                extra = _core_extra(core_base, wc, core_on.astype(dt))
                edges = scaled_edges(base_edge, w, extra, root_slot)
            else:
                edges = scaled_edges(base_edge, w)
            eff = (msgs.astype(dt) * edges).sum(axis=1)
            pick = _choose(phi.reshape(T, P), eff.reshape(T, P), rot)
            idx = ten * P + pick
            new_blue = blue[idx]
            sw_c = sw[idx]
        if admit:
            new_blue, rejected = _admit_ranked(new_blue, sw_c, residual)
            new_drop = rejected.sum(axis=1).astype(jnp.int32)
        else:
            new_drop = jnp.zeros((T,), jnp.int32)
        msgs_c = _messages_body(
            kid[idx], load[idx], send[idx], new_blue, lvl_off=lvl_off,
            lvl_width=lvl_width, lvl_internal=lvl_internal)
        prof, prof_core, cmax, w2, wc2 = _round_penalty(
            w, wc, msgs, blue, root_slot, ln, sw, core_on, msgs_c, new_blue,
            root_slot[idx], ln[idx], sw_c, core_on[idx], link_w,
            core_link_w, capacity, alpha_t, ramp_t, hot_frac, w_cap,
            cap_beta, cap_frac, priced=priced)
        history = history.at[r].set(cmax)
        prof0 = jnp.where(r == 0, prof, prof0)
        prof0c = jnp.where(r == 0, prof_core, prof0c)
        if record:
            log_rho = log_rho.at[r].set(edges[idx])
            log_blue = log_blue.at[r].set(new_blue)
            log_drop = log_drop.at[r].set(new_drop)
        better = cmax < best_cmax
        best_cmax = jnp.where(better, cmax, best_cmax)
        stale = jnp.where(better, 0, stale + 1)
        frozen = frozen | (stale >= patience) | (r + 1 >= max_rounds)
        w = jnp.where(frozen, w, w2)
        wc = jnp.where(frozen, wc, wc2)
        stop = ((pick == choice).all() & (new_blue == blue_c).all()
                & (new_drop == 0).all())
        claims = jnp.zeros((T, W), jnp.int32).at[ten[:, None], sw_c].add(
            new_blue.astype(jnp.int32))
        choice0 = jnp.where(r == 0, pick, choice0)
        drop0 = jnp.where(r == 0, new_drop, drop0)
        return (r + 1, w, wc, stale, frozen, stop, best_cmax, claims, pick,
                new_blue, drop0, choice0, history, prof0, prof0c, log_rho,
                log_blue, log_drop)

    def cond(carry):
        return (carry[0] < bound) & ~carry[5]

    Rl = bound if record else 0
    init = (jnp.int32(0), jnp.ones((Rn, S), dt), jnp.ones((Rn, C), dt),
            jnp.int32(0), jnp.asarray(False), jnp.asarray(False),
            jnp.asarray(jnp.inf, dt), jnp.zeros((T, W), jnp.int32),
            jnp.full((T,), -1, jnp.int32), jnp.zeros((T, S), bool),
            jnp.zeros((T,), jnp.int32), jnp.zeros((T,), jnp.int32),
            jnp.full((bound,), -1.0, dt), jnp.zeros(link_w.shape, dt),
            jnp.zeros((C,), dt),
            jnp.zeros((Rl, T, S), dt), jnp.zeros((Rl, T, S), bool),
            jnp.zeros((Rl, T), jnp.int32))
    out = jax.lax.while_loop(cond, body, init)
    (r, _, _, _, _, _, _, _, choice, blue_c, drop0, choice0, history, prof0,
     prof0c, log_rho, log_blue, log_drop) = out
    return (blue_c, r - 1, r, history, prof0, prof0c, drop0, log_rho,
            log_blue, log_drop, choice, choice0)


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
    candidates: np.ndarray | None = None,
    switch_id: Sequence[np.ndarray] | None = None,
    link_id: Sequence[np.ndarray] | None = None,
    max_rounds: int = 8,
    patience: int = 2,
    alpha: float = 2.0,
    hot_frac: float = 0.75,
    w_cap: float = 8.0,
    rho_weighted: bool = False,
    capacity: Sequence[np.ndarray] | np.ndarray | None = None,
    cap_beta: float = 1.0,
    cap_frac: float = 0.75,
    residual: Sequence[np.ndarray] | np.ndarray | None = None,
    record_rounds: bool = False,
    device_loop: bool = True,
    options: EngineOptions | None = None,
    **engine_kw,
) -> CongestionResult:
    """Minimize max-link congestion for T tenants across a multi-tree fleet.

    ``trees``: the N aggregation trees; ``tree_of[t]`` names tenant t's
    (home) tree;
    ``loads``: one load vector per tenant, shaped for its home tree.
    ``core_rho`` / ``core_path`` describe the shared core (see
    :class:`repro.collectives.topology.Fleet`): a tenant's root-crossing
    messages transit every core link on its tree's path, the per-link
    profile spans the union of tree and core links, and core penalties
    feed back as additive root-edge extensions — tenants on different
    trees trade placements through the shared links.

    **Physical ids.** ``switch_id[g]`` / ``link_id[g]`` (each (n_g,)
    ints, dense from 0) name the physical switch of each node of tree g
    and the physical link of its up-link; trees that share a switch or a
    link give it one id. Congestion is summed per physical link and
    capacity claimed per physical switch. Without them every tree owns
    its switches and links (segment ids in tree order).

    **Path choice.** ``candidates`` (T, P) lists the trees tenant t may
    aggregate on, node-aligned with its home tree (see
    ``Fleet.groups``); the tree is the loop's own choice, made in every
    round, and comes back in ``tree_of`` of the result. Semantics, with
    a ledger (``residual``): admission order is tenant order; a claim on
    a switch is admitted while that *physical* switch has a free slot,
    whichever candidate tree it comes through; each admitted tenant's
    utilization is the least that any of its candidates allows with the
    slots the tenants admitted before it left; among candidates of equal
    utilization the loop chooses by its penalized link costs (see
    :func:`_device_paths`). Path choice runs in the device loop only.
    With one candidate per tenant the loop is the one-tree-per-tenant
    loop below, answer for answer.

    ``avail``: a per-tenant sequence of masks (or None), applied to every
    candidate. ``capacity`` switches on capacity pricing and ``residual``
    on **hard in-loop admission**: integer claim ledgers the returned
    placements are feasible against wholesale — every round's candidate
    blues are truncated to the claims the ledger covers and rejected
    claims ban their (tenant, switch) pair via the ``avail`` mechanics
    (``admission_dropped`` / ``residual_after`` on the result report the
    best round's shortfall and remaining capacity). Both are one vector
    per physical switch when ``switch_id`` is given, else one per tree
    (len N); ``residual_after`` comes back in the same form. Zero-residual
    and zero-capacity switches leave every affected tenant's candidate
    set up front. All other knobs as :func:`solve_congestion`, which is
    the degenerate ``N=1, C=0`` call of this driver.
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
        tid_np = np.asarray(list(tree_of), np.int32)
        if tid_np.shape != (T,):
            raise ValueError(f"tree_of shape {tid_np.shape} != ({T},)")
        if avail is None:
            avails = [None] * T
        else:
            avails = list(avail)
            if len(avails) != T:
                raise ValueError(f"{len(avails)} avail masks for {T} tenants")
        physical = switch_id is not None
        sw_ids, sw_tab, n_sw, ln_ids, ln_tab, n_ln = _physical_ids(
            trees, switch_id, link_id)
        priced = capacity is not None
        if priced:
            capacity = _ledger(capacity, trees, sw_ids, n_sw, physical,
                               "capacity", np.float64)
            bad = ~np.isfinite(capacity) | (capacity < 0)
            if bad.any():
                raise ValueError("capacity must be finite and non-negative "
                                 f"(switch {int(np.argmax(bad))})")
        admit = residual is not None
        if admit:
            raw = _ledger(residual, trees, sw_ids, n_sw, physical,
                          "residual", None)
            rf = raw.astype(np.float64)
            bad = ~np.isfinite(rf) | (rf != np.floor(rf))
            if bad.any():
                raise ValueError("residual ledger must be integer-valued "
                                 f"(switch {int(np.argmax(bad))})")
            residual = raw.astype(np.int64)
            if (residual < 0).any():
                raise ValueError("residual ledger must be non-negative "
                                 f"(switch {int(np.argmax(residual < 0))})")
        cand = None
        if candidates is not None:
            cand = np.asarray(candidates, np.int32)
            if cand.ndim != 2 or cand.shape[0] != T:
                raise ValueError(f"candidates shape {cand.shape} is not "
                                 f"({T}, P)")
            if not device_loop and cand.shape[1] > 1:
                raise ValueError("path choice runs in the device loop only "
                                 "(device_loop=True)")
            if cand.shape[1] == 1:
                tid_np, cand = cand[:, 0].copy(), None
        # hard unavailability flows through the avail mechanics: switches
        # with no residual (or no capacity at all) leave every tenant's
        # candidate set before the first solve
        hard = None
        if admit or priced:
            hp = np.ones(n_sw + 1, bool)
            if admit:
                hp[:n_sw] &= residual > 0
            if priced:
                hp[:n_sw] &= capacity > 0
            if not hp.all():
                hard = [hp[x] for x in sw_ids] if cand is None else hp
        if cand is None:
            if hard is not None:
                avails = [
                    (hard[g].copy() if a is None
                     else np.asarray(a, bool) & hard[g])
                    for a, g in zip(avails, tid_np)]
            if admit:
                # the host ledger replay mutates its per-tenant masks
                # (persistent bans) — every tenant needs its own copy
                avails = [np.ones(trees[g].n, bool) if a is None
                          else np.array(a, dtype=bool, copy=True)
                          for a, g in zip(avails, tid_np)]
            rows_avail = avails
        else:
            with telemetry.span("engine.paths"):
                rows_avail = _candidate_avail(cand, avails, hard, sw_tab,
                                              n_sw)
        use_pallas = pallas_fold(opts)

    # one Forest, one packing, one compiled executable for the whole loop
    f, lay = build_fleet_forest(trees, list(loads), tid_np, rows_avail,
                                core_rho=core_rho, core_path=core_path,
                                candidates=cand)
    with telemetry.span("engine.upload"):
        C = lay.n_core
        dt = opts.dtype
        kid, load, send, avail_d, rho0, par, cidx, _, root_d = \
            _device_inputs(f, dt)
        base_edge, anc, valid, _, _ = _override_inputs(f, dt)
        rows = lay.row_tree
        P = lay.paths

        # per-tenant penalty ramp: deterministic symmetry breaker
        ramp = np.repeat((1.0 + np.arange(T) / max(1, T - 1))[:, None], P,
                         axis=0)
        ramp_t = jnp.asarray(ramp, dt)
        alpha_t = jnp.asarray(alpha, dt) * ramp_t
        scal = dict(hot_frac=jnp.asarray(hot_frac, dt),
                    w_cap=jnp.asarray(w_cap, dt),
                    cap_beta=jnp.asarray(cap_beta, dt),
                    cap_frac=jnp.asarray(cap_frac, dt))
        # physical per-link / per-switch constants; the extra last entry
        # is the dummy every padding slot maps to (weight 0 or 1, capacity
        # 1, and a residual of T, which never rejects)
        if rho_weighted:
            lw = np.zeros(n_ln + 1)
            for g, tr in enumerate(trees):
                lw[ln_ids[g]] = tr.rho
            core_link_w = jnp.asarray(lay.core_rho, dt)
        else:
            lw = np.ones(n_ln + 1)
            core_link_w = jnp.ones((C,), dt)
        link_w = jnp.asarray(lw, dt)
        cap_p = np.ones(n_sw + 1)
        if priced:
            cap_p[:n_sw] = capacity
        cap_p = jnp.asarray(cap_p, dt)
        res_p = np.full(n_sw + 1, T, np.int64)
        if admit:
            res_p[:n_sw] = residual
        res_p = jnp.asarray(res_p, jnp.int32)
        sw_node = _node_ids(sw_tab, n_sw, rows, f.n_max)     # (R, n_max)
        ln_node = _node_ids(ln_tab, n_ln, rows, f.n_max)
        core_base = jnp.asarray(lay.core_rho, dt)              # (C,)
        core_on = jnp.asarray(lay.core_inc)                    # (R, C) bool

    maps = (sw_node, ln_node, n_sw, n_ln, sw_ids, ln_ids)
    if device_loop:
        with telemetry.span("engine.loop"):
            state = _run_device(f, lay, k, opts, use_pallas, kid, load,
                                send, avail_d, rho0, par, cidx, root_d,
                                base_edge, anc, valid, maps, link_w, cap_p,
                                res_p, core_base, core_on, core_link_w,
                                alpha_t, ramp_t, scal, patience, max_rounds,
                                record_rounds, priced, admit)
    else:
        state = _run_host(trees, loads, tid_np, avails, f, lay, k, opts,
                          maps, link_w, cap_p, residual, core_base,
                          core_on, core_link_w, alpha_t, ramp_t, scal,
                          patience, max_rounds, record_rounds, priced,
                          admit)
    (blue_node, best_round, rounds, history, prof0, prof0_core,
     rounds_log, bytes_to_host, best_drop, admission_log, chosen) = state

    with telemetry.span("engine.remeasure"):
        n_big = int(lay.tree_n.max())
        blue = blue_node[:, :n_big]
        # the reported statistics come from the one shared measurement recipe
        # (measure_fleet_multi — same code path the orchestrator's
        # post-admission re-measure uses); its host sweep is bit-identical to
        # the device messages the loop tracked, so nothing shifts in the
        # hand-off
        m = measure_fleet_multi(
            trees, chosen, list(loads),
            [blue[t, : trees[int(chosen[t])].n] for t in range(T)],
            core_rho=lay.core_rho if C else None,
            core_path=lay.core_path if C else None,
            rho_weighted=rho_weighted,
            link_id=ln_ids if n_ln < int((ln_tab < n_ln).sum()) else None,
            n_links=n_ln)
        base0 = prof0[:n_ln]
        if C:
            base0 = np.concatenate([base0, prof0_core])
        base0 = base0[base0 > 0]
        admission_dropped = residual_after = None
        if admit:
            admission_dropped = np.asarray(best_drop, np.int64)
            claims = np.zeros(n_sw, np.int64)
            for t in range(T):
                g = int(chosen[t])
                np.add.at(claims, sw_ids[g][blue[t, : trees[g].n]], 1)
            residual_after = residual - claims
            if not physical:
                residual_after = [residual_after[x] for x in sw_ids]
    return CongestionResult(
        blue=blue, costs=m.costs, msgs=m.msgs, congestion=m.congestion,
        max_congestion=m.max_congestion,
        mean_congestion=m.mean_congestion,
        baseline_max=float(history[0]),
        baseline_mean=float(base0.astype(np.float64).mean())
        if base0.size else 0.0,
        rounds=rounds, best_round=best_round, history=history,
        rounds_log=rounds_log, bytes_to_host=bytes_to_host,
        tree_of=np.asarray(chosen, np.int32).copy(),
        core_congestion=m.core_congestion,
        admission_dropped=admission_dropped, residual_after=residual_after,
        admission_log=admission_log)


def _physical_ids(trees, switch_id, link_id) -> tuple:
    """Per-tree physical switch and link ids (the given ones, or the
    segment layout: tree g's nodes at ``sum n_<g + v``), each with its
    (N, max n_g) table and its count of physical ids."""
    out = []
    for ids in (switch_id, link_id):
        if ids is None:
            offs = np.concatenate([[0], np.cumsum([t.n for t in trees])])
            ids = [np.arange(offs[g], offs[g + 1], dtype=np.int64)
                   for g in range(len(trees))]
        tab, n = _id_table(ids, trees)
        out += [ids, tab, n]
    return tuple(out)


_ID_TABLES: dict[int, tuple] = {}


def _id_table(ids, trees) -> tuple[np.ndarray, int]:
    """``ids`` validated against ``trees`` and stacked: the (N, max n_g)
    table, padded with the id count W (the dummy id), and W. Cached per
    id list, so a fleet that passes the same one every call stacks and
    checks its many trees once."""
    hit = _ID_TABLES.get(id(ids))
    if hit is not None and hit[0] is ids and hit[1] == len(trees):
        return hit[2], hit[3]
    if len(ids) != len(trees):
        raise ValueError(f"{len(ids)} physical id maps for {len(trees)} "
                         "trees")
    width = max(t.n for t in trees)
    tab = np.full((len(ids), width), -1, np.int64)
    for g, (x, t) in enumerate(zip(ids, trees)):
        x = np.asarray(x)
        if x.shape != (t.n,) or not np.issubdtype(x.dtype, np.integer) \
                or (x.size and x.min() < 0):
            raise ValueError(f"physical ids of tree {g} must be {t.n} "
                             "non-negative integers")
        tab[g, : t.n] = x
    W = int(tab.max()) + 1
    tab[tab < 0] = W
    if len(_ID_TABLES) >= 8:
        _ID_TABLES.pop(next(iter(_ID_TABLES)))
    _ID_TABLES[id(ids)] = (ids, len(trees), tab, W)
    return tab, W


def _ledger(vec, trees, sw_ids, n_sw, physical, what, dtype) -> np.ndarray:
    """A capacity or residual ledger as one vector per physical switch:
    given so (``physical``), or gathered from one vector per tree."""
    if physical:
        v = np.asarray(vec)
        if v.shape != (n_sw,):
            raise ValueError(f"{what} shape {v.shape} != ({n_sw},) — one "
                             "entry per physical switch")
        return v if dtype is None else v.astype(dtype)
    vecs = [np.asarray(x) if dtype is None else np.asarray(x, dtype)
            for x in vec]
    if len(vecs) != len(trees):
        noun = "ledgers" if what == "residual" else "vectors"
        raise ValueError(f"{len(vecs)} {what} {noun} for {len(trees)} "
                         "trees")
    for g, x in enumerate(vecs):
        if x.shape != (trees[g].n,):
            tail = f" for tree {g}" if what == "residual" else ""
            raise ValueError(f"{what} shape {x.shape} != "
                             f"({trees[g].n},){tail}")
    out = np.zeros(n_sw, vecs[0].dtype if vecs else np.float64)
    for x, ids in zip(vecs, sw_ids):
        out[ids] = x
    return out


def _node_ids(table: np.ndarray, W: int, rows: np.ndarray, n_max: int
              ) -> np.ndarray:
    """(R, n_max) physical id of each row's nodes, the dummy id W at
    padding."""
    out = table[rows]
    if out.shape[1] < n_max:
        out = np.concatenate(
            [out, np.full((rows.size, n_max - out.shape[1]), W, np.int64)],
            axis=1)
    return out


def _candidate_avail(cand, avails, hard, sw_tab, W) -> np.ndarray:
    """(T * P, n) availability of every (tenant, candidate) row: the
    tenant's mask, node-aligned on each candidate, and the switches with
    capacity left (``hard``, per physical switch plus the dummy, or
    None). Rows of one size pack as one array."""
    T, P = cand.shape
    rows = sw_tab[cand.reshape(-1)]
    n = int((rows[0] < W).sum())
    if (rows[:, n:] < W).any() or (rows[:, :n] == W).any():
        raise ValueError("path choice needs candidate trees of one size")
    base = np.ones((T, n), bool)
    for t, a in enumerate(avails):
        if a is not None:
            base[t] = np.asarray(a, bool)
    out = np.repeat(base, P, axis=0)
    if hard is not None:
        out &= hard[rows[:, :n]]
    return out


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

    ``rows`` selects which batch rows' ``slot_of`` maps apply — the path
    loop maps each tenant's masks through the row of its chosen tree.
    """
    slot_of = f.slot_of if rows is None else f.slot_of[rows]
    B = x_slot.shape[0]
    pad = np.concatenate(
        [x_slot, np.zeros((B, 1), x_slot.dtype)], axis=1)
    return np.take_along_axis(pad, slot_of, axis=1)


def _slot_ids(node_ids: np.ndarray, f, W: int) -> jax.Array:
    """Node-indexed physical ids (R, n_max) in slot order (R, S); padding
    slots read the dummy id W."""
    sn = f.slot_node
    out = np.take_along_axis(node_ids, np.maximum(sn, 0), axis=1)
    return jnp.asarray(np.where(sn >= 0, out, W), jnp.int32)


def _run_device(f, lay, k, opts, use_pallas, kid, load, send, avail_d, rho0,
                par, cidx, root_d, base_edge, anc, valid, maps, link_w,
                cap_p, res_p, core_base, core_on, core_link_w, alpha_t,
                ramp_t, scal, patience, max_rounds, record_rounds, priced,
                admit):
    """Dispatch the resident loop; pull the final state once. Counts the
    loop in ``penalty.loops`` and its rounds in ``penalty.rounds``; a
    path-choice loop also in ``paths.waves``, its candidate rows in
    ``paths.candidates`` and the tenants whose tree changed between its
    first and last round in ``paths.moves``."""
    sw_node, ln_node, n_sw, n_ln, _, _ = maps
    n_big = int(lay.tree_n.max())
    P = lay.paths
    ln = _slot_ids(ln_node, f, n_ln)
    sw = _slot_ids(sw_node, f, n_sw)
    static = dict(
        lvl_off=f.lvl_off, lvl_width=f.lvl_width,
        lvl_internal=f.lvl_internal, lvl_sub=f.lvl_sub,
        k=k, cap=bool(opts.cap), use_pallas=bool(use_pallas),
        interpret=bool(opts.interpret), max_rounds=int(max_rounds),
        record=bool(record_rounds), priced=priced, admit=admit)
    scalars = (scal["hot_frac"], scal["w_cap"], scal["cap_beta"],
               scal["cap_frac"], jnp.int32(patience))
    T = lay.tree_of.size
    if P == 1:
        out = _device_driver(
            kid, load, send, avail_d, par, cidx, root_d,
            base_edge, anc, valid, ln, sw, link_w, cap_p, res_p,
            core_base, core_on, core_link_w, alpha_t, ramp_t, *scalars,
            **static)
        out = tuple(np.asarray(x) for x in out)
        choice = np.zeros(T, np.int64)
    else:
        rot = jnp.asarray(np.arange(T) % P, jnp.int32)
        out = _device_paths(
            kid, load, send, avail_d, par, cidx, root_d, rho0,
            base_edge, anc, valid, ln, sw, link_w, cap_p, res_p,
            core_base, core_on, core_link_w, alpha_t, ramp_t, rot,
            *scalars, **static)
        out = tuple(np.asarray(x) for x in out)
        with telemetry.span("engine.paths"):
            choice, choice0 = out[10].astype(np.int64), out[11]
            telemetry.count("paths.waves")
            telemetry.count("paths.candidates", T * P)
            telemetry.count("paths.moves", int((choice != choice0).sum()))
    (best_blue_s, best_round_d, rounds_d, hist_d, prof0_d, prof0c_d,
     best_drop_d, log_rho, log_blue, log_drop) = out[:10]
    bytes_to_host = sum(int(x.nbytes) for x in out)
    rounds = int(rounds_d)
    telemetry.count("penalty.loops")
    telemetry.count("penalty.rounds", rounds)
    best_round = int(best_round_d)
    history = [float(c) for c in hist_d[:rounds]]
    rows = np.arange(T) * P + choice
    chosen = lay.row_tree[rows]
    blue_node = _slots_to_nodes_np(best_blue_s, f, rows=rows)
    rounds_log = None
    if record_rounds:
        rounds_log = []
        for r in range(rounds):
            rho_eff = _slots_to_nodes_np(
                log_rho[r], f, rows=rows).astype(np.float64)[:, :n_big]
            rounds_log.append(
                (rho_eff,
                 _slots_to_nodes_np(log_blue[r], f, rows=rows)[:, :n_big]))
    admission_log = None
    if admit and record_rounds:
        admission_log = [log_drop[r].astype(np.int64) for r in range(rounds)]
    return (blue_node, best_round, rounds, history, prof0_d, prof0c_d,
            rounds_log, bytes_to_host, best_drop_d.astype(np.int64),
            admission_log, chosen)


def _run_host(trees, loads, tid_np, avails, f, lay, k, opts, maps, link_w,
              cap_p, residual, core_base, core_on, core_link_w, alpha_t,
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

    With ``admit`` each round replays a literal sequential ledger of the
    physical switches in tenant order — the admission the device loop's
    cumsum rank computes in one shot — and persists rejections into
    ``avails`` so the next round's rebuilt Forest excludes them.
    """
    from ..core.congestion import messages_up_forest
    from .batched import solve_forest

    sw_node, ln_node, _, _, sw_ids, _ = maps
    T, n_max = f.mask.shape
    C = int(lay.n_core)
    n_big = int(lay.tree_n.max())
    dt = np.dtype(opts.dtype)
    base_edge_node = jnp.asarray(
        np.where(np.isfinite(f.rho_up[:, :, 1]), f.rho_up[:, :, 1], 0.0), dt)
    root_idx = jnp.asarray(f.root)
    ln = jnp.asarray(ln_node, jnp.int32)
    sw = jnp.asarray(sw_node, jnp.int32)
    w = jnp.ones((T, n_max), dt)
    wc = jnp.ones((T, C), dt)
    best = None                     # (cmax, round, blue, drop)
    history: list[float] = []
    rounds_log: list | None = [] if record_rounds else None
    admission_log: list | None = \
        [] if (admit and record_rounds) else None
    prof0 = prof0_core = None
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
            # the sequential ledger the device cumsum rank reproduces:
            # claims replayed in tenant order against a fresh per-round
            # copy of the residual; rejections ban the (tenant, switch)
            # pair from every later round via the avail masks
            blue = blue.copy()
            led = residual.copy()
            for t in range(T):
                ids = sw_ids[int(tid_np[t])]
                for v in np.nonzero(blue[t, : ids.size])[0]:
                    if led[ids[v]] > 0:
                        led[ids[v]] -= 1
                    else:
                        blue[t, v] = False
                        avails[t][v] = False
                        drop[t] += 1
                        banned = True
        msgs64 = messages_up_forest(fr, blue)
        msgs = jnp.asarray(msgs64.astype(np.int32))
        bytes_to_host += msgs.nbytes
        blue_d = jnp.asarray(blue)
        prof, prof_core, cmax_d, w2, wc2 = _penalty_step(
            w, wc, msgs, blue_d, root_idx, ln, sw, core_on, msgs, blue_d,
            root_idx, ln, sw, core_on, link_w, core_link_w, cap_p, alpha_t,
            ramp_t, scal["hot_frac"], scal["w_cap"], scal["cap_beta"],
            scal["cap_frac"], priced=priced)
        cmax = float(cmax_d)
        bytes_to_host += 4
        history.append(cmax)
        rounds = r + 1
        if r == 0:
            prof0 = np.asarray(prof)
            prof0_core = np.asarray(prof_core)
            bytes_to_host += prof0.nbytes + prof0_core.nbytes
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
    return (blue_node, best_round, rounds, history, prof0, prof0_core,
            rounds_log, bytes_to_host, best_drop, admission_log,
            tid_np.astype(np.int64))
