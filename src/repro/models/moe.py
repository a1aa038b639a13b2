"""Mixture-of-Experts FFN: sort-based capacity dispatch, two lowerings.

Token-choice top-k routing with two interchangeable dispatch paths:

1. ``_moe_forward_dense`` — single-program sort/scatter dispatch into an
   (E, C, d) buffer under auto-SPMD. Simple and correct, but at 256-device
   scale XLA lowers the global scatters into full (tokens*k, d) all-reduces
   (~240 GB per layer for kimi prefill; see EXPERIMENTS.md §Perf).

2. ``_moe_forward_ep`` — the production expert-parallel path: a shard_map
   interior where each device routes its local tokens, exchanges rows with
   its model-axis peers via two ``lax.all_to_all`` ops (payload
   N_loc*k*cf rows, ~500x smaller), sorts received rows into its E/G local
   experts, and runs the expert MLP locally. Expert weights arrive sharded
   (E over 'model', d over FSDP) and are all-gathered over the FSDP axes
   only (the standard FSDP weight gather). Capacity is enforced per shard
   (GShard/Switch semantics) rather than globally — drops can differ from
   the dense path when routing is skewed; with enough capacity_factor the
   two are numerically identical (tested).

The EP path activates when a mesh with a >1 'model' axis is installed via
``parallel.sharding.axis_rules(rules, mesh)`` and shapes divide; otherwise
the dense path runs (single-device smoke tests, decode micro-batches).

FLOPs are the honest active-FLOPs (tokens * top_k * cf * expert_mlp), not
the dense E-times blow-up.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import cs, current_mesh, current_rules
from .config import ModelConfig
from .layers import dense_init, dtype_of, init_mlp, mlp_einsum, apply_mlp


def init_moe(key, cfg: ModelConfig):
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 8)
    dt = dtype_of(cfg)
    experts = {
        "w_up": dense_init(ks[0], (E, d, f), dt),
        "w_down": dense_init(ks[1], (E, f, d), dt),
    }
    if cfg.mlp_type == "swiglu":
        experts["w_gate"] = dense_init(ks[2], (E, d, f), dt)
    p = {
        "router": {"w": dense_init(ks[3], (d, E), jnp.float32, scale=0.1)},
        "experts": experts,
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, cfg.n_shared_experts * f)
    return p


#: EP lowering selector: "replicated" routes every model column over its dp
#: shard's tokens and combines expert groups with one psum (no activation
#: resharding — the measured winner, see EXPERIMENTS.md §Perf); "a2a"
#: exchanges token rows across the model axis with two all_to_alls
#: (smaller collective payload, but flattening tokens over dp x model forces
#: an activation reshard each layer that XLA lowers catastrophically).
EP_MODE = "replicated"


def moe_forward(p, x, cfg: ModelConfig):
    """x: (B, T, d) -> (out, aux_loss). Dispatch-path selection."""
    B, T, d = x.shape
    mesh = current_mesh()
    rules = current_rules()
    if mesh is not None and rules is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        G = sizes.get("model", 1)
        n_dev = mesh.devices.size
        dp_size = max(1, n_dev // G)
        if G > 1 and cfg.n_experts % G == 0:
            if EP_MODE == "a2a" and (B * T) % n_dev == 0:
                return _moe_forward_ep_a2a(p, x, cfg, mesh, rules)
            if EP_MODE == "replicated" and (B * T) % dp_size == 0:
                return _moe_forward_ep(p, x, cfg, mesh, rules)
    return _moe_forward_dense(p, x, cfg)


# ---------------------------------------------------------------------------
# Path 1: auto-SPMD dense dispatch (reference semantics)
# ---------------------------------------------------------------------------

def _moe_forward_dense(p, x, cfg: ModelConfig):
    B, T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    N = B * T
    xt = x.reshape(N, d)
    xt = cs(xt, "tokens_flat", None)

    gates = jax.nn.softmax((xt.astype(jnp.float32) @ p["router"]["w"]), axis=-1)
    gate_w, eidx = jax.lax.top_k(gates, k)                     # (N, k)
    gate_w = gate_w / jnp.clip(gate_w.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance auxiliary loss (Switch-style) ----------------------
    me = gates.mean(0)                                          # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (N * k)
    aux = E * jnp.sum(me * ce)

    # ---- sort-based dispatch ---------------------------------------------
    F = N * k
    C = max(1, math.ceil(N * k / E * cfg.capacity_factor))
    flat_e = eidx.reshape(F)
    order = jnp.argsort(flat_e, stable=True)                    # (F,)
    sorted_e = flat_e[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(F, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < C
    dest_sorted = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # OOB -> drop
    tok_sorted = order // k

    xbuf = jnp.zeros((E * C, d), x.dtype).at[dest_sorted].set(
        xt[tok_sorted], mode="drop")
    xbuf = cs(xbuf.reshape(E, C, d), "experts", "expert_cap", None)

    ybuf = mlp_einsum(p["experts"], xbuf, cfg)                  # (E, C, d)
    ybuf = cs(ybuf, "experts", "expert_cap", None).reshape(E * C, d)

    # ---- combine -----------------------------------------------------------
    y_sorted = ybuf[jnp.minimum(dest_sorted, E * C - 1)]
    y_sorted = jnp.where(keep[:, None], y_sorted, 0)
    y_flat = jnp.zeros((F, d), x.dtype).at[order].set(y_sorted)  # unsort
    y = jnp.einsum("nkd,nk->nd", y_flat.reshape(N, k, d),
                   gate_w.astype(x.dtype))
    if "shared" in p:
        y = y + apply_mlp(p["shared"], xt, cfg)
    y = cs(y, "tokens_flat", None)
    return y.reshape(B, T, d), aux


# ---------------------------------------------------------------------------
# Path 2: expert-parallel shard_map interior (production lowering)
# ---------------------------------------------------------------------------

def _sort_into_bins(values_idx, n_bins: int, capacity: int):
    """Rank items by bin with a per-bin capacity (sort-based, no one-hot).

    values_idx: (R,) int bin id per item; ids >= n_bins are invalid/padding.
    Returns (order, dest, keep): items iterated in sorted order; item
    ``order[i]`` goes to flat slot ``dest[i]`` (bin * capacity + rank) when
    ``keep[i]`` — overflow and invalid ids are dropped.
    """
    R = values_idx.shape[0]
    order = jnp.argsort(values_idx, stable=True)
    sorted_b = values_idx[order]
    counts = jnp.zeros((n_bins + 1,), jnp.int32).at[
        jnp.minimum(values_idx, n_bins)].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(R, dtype=jnp.int32) - starts[jnp.minimum(sorted_b, n_bins)]
    keep = (pos < capacity) & (sorted_b < n_bins)
    dest = jnp.where(keep, sorted_b * capacity + pos, n_bins * capacity)
    return order, dest, keep


def _moe_forward_ep(p, x, cfg: ModelConfig, mesh, rules):
    """Replicated-routing EP: tokens stay dp-sharded end to end.

    Every device in a model row holds the same N/dp tokens (activations are
    replicated across 'model' for the token dim, exactly as in the dense
    layers). Each model column g routes those tokens, keeps only the pairs
    destined to its E/G local experts, runs them, and contributes a partial
    combine; one psum over 'model' completes the sum. Routing work (softmax
    + top_k over E) is duplicated G times — negligible next to the expert
    matmuls — and NO activation layout change ever happens, which is what
    makes this the fastest lowering measured (EXPERIMENTS.md §Perf).
    """
    B, T, d = x.shape
    E, k, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    N = B * T
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    G = sizes["model"]
    E_loc = E // G
    dp = tuple(a for a in rules.get("batch", ()) if a) or ()
    dp = dp if isinstance(dp, tuple) else (dp,)
    dp_size = max(1, mesh.devices.size // G)
    N_loc = N // dp_size
    c_exp = max(1, math.ceil(N_loc * k * cf / E))

    tok_spec = P(dp)
    w_specs = {"w_up": P("model", dp, None), "w_down": P("model", None, dp)}
    if "w_gate" in p["experts"]:
        w_specs["w_gate"] = P("model", dp, None)
    rw_spec = P(dp, None)

    def body(xt, rw, experts):
        # xt: (N_loc, d) — replicated across the model axis
        rw_full = jax.lax.all_gather(rw, dp, axis=0, tiled=True) if dp else rw
        wf = {name: jax.lax.all_gather(w, dp, axis=(1 if name != "w_down"
                                                    else 2), tiled=True)
              if dp else w for name, w in experts.items()}
        # Mark the replicated token/router values as VARYING over 'model'.
        # Numerically a no-op (all columns hold equal values), but it makes
        # shard_map's transpose insert the psum-over-'model' that the
        # cotangents of the varying-index gathers below require. Without
        # this the router / activation grads silently come back wrong
        # (caught by tests/helpers/moe_ep_check.py; see DESIGN.md §8).
        xt = jax.lax.pcast(xt, "model", to="varying")
        rw_full = jax.lax.pcast(rw_full, "model", to="varying")
        g_mine = jax.lax.axis_index("model")
        gates = jax.nn.softmax(xt.astype(jnp.float32) @ rw_full, axis=-1)
        gw, eidx = jax.lax.top_k(gates, k)
        gw = gw / jnp.clip(gw.sum(-1, keepdims=True), 1e-9)

        # every model column computes identical aux terms; pmean over
        # 'model' returns the (invarying) value while scaling cotangents by
        # 1/G — exactly cancelling the psum of G equal contributions.
        me = jax.lax.pmean(gates.mean(0), dp) if dp else gates.mean(0)
        ce_loc = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(
            1.0) / (N_loc * k)
        ce = jax.lax.pmean(ce_loc, dp) if dp else ce_loc
        aux = jax.lax.pmean(E * jnp.sum(me * ce), "model")

        # Route INDICES, not rows: slot -> source-token maps are (R,)-sized,
        # so the only (rows x d) traffic is one gather into the expert
        # buffer and one scatter-add combine — R = E_loc*c_exp ~ F/G rows
        # instead of the F-row round-trips of the naive form (§Perf).
        F = N_loc * k
        flat_e = eidx.reshape(F)
        lb = flat_e - g_mine * E_loc
        local_bin = jnp.where((lb >= 0) & (lb < E_loc), lb, E_loc)
        order, dest, keep = _sort_into_bins(local_bin, E_loc, c_exp)
        R = E_loc * c_exp
        tok_slot = jnp.full((R + 1,), N_loc, jnp.int32).at[dest].set(
            order // k, mode="drop")[:-1]                    # (R,)
        gw_slot = jnp.zeros((R + 1,), jnp.float32).at[dest].set(
            gw.reshape(F)[order], mode="drop")[:-1]          # (R,)
        x_pad = jnp.concatenate([xt, jnp.zeros((1, d), x.dtype)])
        xexp = x_pad[tok_slot]                               # (R, d)
        yexp = mlp_einsum(wf, xexp.reshape(E_loc, c_exp, d), cfg)
        contrib = yexp.reshape(R, d) * gw_slot[:, None].astype(x.dtype)
        y = jnp.zeros((N_loc + 1, d), x.dtype).at[tok_slot].add(
            contrib)[:-1]
        return jax.lax.psum(y, "model"), aux

    xt = cs(x.reshape(N, d), "batch", None)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, rw_spec, w_specs),
        out_specs=(tok_spec, P()),
    )(xt, p["router"]["w"], p["experts"])
    y = y.reshape(B, T, d)
    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, cfg)
    return cs(y, "batch", "seq", None), aux


def _moe_forward_ep_a2a(p, x, cfg: ModelConfig, mesh, rules):
    B, T, d = x.shape
    E, k, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    N = B * T
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    G = sizes["model"]                      # expert-parallel groups
    E_loc = E // G
    dp = tuple(a for a in rules.get("batch", ()) if a) or ()
    dp = dp if isinstance(dp, tuple) else (dp,)
    n_dev = mesh.devices.size
    N_loc = N // n_dev
    # per-shard capacities (GShard-style; slack at both levels)
    c_send = max(1, math.ceil(N_loc * k * cf / G))
    c_exp = max(1, math.ceil(G * c_send * cf / E_loc))

    tok_spec = P(dp + ("model",))
    w_specs = {
        "w_up": P("model", dp, None),
        "w_down": P("model", None, dp),
    }
    if "w_gate" in p["experts"]:
        w_specs["w_gate"] = P("model", dp, None)
    rw_spec = P(dp, None)

    def body(xt, rw, experts):
        # xt: (N_loc, d) local tokens; rw: (d/dp, E); experts: local shards
        rw_full = jax.lax.all_gather(rw, dp, axis=0, tiled=True) if dp else rw
        wf = {name: jax.lax.all_gather(w, dp, axis=(1 if name != "w_down"
                                                    else 2), tiled=True)
              if dp else w for name, w in experts.items()}
        gates = jax.nn.softmax(xt.astype(jnp.float32) @ rw_full, axis=-1)
        gw, eidx = jax.lax.top_k(gates, k)                  # (N_loc, k)
        gw = gw / jnp.clip(gw.sum(-1, keepdims=True), 1e-9)

        # aux loss (global means via psum over every mesh axis)
        all_axes = dp + ("model",)
        me = jax.lax.pmean(gates.mean(0), all_axes)
        ce_loc = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(
            1.0) / (N_loc * k)
        ce = jax.lax.pmean(ce_loc, all_axes)
        aux = E * jnp.sum(me * ce)

        # ---- send-side: bin routed pairs by destination EP group ----------
        F = N_loc * k
        flat_e = eidx.reshape(F)
        grp = flat_e // E_loc
        order, dest, keep = _sort_into_bins(grp, G, c_send)
        tok_of = order // k
        pad_x = jnp.zeros((G * c_send + 1, d), x.dtype)
        send_x = pad_x.at[dest].set(xt[tok_of], mode="drop")[:-1]
        meta_e = jnp.full((G * c_send + 1,), E_loc, jnp.int32)
        send_e = meta_e.at[dest].set(flat_e[order] % E_loc, mode="drop")[:-1]

        # ---- exchange rows with model-axis peers --------------------------
        recv_x = jax.lax.all_to_all(send_x.reshape(G, c_send, d), "model",
                                    split_axis=0, concat_axis=0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e.reshape(G, c_send), "model",
                                    split_axis=0, concat_axis=0, tiled=False)
        rows = G * c_send

        # ---- group received rows by local expert --------------------------
        re = recv_e.reshape(rows)
        order2, dest2, keep2 = _sort_into_bins(re, E_loc, c_exp)
        pad2 = jnp.zeros((E_loc * c_exp + 1, d), x.dtype)
        xexp = pad2.at[dest2].set(recv_x.reshape(rows, d)[order2],
                                  mode="drop")[:-1]
        yexp = mlp_einsum(wf, xexp.reshape(E_loc, c_exp, d), cfg)

        # ---- ungroup, return rows, combine ---------------------------------
        y_sorted = yexp.reshape(-1, d)[jnp.minimum(dest2, E_loc * c_exp - 1)]
        y_sorted = jnp.where(keep2[:, None], y_sorted, 0)
        y_rows = jnp.zeros((rows, d), x.dtype).at[order2].set(y_sorted)
        back = jax.lax.all_to_all(y_rows.reshape(G, c_send, d), "model",
                                  split_axis=0, concat_axis=0, tiled=False)
        y_slot = back.reshape(rows, d)[jnp.minimum(dest, rows - 1)]
        y_slot = jnp.where(keep[:, None], y_slot, 0)
        y_pairs = jnp.zeros((F, d), x.dtype).at[order].set(y_slot)
        y = jnp.einsum("nkd,nk->nd", y_pairs.reshape(N_loc, k, d),
                       gw.astype(x.dtype))
        return y, aux

    xt = cs(x.reshape(N, d), "tokens_flat", None)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, rw_spec, w_specs),
        out_specs=(tok_spec, P()),
    )(xt, p["router"]["w"], p["experts"])
    # hand the activation back in the attention-friendly (batch, seq) layout
    # — an explicit reshard, instead of letting SPMD full-rematerialize when
    # the (tokens over dp x model) flat layout leaks through the reshape.
    y = cs(y.reshape(B, T, d), "batch", "seq", None)
    if "shared" in p:
        # shared experts are dense token-pointwise MLPs; run them in the
        # batch/seq layout (d_ff sharded over 'model') like any dense FFN.
        y = y + apply_mlp(p["shared"], cs(x, "batch", "seq", None), cfg)
    return y, aux
