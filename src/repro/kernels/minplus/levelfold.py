"""Fused level-fold: one launch per tree level of the batched SOAR-Gather.

The level-synchronous gather in ``repro.engine`` folds, for every internal
node of a depth level, the min-plus convolutions of all its children's DP
tables (the mCost chain of Algorithm 3), then applies the red/blue
recurrence. This module runs the whole fold of a level as one kernel:

  * the children's rows are gathered in XLA (children always live exactly
    one level down) and laid out budget-major, the parent columns filling
    the ``(8, 128)`` vreg tile; the kernel chains the min-plus
    convolutions with the ``(rows, K)`` partial accumulators in VMEM
    scratch, one child per step of a reduction grid axis;
  * the red chain (child rows ``1..nl``), the blue chain (child row 1),
    the availability mask, the blue budget shift and the at-most-k
    ``cummin`` all happen in the same kernel body, so a level costs one
    launch and one HBM write (the level's output block).

``level_fold`` is the dispatcher: ``use_pallas=True`` runs the Pallas
kernel (compiled by Mosaic on TPU; ``interpret=True`` executes its body in
Python, the CPU validation mode), ``use_pallas=False`` runs
``level_fold_jnp``, a fused jnp formulation of the identical math that XLA
fuses into one loop nest.

All arithmetic runs on the finite ``BIG`` sentinel from
``repro.core.tropical`` (never ``inf``: padded slots multiply by zero
loads, and ``0 * inf`` is NaN), and both paths share
:func:`minplus_fused`, so they agree bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.tropical import BIG


def minplus_fused(a: jax.Array, b: jax.Array) -> jax.Array:
    """Fused min-plus convolution, (rows, K) x (rows, K) -> (rows, K).

    The j-shift reduction unrolled over the (static) budget width so XLA
    keeps everything in one elementwise loop — no (rows, K, K) candidate
    tensor is ever materialized. Identical candidate order on every
    backend, hence bit-identical results.
    """
    rows, k = a.shape
    acc = a + b[:, :1]
    for j in range(1, k):
        shifted = jnp.concatenate(
            [jnp.full((rows, j), BIG, a.dtype), a[:, : k - j]], axis=1)
        acc = jnp.minimum(acc, shifted + b[:, j : j + 1])
    return acc


def chain_fold(st: jax.Array, collect: bool = False):
    """Fold a stack of row-batches through the min-plus chain.

    ``st``: (max_c, R, K) — child 0 first. Returns the final accumulator
    (R, K), plus (when ``collect=True``) the full (max_c, R, K) prefix
    stack (partial chains, needed by the color traceback's mSplit
    replay). One lax.scan over the child index: identical fold order to
    an unrolled loop — hence bit-identical results everywhere this chain
    is spelled — at O(max_c) smaller HLO. This is the single definition
    the gather fold and the on-device color both call; keep it that way,
    the bit-identical-mask guarantee rides on it.
    """
    def fold(acc, ch):
        y = minplus_fused(acc, ch)
        return y, y

    last, partials = jax.lax.scan(fold, st[0], st[1:])
    if not collect:
        return last
    return last, jnp.concatenate([st[:1], partials], axis=0)


def rho_up_from_edges(rho_edge: jax.Array, anc: jax.Array,
                      valid: jax.Array) -> jax.Array:
    """Recompute the packed rho-up table from per-edge rates, on device.

    The congestion driver re-solves one prebuilt Forest every round under
    penalty-reweighted *edge* rates; repacking the ``(B, S, h+2)``
    cumulative table on the host (as ``Tree.rho_up_table`` does) would
    drag the loop off the accelerator. This recomputes it from the slot
    layout instead:

        rho_up[b, s, ell] = sum_{j < ell} rho_edge[b, anc[b, s, j]]

    ``rho_edge``: (B, S) effective up-edge rate per slot (finite
    everywhere — padded slots carry 0); ``anc``: (B, S, h_max+1) int32,
    ``anc[b, s, j]`` = slot of the j-th ancestor of s (j=0 is s itself;
    entries past the root point at slot 0 and are masked); ``valid``:
    (B, S, h_max+2) bool — True exactly where the host table is finite.
    Returns (B, S, h_max+2) with ``BIG`` at invalid entries.

    The accumulation order is one edge per hop, left to right — the
    *same* per-node association as the host ``Tree.rho_up_table`` walk —
    so on rates that are exactly representable (the dyadic-quantized
    penalty weights on dyadic-rho trees) the result is bit-identical to
    packing the host table and casting. Masked lanes accumulate finite
    garbage (real edge rates, never BIG) that the mask discards.
    """
    B, S = rho_edge.shape
    dt = rho_edge.dtype
    H2 = valid.shape[2]
    acc = jnp.zeros((B, S), dt)
    rows = [jnp.where(valid[:, :, 0], acc, BIG)]
    for ell in range(1, H2):
        acc = acc + jnp.take_along_axis(rho_edge, anc[:, :, ell - 1], axis=1)
        rows.append(jnp.where(valid[:, :, ell], acc, BIG))
    return jnp.stack(rows, axis=2)


def scaled_edges(rho_edge: jax.Array, scale: jax.Array,
                 extra: jax.Array | None = None,
                 root_idx: jax.Array | None = None) -> jax.Array:
    """Effective per-edge rates: ``rho_edge * scale``, optionally with an
    additive extension on each instance's root edge.

    The additive term is how the fleet congestion driver folds shared-core
    transit into the per-tree DP: a tenant's root-crossing messages also
    traverse its core path, so the core links' (penalty-weighted) rates
    extend the root up-edge — additively, because core hops are in series
    with the root hop. ``extra``: (B,) per-instance extension; ``root_idx``:
    (B,) int column of each instance's root edge. Both loop flavors of the
    driver call this single definition (multiplied then extended in the
    same order), which is what keeps their effective edge rates
    bit-identical; :func:`rho_up_from_edges` then accumulates them into
    the packed rho-up table on device.
    """
    edges = rho_edge * scale
    if extra is None:
        return edges
    B = edges.shape[0]
    return edges.at[jnp.arange(B), root_idx].add(extra)


def _gather_children(xs, xb, kid):
    """Every parent's child rows, ``(B, W, max_c, nl, kcap)`` red and
    ``(B, W, max_c, kcap)`` blue, gathered over the leading batch axis
    (sentinel children read the appended identity at index C-1)."""
    B, W, max_c = kid.shape
    _, _, nl, kcap = xs.shape
    flat = kid.reshape(B, W * max_c)
    g_r = jnp.take_along_axis(xs, flat[:, :, None, None], axis=1)
    g_b = jnp.take_along_axis(xb, flat[:, :, None], axis=1)
    return (g_r.reshape(B, W, max_c, nl, kcap),
            g_b.reshape(B, W, max_c, kcap))


def level_fold_jnp(xs, xb, kid, load, send, avail, rho, *, nl: int,
                  kcap: int):
    """Fused-jnp level fold, spelled with ``take_along_axis`` over the
    leading batch axis (cheaper for XLA:CPU to compile than a vmapped
    per-instance body).

    xs: (B, C, nl, kcap) the child level's tables at rows 1..nl, identity
    (all-zeros) appended at index C-1; xb: (B, C, kcap) the same at row 1
    (the blue-chain operand); kid: (B, W, max_c) *child-level-local*
    indices (sentinel C-1); load, send: (B, W); avail: (B, W) bool; rho:
    (B, W, nl). Returns the level's internal block values,
    (B, W, nl, kcap).
    """
    B, W, max_c = kid.shape
    dt = xs.dtype
    g_r, g_b = _gather_children(xs, xb, kid)
    rows_r = jnp.moveaxis(g_r, 2, 0).reshape(max_c, B * W * nl, kcap)
    rows_b = jnp.moveaxis(g_b, 2, 0).reshape(max_c, B * W, kcap)
    chs = jnp.concatenate([rows_r, rows_b], axis=1)    # (max_c, R, kcap)
    acc = chain_fold(chs)
    acc_r = acc[: B * W * nl].reshape(B, W, nl, kcap)
    acc_b = acc[B * W * nl :].reshape(B, W, kcap)
    rl = rho[..., None]                                # (B, W, nl, 1)
    red = acc_r + load[:, :, None, None] * rl
    blue = jnp.concatenate(
        [jnp.full((B, W, nl, 1), BIG, dt),
         acc_b[:, :, None, :-1] + send[:, :, None, None] * rl], axis=-1)
    blue = jnp.where(avail[:, :, None, None], blue, BIG)
    out = jnp.minimum(red, blue)
    return jax.lax.cummin(out, axis=3)                 # at-most-k monotone


LANE, SUBLANE = 128, 8
VMEM_BUDGET = 12 * 2**20    # under the 16 MiB scoped-VMEM default of v5e


def _minplus_into(acc_ref, acc_at, x_ref, x_at, pad_ref, kcap: int):
    """``acc <- minplus_fused(acc, x)`` on one budget-major slab.

    ``acc_ref[acc_at]`` / ``x_ref[x_at]`` are ``(kcap, TS, 128)``: budget
    on the leading axis, parent columns on the (sublane, lane) tile. The
    j-shift of :func:`minplus_fused` is a dynamic leading-axis window of
    ``pad_ref`` (``kcap - 1`` BIG rows, then the accumulator), so the
    candidate set is exactly the jnp one — shifted accumulator plus the
    child's entry j, BIG where the shift runs off the front.
    """
    a = acc_ref[acc_at]
    pad_ref[kcap - 1 :] = a

    def body(j, run):
        return jnp.minimum(run, pad_ref[pl.ds(kcap - 1 - j, kcap)]
                           + x_ref[(*x_at, j)])

    acc_ref[acc_at] = jax.lax.fori_loop(1, kcap, body, a + x_ref[(*x_at, 0)])


def _levelfold_kernel(xr_ref, xb_ref, load_ref, send_ref, avail_ref, rho_ref,
                      o_ref, accr_ref, accb_ref, pad_ref, *, lt: int,
                      kcap: int):
    """Grid (parent tiles, row tiles, child index m); m is the reduction
    axis: child 0 seeds the accumulators, children 1.. fold into them,
    and the last child's step applies red/blue, the availability mask and
    the at-most-k cummin, writing the output block once."""
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _():
        accr_ref[...] = xr_ref[0]
        accb_ref[...] = xb_ref[0]
        if kcap > 1:
            pad_ref[: kcap - 1] = jnp.full(
                (kcap - 1, *pad_ref.shape[1:]), BIG, pad_ref.dtype)

    @pl.when(m > 0)
    def _():
        def fold_row(r, c):
            _minplus_into(accr_ref, (r,), xr_ref, (0, r), pad_ref, kcap)
            return c

        jax.lax.fori_loop(0, lt, fold_row, 0)
        _minplus_into(accb_ref, (), xb_ref, (0,), pad_ref, kcap)

    @pl.when(m == pl.num_programs(2) - 1)
    def _():
        avail = avail_ref[...] != 0
        load, send = load_ref[...], send_ref[...]
        acc_b = accb_ref[...]

        def emit(r, c):
            rl = rho_ref[r]                            # (TS, 128)
            red = accr_ref[r] + load * rl              # (kcap, TS, 128)
            o_ref[r, 0] = jnp.minimum(red[0], BIG)     # blue needs budget
            if kcap > 1:
                blue = jnp.where(avail, acc_b[:-1] + send * rl, BIG)
                o_ref[r, 1:] = jnp.minimum(red[1:], blue)
            s = 1
            while s < kcap:                            # prefix-min doubling
                o_ref[r, s:] = jnp.minimum(o_ref[r, s:], o_ref[r, : kcap - s])
                s *= 2
            return c

        jax.lax.fori_loop(0, lt, emit, 0)


def _tiles(P: int, nl: int, kcap: int) -> tuple[int, int, int, int]:
    """Tile sizes for ``P`` parent columns: ``(ts, lt, pr, vmem)``.

    ``ts`` sublane rows of 128 parent columns per block (a power of two in
    8..64, about 1024 budget x column rows per slab, or the whole padded
    column count when it is smaller than one tile); ``lt`` the largest
    divisor of ``nl`` whose blocks fit :data:`VMEM_BUDGET`; ``pr`` the
    padded column-row count; ``vmem`` the bytes one grid step holds.
    """
    pr = -(-P // LANE)
    ts = SUBLANE
    while ts < 64 and 2 * ts * kcap <= 1024:
        ts *= 2
    if pr <= ts:
        ts = pr
    else:
        pr = -(-pr // ts) * ts
    tile = 4 * max(ts, SUBLANE) * LANE

    def vmem(lt):
        # double-buffered inputs and output, accumulators, shift window
        return tile * (2 * (lt * kcap + kcap + 3 + lt) + 2 * lt * kcap
                       + lt * kcap + kcap + 2 * kcap - 1)

    lt = max(d for d in range(1, nl + 1)
             if nl % d == 0 and (d == 1 or vmem(d) <= VMEM_BUDGET))
    return ts, lt, pr, vmem(lt)


def level_fold_pallas(xs, xb, kid, load, send, avail, rho, *, nl: int,
                      kcap: int, interpret: bool = False):
    """One-launch-per-level Pallas fold; same contract as level_fold_jnp.

    The child rows are gathered in XLA (:func:`_gather_children`) and laid
    out *budget-major*: the budget axis is a leading block axis and the
    ``B * W`` parent columns fill the ``(8, 128)`` vreg tile. The
    min-plus shift is then a leading-axis window and the blue budget
    shift and the cummin are leading-axis slices — no lane shuffles and
    no lane padding. The grid tiles parent columns and barrier rows and
    runs the child chain as its innermost reduction axis, so one step
    holds one child's block (see :func:`_tiles`) whatever the level's
    width; the ``(rows, kcap)`` partial accumulators stay in VMEM scratch.
    The candidate set of every min is the jnp one, so the result is
    bit-identical to :func:`level_fold_jnp`.
    """
    B, W, max_c = kid.shape
    dt = xs.dtype
    P = B * W
    ts, lt, pr, vmem = _tiles(P, nl, kcap)
    g_r, g_b = _gather_children(xs, xb, kid)

    def cols(x, lead):
        """(B, W, *lead) -> (*lead, pr, 128), parent columns zero-padded."""
        nd = len(lead)
        x = jnp.transpose(x, (*range(2, 2 + nd), 0, 1)).reshape(*lead, P)
        x = jnp.pad(x, [(0, 0)] * nd + [(0, pr * LANE - P)])
        return x.reshape(*lead, pr, LANE)

    xr = cols(g_r, (max_c, nl, kcap))
    xbb = cols(g_b, (max_c, kcap))
    vec = [cols(v.astype(t)[..., None], (1,))[0]
           for v, t in ((load, dt), (send, dt), (avail, jnp.int32))]
    rl = cols(rho, (nl,))
    tl = (ts, LANE)
    params = dict(dimension_semantics=("parallel", "parallel", "arbitrary"))
    if vmem > VMEM_BUDGET:
        params["vmem_limit_bytes"] = vmem + 4 * 2**20
    out = pl.pallas_call(
        functools.partial(_levelfold_kernel, lt=lt, kcap=kcap),
        grid=(pr // ts, nl // lt, max_c),
        in_specs=[
            pl.BlockSpec((1, lt, kcap, *tl), lambda p, l, m: (m, l, 0, p, 0)),
            pl.BlockSpec((1, kcap, *tl), lambda p, l, m: (m, 0, p, 0)),
            *[pl.BlockSpec(tl, lambda p, l, m: (p, 0))] * 3,
            pl.BlockSpec((lt, *tl), lambda p, l, m: (l, p, 0)),
        ],
        out_specs=pl.BlockSpec((lt, kcap, *tl),
                               lambda p, l, m: (l, 0, p, 0)),
        out_shape=jax.ShapeDtypeStruct((nl, kcap, pr, LANE), dt),
        scratch_shapes=[pltpu.VMEM((lt, kcap, *tl), dt),
                        pltpu.VMEM((kcap, *tl), dt),
                        pltpu.VMEM((2 * kcap - 1, *tl), dt)],
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret,
        name="levelfold",
    )(xr, xbb, *vec, rl)
    out = out.reshape(nl, kcap, pr * LANE)[:, :, :P]
    return jnp.transpose(out.reshape(nl, kcap, B, W), (2, 3, 0, 1))


def level_fold(xs, xb, kid, load, send, avail, rho, *, nl: int, kcap: int,
               use_pallas: bool = False, interpret: bool = False):
    """Backend dispatch for the fused level fold (see module docstring)."""
    if use_pallas:
        return level_fold_pallas(xs, xb, kid, load, send, avail, rho,
                                 nl=nl, kcap=kcap, interpret=interpret)
    return level_fold_jnp(xs, xb, kid, load, send, avail, rho,
                          nl=nl, kcap=kcap)
