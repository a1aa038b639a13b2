"""Pallas TPU kernel: batched min-plus (tropical) convolution.

SOAR-Gather's mCost inner loop (paper Alg. 3 lines 30-34) is, for every
(node, ell) pair, the min-plus convolution of two monotone budget vectors:

    C[b, i] = min_{0 <= j <= i}  A[b, i-j] + B[b, j]

The level-synchronous gather batches all (node, ell) rows of a tree level;
this kernel tiles the batch into VMEM blocks and runs the j-shift reduction
on the VPU. Budget width K is padded to the 128-lane boundary by ops.py.

Infeasible shift positions and lane padding use the finite ``BIG``
sentinel from ``repro.core.tropical`` — the same stand-in the engine's
fused jnp path runs on — so ``0 * pad`` can never go NaN and the
interpret-mode kernel matches the fused path bit-for-bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.tropical import BIG


def _minplus_loop(a: jax.Array, b: jax.Array) -> jax.Array:
    """``minplus_fused`` spelled as a fori_loop: identical candidates and
    BIG shift padding (bit-identical results), but O(1) HLO in the
    budget width, so the lane-padded kernel doesn't pay a 128-step unroll
    at trace time."""
    rows, kk = a.shape
    a_pad = jnp.concatenate([jnp.full((rows, kk), BIG, a.dtype), a], axis=1)

    def body(j, acc):
        seg = jax.lax.dynamic_slice(a_pad, (0, kk - j), (rows, kk))
        bj = jax.lax.dynamic_slice(b, (0, j), (rows, 1))
        return jnp.minimum(acc, seg + bj)

    return jax.lax.fori_loop(1, kk, body, a + b[:, :1])


def _minplus_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = _minplus_loop(a_ref[...], b_ref[...])


def minplus_pallas(a: jax.Array, b: jax.Array, block_rows: int = 128,
                   interpret: bool = False) -> jax.Array:
    """a, b: (rows, K) float32, K a multiple of 128 (pad in ops.py)."""
    rows, k = a.shape
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, k), lambda i: (i, 0))
    return pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, k), a.dtype),
        interpret=interpret,
    )(a, b)
