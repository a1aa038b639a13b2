"""Pallas TPU kernel: per-row top-k magnitude selection (gradient compression).

The distributed-optimization path compresses gradient shards before they
enter the SOAR-scheduled reduction tree: each row (a flattened gradient
block) keeps its k largest-|x| entries. The kernel runs k argmax rounds over
a VMEM-resident row tile — O(kD) VPU work, no sort, deterministic ties
(first index wins), which keeps compression reproducible across replicas.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _topk_kernel(x_ref, v_ref, i_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)        # (TB, D)
    tb, d = x.shape

    def body(j, carry):
        cur = carry
        mag = jnp.abs(cur)
        idx = jnp.argmax(mag, axis=1)                       # (TB,)
        val = jnp.take_along_axis(cur, idx[:, None], axis=1)  # (TB, 1)
        v_ref[:, pl.ds(j, 1)] = val.astype(v_ref.dtype)
        i_ref[:, pl.ds(j, 1)] = idx[:, None].astype(jnp.int32)
        cur = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (tb, d), 1) == idx[:, None],
            0.0, cur)
        return cur

    jax.lax.fori_loop(0, k, body, x)


def topk_compress_pallas(x: jax.Array, k: int, block_rows: int = 8,
                         interpret: bool = False):
    """x: (R, D) -> (values (R, k), indices (R, k))."""
    r, d = x.shape
    grid = (pl.cdiv(r, block_rows),)
    kernel = functools.partial(_topk_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, k), x.dtype),
            jax.ShapeDtypeStruct((r, k), jnp.int32),
        ],
        interpret=interpret,
    )(x)
