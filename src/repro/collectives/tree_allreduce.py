"""shard_map executor for the SOAR reduction program.

Runs the paper's Reduce (Algorithm 1) as an actual JAX collective: red
switches forward message buffers upward (ppermute rounds), blue switches
collapse their buffer to a single partial sum, and the destination performs
the final aggregation + broadcast. Semantically equivalent to psum — proven
by tests — while its *network cost* equals the placement's phi, so the
SOAR-optimal placement minimizes the interconnect time of this collective.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .schedule import CompactOp, CompressOp, FoldOp, PermuteRound, ReduceProgram


def _left_fold(buf, start, width, hi):
    """Strict sequential left fold of ``buf[start : start+width]``.

    Aggregations run as ``((s_0 + s_1) + s_2) + ...`` — a fixed summation
    order, so a degraded switch's partial fold is a *prefix* of the
    fault-free fold and the parent-side completion (:class:`FoldOp`)
    reproduces the pristine sum bit-for-bit. ``hi`` is the static loop
    bound; slots past ``width`` contribute exact zeros.
    """
    n = buf.shape[0]
    init = jnp.take(buf, jnp.clip(start, 0, n - 1), axis=0)

    def body(j, acc):
        slot = jnp.take(buf, jnp.clip(start + j, 0, n - 1), axis=0)
        return acc + jnp.where(j < width, slot, 0)

    return jax.lax.fori_loop(1, max(hi, 1), body, init)


def _apply_program(x, prog: ReduceProgram, axis: str):
    """x: local block (1, D) from shard_map -> flattened (D,)."""
    x = x.reshape(-1)
    d = x.shape[-1]
    dev = jax.lax.axis_index(axis)
    buf = jnp.zeros((prog.n_slots, d), x.dtype).at[0].set(x)
    sl = jnp.arange(prog.n_slots)
    for op in prog.ops:
        if isinstance(op, PermuteRound):
            sent = buf[: op.slab]
            recv = jax.lax.ppermute(sent, axis, op.perm)
            off = jnp.asarray(op.recv_offset)[dev]
            cnt = jnp.asarray(op.recv_count)[dev]
            rsl = jnp.arange(op.slab)
            mask = (rsl < cnt)[:, None]
            idx = jnp.clip(off + rsl, 0, prog.n_slots - 1)
            buf = buf.at[idx].add(jnp.where(mask, recv, 0))
        elif isinstance(op, CompressOp):
            flag = jnp.asarray(op.flag)[dev]
            width = jnp.asarray(op.width)[dev]
            s = _left_fold(buf, 0, width, prog.n_slots)
            # fold lands in slot 0, slots [1, width) clear; slots >= width
            # keep a degraded switch's raw overflow for the spill upward
            folded = jnp.where((sl == 0)[:, None], s[None, :],
                               jnp.where((sl < width)[:, None], 0, buf))
            buf = jnp.where(flag, folded, buf)
        elif isinstance(op, FoldOp):
            start = jnp.asarray(op.start)[dev]
            cnt = jnp.asarray(op.count)[dev]
            # continue the child's fold: acc starts at its partial P'.
            # Idle devices (cnt == 0) keep their buffer bitwise untouched.
            acc = _left_fold(buf, start, cnt, op.span)
            buf = jnp.where(cnt > 0, buf.at[start].set(acc), buf)
        else:  # CompactOp: static gather back to the fault-free layout
            idx = jnp.asarray(op.src)[dev]
            gathered = jnp.take(buf, jnp.clip(idx, 0, prog.n_slots - 1),
                                axis=0)
            buf = jnp.where((idx >= 0)[:, None], gathered, 0)
    # destination d: aggregate the root's outgoing messages (same strict
    # left fold — completing a degraded root's spill), broadcast back
    local = _left_fold(buf, 0, prog.root_count, prog.root_count)
    local = jnp.where(dev == prog.root_home, local, 0)
    return jax.lax.psum(local, axis)


def reduce_local(x, prog: ReduceProgram, axis: str = "data"):
    """SOAR-reduce a per-device value *inside* an existing shard_map body.

    x: the device-local array (any shape); returns the global sum,
    replicated. Used by the training driver to reduce gradients with the
    SOAR program while the rest of the step stays in the same shard_map.
    """
    out = _apply_program(x.reshape(1, -1), prog, axis)
    return out.reshape(x.shape)


def tree_allreduce(x, prog: ReduceProgram, mesh, axis: str = "data"):
    """AllReduce-sum of x over `axis` following the SOAR program.

    x: (n_dev_along_axis, D) global view, or any array whose leading dim is
    sharded over `axis`.
    """
    fn = jax.shard_map(
        functools.partial(_apply_program, prog=prog, axis=axis),
        mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(axis),
        out_specs=jax.sharding.PartitionSpec(),
    )
    # one compiled program per call: an eager shard_map dispatches every
    # op of the program on its own
    return jax.jit(fn)(x)


def tree_allreduce_tree(grads, prog: ReduceProgram, mesh, axis: str = "data"):
    """Apply the SOAR collective to every leaf of a gradient pytree."""

    def one(g):
        flat = g.reshape(1, -1) if g.ndim else g.reshape(1, 1)
        out = tree_allreduce(flat, prog, mesh, axis)
        return out.reshape(g.shape)

    return jax.tree.map(one, grads)
