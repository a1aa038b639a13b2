"""shard_map executor for the SOAR reduction program.

Runs the paper's Reduce (Algorithm 1) as an actual JAX collective: red
switches forward message buffers upward (ppermute rounds), blue switches
collapse their buffer to a single partial sum, and the destination performs
the final aggregation + broadcast. Semantically equivalent to psum — proven
by tests — while its *network cost* equals the placement's phi, so the
SOAR-optimal placement minimizes the interconnect time of this collective.

Each device holds its messages in one ``(n_slots, *shape)`` slot buffer:
a slot is one message in the reduced value's own shape, so on the TPU it
keeps the value's tiled layout and a slot is a contiguous run of whole
tiles. The buffer is never updated by a scatter: only an op's per-device
offset and count are traced values, while ``n_slots``, each round's
``slab`` and the set of slots that any device's span can cover are fixed
when the program is built. So every update is a select written in place at
a static slot (see :func:`_put_rows`), an element-wise pass over whole
tiles that runs at memory speed.
"""
from __future__ import annotations

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


def _covered(start, count, n_slots: int) -> list:
    """The static slots that some device's span ``[start, start + count)``
    covers: the only rows an op may change on any device.

    ``build_program`` places every span inside the buffer; a span past
    ``n_slots`` would lose rows, so it is refused here, when the program
    is traced.
    """
    start, count = np.asarray(start), np.asarray(count)
    live = count > 0
    if np.any(start[live] + count[live] > n_slots):
        raise ValueError(f"a span passes the program's {n_slots} slots")
    return sorted({int(a) + j for a, c in zip(start[live], count[live])
                   for j in range(int(c))})


def _put_rows(buf, slots, row_of):
    """``buf[s] = row_of(s, buf[s])`` for each static slot ``s``.

    Each row is a ``dynamic_update_slice`` at a constant slot, which XLA
    fuses with ``row_of`` and writes in place: the work is one row per
    slot, and no slot the program never writes is touched.
    """
    for s in slots:
        buf = jax.lax.dynamic_update_index_in_dim(buf, row_of(s, buf[s]), s,
                                                  0)
    return buf


def _apply_program(x, prog: ReduceProgram, axis: str):
    """Run ``prog`` on this device's value ``x``; returns the global sum,
    replicated, in ``x``'s shape.

    The slot buffer is ``(n_slots, *x.shape)``: a slot is one message in
    the value's own shape and layout, so every slot is whole memory tiles
    and an update of one slot is contiguous. Slot updates are
    static-slot selects (:func:`_put_rows`). A ``PermuteRound`` adds
    received row ``j = s - off`` to slot ``s`` where ``0 <= j < cnt``, and
    leaves every other slot as it was: each slot gains at most one row,
    so the sums are those of a scatter-add of the valid rows, bit for bit.
    ``build_program`` raises ``n_slots`` to cover the span ``off + cnt`` of
    every delivery it lays out, so ``off + cnt <= n_slots`` holds on every
    device (:func:`_covered` refuses a program where it does not).
    """
    dev = jax.lax.axis_index(axis)
    buf = jnp.concatenate([x[None], jnp.zeros((prog.n_slots - 1, *x.shape),
                                              x.dtype)])
    # slot index, broadcast over the message
    sl = jnp.arange(prog.n_slots).reshape((-1,) + (1,) * x.ndim)
    for op in prog.ops:
        if isinstance(op, PermuteRound):
            sent = buf[: op.slab]
            recv = jax.lax.ppermute(sent, axis, op.perm)
            off = jnp.asarray(op.recv_offset)[dev]
            cnt = jnp.asarray(op.recv_count)[dev]

            def add_recv(s, row):
                j = s - off
                got = jax.lax.dynamic_index_in_dim(
                    recv, jnp.clip(j, 0, op.slab - 1), keepdims=False)
                return jnp.where((j >= 0) & (j < cnt), row + got, row)

            buf = _put_rows(buf, _covered(op.recv_offset, op.recv_count,
                                          prog.n_slots), add_recv)
        elif isinstance(op, CompressOp):
            flag = jnp.asarray(op.flag)[dev]
            width = jnp.asarray(op.width)[dev]
            s = _left_fold(buf, 0, width, prog.n_slots)
            # fold lands in slot 0, slots [1, width) clear; slots >= width
            # keep a degraded switch's raw overflow for the spill upward
            folded = jnp.where(sl == 0, s[None],
                               jnp.where(sl < width, 0, buf))
            buf = jnp.where(flag, folded, buf)
        elif isinstance(op, FoldOp):
            start = jnp.asarray(op.start)[dev]
            cnt = jnp.asarray(op.count)[dev]
            # continue the child's fold: acc starts at its partial P',
            # written at the span's first slot. Idle devices (cnt == 0)
            # keep their buffer bitwise untouched.
            acc = _left_fold(buf, start, cnt, op.span)
            buf = _put_rows(
                buf, _covered(op.start, np.minimum(op.count, 1),
                              prog.n_slots),
                lambda s, row: jnp.where((cnt > 0) & (start == s), acc, row))
        else:  # CompactOp: static gather back to the fault-free layout
            idx = jnp.asarray(op.src)[dev]
            gathered = jnp.take(buf, jnp.clip(idx, 0, prog.n_slots - 1),
                                axis=0)
            buf = jnp.where((idx >= 0).reshape(sl.shape), gathered, 0)
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
    The value keeps its shape through the program, so its slots keep the
    layout the array already has.
    """
    return _apply_program(x.reshape(x.shape or (1,)), prog,
                          axis).reshape(x.shape)


def tree_allreduce(x, prog: ReduceProgram, mesh, axis: str = "data"):
    """AllReduce-sum of x over `axis` following the SOAR program.

    x: (n_dev_along_axis, D) global view, or any array whose leading dim is
    sharded over `axis`.
    """
    fn = jax.shard_map(
        lambda b: _apply_program(b.reshape(-1), prog, axis),
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
