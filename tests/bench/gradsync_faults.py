"""Faults planted under the gradient-sync cell's timed path.

Each entry of ``FAULTS`` replaces a function of
``repro.collectives.tree_allreduce`` that the cell's driver traces (or,
for ``copy_altered``, ``Driver.call``), and returns a function that
puts it back. Used by ``gradsync_cpu_check.py``
on 4 host devices and by ``gradsync_chip_readings.py`` on the chips.
"""
from __future__ import annotations

import importlib


def _wrap(transform):
    """Replace ``reduce_local`` with ``transform(orig, x, prog, axis)``."""
    ta = importlib.import_module("repro.collectives.tree_allreduce")
    orig = ta.reduce_local

    def broken(x, prog, axis="data"):
        return transform(orig, x, prog, axis)
    ta.reduce_local = broken
    return lambda: setattr(ta, "reduce_local", orig)


def _dropped_contribution():
    import jax
    import jax.numpy as jnp
    return _wrap(lambda f, x, p, a: f(
        jnp.where(jax.lax.axis_index(a) == 3, 0, x), p, a))


def _bf16_inputs():
    import jax.numpy as jnp
    return _wrap(lambda f, x, p, a: f(
        x.astype(jnp.bfloat16).astype(x.dtype), p, a))


def _leaf_unreduced():
    """The first leaf traced: chip 0's own gradient, broadcast."""
    import jax
    import jax.numpy as jnp
    seen = []

    def t(f, x, p, a):
        seen.append(1)
        if len(seen) == 1:
            return jax.lax.psum(
                jnp.where(jax.lax.axis_index(a) == 0, x, 0), a)
        return f(x, p, a)
    return _wrap(t)


def _exchange_left_out():
    """The program without its ppermute rounds."""
    import dataclasses
    from repro.collectives.schedule import PermuteRound

    def t(f, x, p, a):
        return f(x, dataclasses.replace(p, ops=[
            op for op in p.ops if not isinstance(op, PermuteRound)]), a)
    return _wrap(t)


def _state_unchanged():
    """Every chip's answer buffer left as it was made: zeros."""
    import jax.numpy as jnp
    return _wrap(lambda f, x, p, a: jnp.zeros(x.shape, x.dtype))


def _answer_altered():
    def t(f, x, p, a):
        out = f(x, p, a)
        return out.reshape(-1).at[0].add(1.0).reshape(out.shape)
    return _wrap(t)


def _half_the_leaves():
    """A whole-tree reduce that answers the first half of the leaves."""
    ta = importlib.import_module("repro.collectives.tree_allreduce")
    had = hasattr(ta, "reduce_tree_local")
    orig = getattr(ta, "reduce_tree_local", None)

    def broken(grads, prog, axis):
        keys = sorted(grads)[: len(grads) // 2]
        return {k: ta.reduce_local(grads[k], prog, axis) for k in keys}
    ta.reduce_tree_local = broken

    def undo():
        if had:
            ta.reduce_tree_local = orig
        else:
            del ta.reduce_tree_local
    return undo


def _copy_altered():
    """One element of one chip's copy of each answer altered, the other
    copies left right: only a comparison that reads that chip's copy sees
    it."""
    import jax
    import numpy as np
    from bench.registry import Registry
    orig = Registry.driver

    def alter(tree):
        path = sorted(tree)[0]
        leaf = tree[path]
        shards = sorted(leaf.addressable_shards, key=lambda s: s.device.id)
        data = [np.array(s.data) for s in shards]
        data[2].flat[0] += 1.0
        tree = dict(tree)
        tree[path] = jax.make_array_from_single_device_arrays(
            leaf.shape, leaf.sharding,
            [jax.device_put(d, s.device) for d, s in zip(data, shards)])
        return tree

    def driver(self, name):
        base = orig(self, name)

        class Altered(base):
            def call(self, i):
                n = super().call(i)
                self.last = alter(self.last)
                if i == self.keep_at:
                    self.kept = self.last
                return n
        return Altered
    Registry.driver = driver
    return lambda: setattr(Registry, "driver", orig)


FAULTS = {"dropped_contribution": _dropped_contribution,
          "bf16_inputs": _bf16_inputs,
          "leaf_unreduced": _leaf_unreduced,
          "exchange_left_out": _exchange_left_out,
          "state_unchanged": _state_unchanged,
          "answer_altered": _answer_altered,
          "half_the_leaves": _half_the_leaves,
          "copy_altered": _copy_altered}
