"""Closed loop of whole-gradient SOAR all-reduces over a data-parallel
group of chips.

Configuration keys: ``fabric`` (``n_devices``, the budget ``k`` and the
reduction tree as ``parent``, ``rho``, ``load`` and ``device_leaf`` lists,
which must equal the program's ``repro.launch.train.dp_fleet``) and
``leaves`` (``[path, shape, dtype]`` of every gradient leaf one chip
holds). Traffic key: ``check_first_calls`` (the compared call other than
the last is drawn from the seed among the first this many).

Set-up places the budget with ``Orchestrator(dp_fleet(n), k)``, whose
``program`` is the SOAR reduce program, and makes each chip's float32
gradient leaves on the device from the seed (standard normals, a key per
chip and leaf), one jitted call. The timed path is the training step's
reduce: one jitted ``shard_map`` over the ``data`` axis that reduces the
whole gradient pytree with ``repro.collectives.tree_allreduce``'s
``reduce_tree_local(grads, prog, axis)`` where the program has it, and
otherwise maps ``reduce_local`` over the leaves, as ``make_step`` does.
A call is one all-reduce and returns once every leaf of its sum is
ready.

After the window, two syncs are compared with the plain reference
(``bench/gradsync_reference.py``): one drawn from the seed among the
first ``check_first_calls`` calls (its answer kept on the device) and the
window's last. Every element of every leaf has to lie within the
rounding of three float32 additions of the float64 sum of the four
contributions, read back from the device leaf by leaf. The first chip's
copy of each answer is read; another chip's copy is read too, and
compared, where its digest differs from the first's.
"""
from __future__ import annotations

import concurrent.futures as cf
import importlib
import math
import time

import numpy as np

from bench import generator
from bench import gradsync_reference as ref

AXIS = "data"
CHUNK = 1 << 22             # elements per block of the host reference
THREADS = 8


def program_reduce():
    """The program's reduce of a gradient pytree inside a shard_map body:
    ``reduce_tree_local`` where the program has it, else ``reduce_local``
    over the leaves."""
    ta = importlib.import_module("repro.collectives.tree_allreduce")
    whole = getattr(ta, "reduce_tree_local", None)
    if whole is not None:
        return whole
    import jax
    return lambda grads, prog, axis: jax.tree.map(
        lambda g: ta.reduce_local(g, prog, axis), grads)


def _blocks(n: int):
    return [(a, min(a + CHUNK, n)) for a in range(0, n, CHUNK)]


class Driver:
    def __init__(self, config, traffic, seed, seconds, devices):
        from jax.sharding import Mesh
        from repro.launch.train import dp_fleet
        from repro.runtime import Orchestrator, OrchestratorConfig
        fab = config["fabric"]
        self.n_dev, self.k = int(fab["n_devices"]), int(fab["k"])
        if len(devices) != self.n_dev:
            raise RuntimeError(f"the fabric has {self.n_dev} chips, the "
                               f"cell was given {len(devices)}")
        self.parent = np.asarray(fab["parent"], np.int64)
        self.rho = np.asarray(fab["rho"], np.float64)
        self.load = np.asarray(fab["load"], np.int64)
        self.device_leaf = np.asarray(fab["device_leaf"], np.int64)
        topo = dp_fleet(self.n_dev)
        if not (np.array_equal(topo.tree.parent, self.parent)
                and np.array_equal(topo.tree.rho, self.rho)
                and np.array_equal(topo.load, self.load)
                and np.array_equal(topo.device_leaf, self.device_leaf)):
            raise RuntimeError("the program's dp_fleet differs from the "
                               "configuration's fabric")
        self.leaves = [(p, tuple(int(x) for x in s), d)
                       for p, s, d in config["leaves"]]
        if any(d != "float32" for _, _, d in self.leaves):
            raise ValueError("the driver makes float32 gradients only")
        self.grad_bytes = 4 * sum(math.prod(s) for _, s, _ in self.leaves)
        self.mesh = Mesh(np.asarray(devices), (AXIS,))
        self.orch = Orchestrator(topo, OrchestratorConfig(k=self.k))
        self.prog = self.orch.program
        self.blue = np.asarray(self.orch.blue, bool).copy()
        g = generator.rng(seed, 0)
        self.key = int(g.integers(0, 2 ** 31))
        self.keep_at = int(g.integers(0, int(traffic["check_first_calls"])))
        self.x = self.sync = None
        self.kept = self.last = None
        self.calls = 0

    # -- set-up and the timed path --------------------------------------------

    def _make_inputs(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        leaves = self.leaves

        def gen(key):
            k = jax.random.fold_in(key, jax.lax.axis_index(AXIS))
            return {p: jax.random.normal(jax.random.fold_in(k, i), (1, *s),
                                         jnp.float32)
                    for i, (p, s, _) in enumerate(leaves)}
        f = jax.jit(jax.shard_map(gen, mesh=self.mesh, in_specs=P(),
                                  out_specs=P(AXIS)))
        return f(jax.random.key(self.key))

    def _make_sync(self):
        import jax
        from jax.sharding import PartitionSpec as P
        reduce_tree, prog = program_reduce(), self.prog

        def body(grads):
            return reduce_tree({p: g[0] for p, g in grads.items()}, prog,
                               AXIS)
        return jax.jit(jax.shard_map(body, mesh=self.mesh,
                                     in_specs=P(AXIS), out_specs=P()))

    def warm(self):
        import jax
        self.x = self._make_inputs()
        self.sync = self._make_sync()
        for _ in range(2):
            jax.block_until_ready(self.sync(self.x))

    def call(self, i: int) -> int:
        import jax
        self.last = None                    # the kept answer stays alive
        out = jax.block_until_ready(self.sync(self.x))
        self.last = out
        if i == self.keep_at:
            self.kept = out
        self.calls = i + 1
        return 1

    def after(self, i: int):
        pass

    def counters(self) -> dict:
        return {}

    def release(self):
        """Drops the compiled sync and the orchestrator; the inputs and
        the compared answers stay for the reference."""
        self.sync = self.orch = self.prog = None

    # -- the comparison ----------------------------------------------------------

    def _answers(self) -> list:
        out = [self.kept] if self.kept is not None else []
        if self.last is not None and self.calls - 1 != self.keep_at:
            out.append(self.last)
        return out

    def check(self, control=False) -> dict:
        """Every element of every leaf of the compared syncs against the
        float64 sum; with ``control`` the answers are the reference's sum
        computed in bfloat16 instead. A chip's copy of an answer leaf is
        read where its digest differs from the first chip's, so a copy
        that differs is compared too."""
        t0 = time.perf_counter()
        answers = [] if control else self._answers()
        # a window that completed no sync has nothing to compare
        unanswered = int(not control and not answers)
        bad = [False] * max(len(answers), 1)
        got = []
        for j, a in enumerate(answers):
            leaves = {}
            for path, shape, _ in self.leaves:
                leaf = a.get(path) if isinstance(a, dict) else None
                if leaf is None or tuple(leaf.shape) != shape:
                    unanswered += 1
                    bad[j] = True
                    leaf = None
                leaves[path] = leaf
            got.append(leaves)
        agree = [self._copies_agree(g) for g in got]
        # every chip's contribution as an array of its own, each transfer
        # started before the first is read
        parts = {p: [sh.data for sh in sorted(self.x[p].addressable_shards,
                                              key=lambda sh: sh.device.id)]
                 for p, _, _ in self.leaves}
        for path, _, _ in self.leaves:
            for a in parts[path]:
                a.copy_to_host_async()
            for g in got:
                if g[path] is not None:
                    g[path].copy_to_host_async()
        n_excess = 0
        with cf.ThreadPoolExecutor(THREADS) as pool:
            for path, _, _ in self.leaves:
                contribs = [np.asarray(a).reshape(-1)
                            for a in parts.pop(path)]
                reps = []
                for g, same in zip(got, agree):
                    leaf = g[path]
                    if leaf is None:
                        reps.append([])
                    elif same[path]:
                        reps.append([np.asarray(leaf).reshape(-1)])
                    else:
                        reps.append([np.asarray(sh.data).reshape(-1)
                                     for sh in leaf.addressable_shards])
                counts = pool.map(
                    lambda blk: _block_excess(contribs, reps, blk, control),
                    _blocks(contribs[0].size))
                for per_answer in counts:
                    for j, c in enumerate(per_answer):
                        n_excess += c
                        bad[j] = bad[j] or c > 0
        self.x = self.kept = self.last = None
        print(f"[reference] seconds={time.perf_counter() - t0} "
              f"answers={len(answers)} control={bool(control)}", flush=True)
        return {"checks": [("grad_excess", float(n_excess), 0.0),
                           ("unanswered", float(unanswered), 0.0)],
                "failed": int(sum(bad))}

    def _copies_agree(self, leaves: dict) -> dict:
        """Per leaf: whether every chip's copy has the first chip's
        digest (two 32-bit sums of its bits, weighted by position)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        have = {p: x for p, x in leaves.items() if x is not None}

        def body(tree):
            out = {}
            for p, x in tree.items():
                b = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32)
                i = jnp.arange(b.size, dtype=jnp.uint32)
                h = jnp.stack([
                    jnp.sum(b * (2 * i + 1), dtype=jnp.uint32),
                    jnp.sum(b ^ (i * jnp.uint32(0x9E3779B9)),
                            dtype=jnp.uint32)])
                out[p] = h[None]
            return out
        digest = jax.jit(jax.shard_map(body, mesh=self.mesh, in_specs=P(),
                                       out_specs=P(AXIS), check_vma=False))
        rows = jax.device_get(digest(have)) if have else {}
        return {p: bool((r == r[0]).all()) for p, r in rows.items()}


def _block_excess(contribs, reps, blk, control) -> list:
    """Elements outside the bound in block ``blk``: per compared answer,
    or of the control's bfloat16 sum."""
    a, b = blk
    part = [c[a:b] for c in contribs]
    s, bound = ref.sum_and_bound(part)
    if control:
        return [ref.excess(ref.sum_bf16(part), s, bound)]
    return [sum(ref.excess(c[a:b], s, bound) for c in copies)
            for copies in reps]
