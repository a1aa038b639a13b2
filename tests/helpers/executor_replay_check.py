"""Subprocess helper: the shard_map executor against a numpy replay.

For each program of ``collective_check.py``'s sweep, a failed-device
program and ``degraded_check.py``'s spill programs, runs
``tree_allreduce`` (flat values) and ``reduce_local`` (values of shape
``(2, 8)``) on 8 fake devices and compares both, byte for byte, with
``replay``: a plain float32 replay of the same program, slot by slot,
with the same left-fold order and a scatter-add by plain indexing. Also
lowers ``reduce_local`` for ``dp_fleet(4)``'s k = 2 program on 4 of the
devices and counts ``stablehlo.scatter`` in the text.

Prints one line per case, ``CASE <name> ok`` or ``CASE <name> FAIL
<why>``, then ``FEATURE <name> <count>`` for the degraded ops covered, and
``SCATTERS <n>``. Run directly:
PYTHONPATH=src python tests/helpers/executor_replay_check.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import auto_mesh
from repro.collectives import (build_program, chip_level_tree,
                               degrade_switches, fail_devices, plan,
                               tree_allreduce)
from repro.collectives.schedule import (CompactOp, CompressOp, FoldOp,
                                        PermuteRound)
from repro.collectives.tree_allreduce import reduce_local
from repro.core.reduce import all_blue, all_red

N_DEV = 8
SHAPE = (2, 8)                  # each device's value in the reduce_local run


def _left_fold(slots):
    acc = slots[0].copy()
    for s in slots[1:]:
        acc = acc + s
    return acc


def replay(prog, x):
    """The program's sum of ``x`` (``(n_dev, D)`` float32), one device
    buffer at a time. Raises where a delivery passes the buffer."""
    n, d = x.shape
    buf = np.zeros((n, prog.n_slots, d), np.float32)
    buf[:, 0] = x
    for op in prog.ops:
        if isinstance(op, PermuteRound):
            recv = np.zeros((n, op.slab, d), np.float32)
            for src, dst in op.perm:
                recv[dst] = buf[src, : op.slab]
            for dev in range(n):
                off, cnt = int(op.recv_offset[dev]), int(op.recv_count[dev])
                if off + cnt > prog.n_slots:
                    raise AssertionError(f"off {off} + cnt {cnt} > n_slots "
                                         f"{prog.n_slots} on device {dev}")
                buf[dev, off: off + cnt] += recv[dev, :cnt]
        elif isinstance(op, CompressOp):
            for dev in np.nonzero(op.flag)[0]:
                w = int(op.width[dev])
                buf[dev, 0] = _left_fold(buf[dev, :w])
                buf[dev, 1:w] = 0
        elif isinstance(op, FoldOp):
            for dev in np.nonzero(op.count)[0]:
                a, c = int(op.start[dev]), int(op.count[dev])
                buf[dev, a] = _left_fold(buf[dev, a: a + c])
        else:
            for dev in range(n):
                src = op.src[dev]
                buf[dev] = np.where((src >= 0)[:, None],
                                    buf[dev, np.maximum(src, 0)], 0)
    return _left_fold(buf[prog.root_home, : prog.root_count])


def cases():
    """(name, program, x) for every case, in a fixed order."""
    topo = chip_level_tree(n_pods=2, racks_per_pod=2, chips_per_rack=2)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(N_DEV, 16)).astype(np.float32)
    out = []
    for k in (0, 1, 2, 4, topo.tree.n):
        for strategy in ("soar", "top", "max", "random"):
            _, prog = plan(topo, k, strategy=strategy)
            out.append((f"k{k}-{strategy}", prog, x))
    for name, blue in (("all_red", all_red(topo.tree)),
                       ("all_blue", all_blue(topo.tree))):
        out.append((name, build_program(topo, blue), x))
    dead = [3, 6]
    _, prog = plan(fail_devices(topo, dead), 2, strategy="soar")
    x2 = x.copy()
    x2[dead] = 0.0
    out.append(("failed_devices", prog, x2))
    # degraded_check.py's spill programs, drawn from its own generator
    t = topo.tree
    rng = np.random.default_rng(0)
    xd = rng.normal(size=(N_DEV, 16)).astype(np.float32)
    for trial in range(8):
        blue = rng.random(t.n) < 0.5
        ks = rng.choice(t.n, size=int(rng.integers(1, 4)), replace=False)
        scales = {int(s): float(rng.choice([0.75, 0.5, 0.25, 0.05]))
                  for s in ks}
        out.append((f"spill{trial}",
                    build_program(degrade_switches(topo, scales), blue), xd))
    out.append(("degraded_root",
                build_program(degrade_switches(topo, {int(t.root): 0.05}),
                              np.ones(t.n, bool)), xd))
    # all blue with degraded racks (depth 2) and a pod (depth 1): spills
    # that the parent completes with a FoldOp
    racks = [v for v in range(t.n) if t.depth[v] == 2]
    pods = [v for v in range(t.n) if t.depth[v] == 1]
    for name, scales in (("spill_rack", {racks[0]: 0.5}),
                         ("spill_two_racks", {racks[0]: 0.25,
                                              racks[3]: 0.5}),
                         ("spill_pod_and_rack", {pods[0]: 0.5,
                                                 racks[1]: 0.5})):
        out.append((name, build_program(degrade_switches(topo, scales),
                                        np.ones(t.n, bool)), xd))
    return out


def features(progs) -> dict:
    ops = [op for p in progs for op in p.ops]
    return {"slab_gt_1": sum(isinstance(op, PermuteRound) and op.slab > 1
                             for op in ops),
            "FoldOp": sum(isinstance(op, FoldOp) for op in ops),
            "CompactOp": sum(isinstance(op, CompactOp) for op in ops),
            "root_count_gt_1": sum(p.root_count > 1 for p in progs)}


def scatters_in_cell_program() -> int:
    from repro.launch.train import dp_fleet
    from repro.runtime import Orchestrator, OrchestratorConfig
    prog = Orchestrator(dp_fleet(4), OrchestratorConfig(k=2)).program
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    f = jax.jit(jax.shard_map(lambda g: reduce_local(g[0], prog, "data"),
                              mesh=mesh, in_specs=P("data"),
                              out_specs=P()))
    txt = f.lower(jax.ShapeDtypeStruct((4, 64, 128), jnp.float32)).as_text()
    return txt.count("stablehlo.scatter")


def main():
    assert jax.device_count() == N_DEV, jax.device_count()
    mesh = auto_mesh((N_DEV,), ("data",))
    all_cases = cases()
    for name, prog, x in all_cases:
        try:
            want = replay(prog, x)
            with mesh:
                flat = np.asarray(tree_allreduce(jnp.asarray(x), prog, mesh,
                                                 "data"))
                shaped = jax.jit(jax.shard_map(
                    lambda g: reduce_local(g[0], prog, "data"), mesh=mesh,
                    in_specs=P("data"), out_specs=P()))(
                        jnp.asarray(x).reshape(N_DEV, *SHAPE))
            shaped = np.asarray(shaped)
            if flat.tobytes() != want.tobytes():
                raise AssertionError("tree_allreduce differs from replay")
            if shaped.shape != SHAPE or shaped.tobytes() != want.tobytes():
                raise AssertionError("reduce_local differs from replay")
            print(f"CASE {name} ok", flush=True)
        except Exception as e:  # reported per case
            print(f"CASE {name} FAIL {type(e).__name__}: {e}".replace(
                "\n", " "), flush=True)
    for k, v in features([p for n, p, _ in all_cases
                          if n.startswith(("spill", "degraded"))]).items():
        print(f"FEATURE {k} {v}")
    print(f"SCATTERS {scatters_in_cell_program()}")


if __name__ == "__main__":
    main()
