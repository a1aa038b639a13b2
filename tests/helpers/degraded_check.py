"""Subprocess helper: degraded-mode execution on 8 fake devices.

Two checks that need a real multi-device mesh (forced device count must
be set before jax initializes, hence a separate process):

  1. **executor bitwise identity** — for random placements and random
     partial-capacity degradations, the shard_map executor's degraded
     (spilling) program returns the global sum *bit-for-bit* equal to
     the fault-free program's;
  2. **training-coupled chaos** — a ChaosTrainer over the 8-device dp
     fleet steps through degrade/crash events with every lossless
     recovery asserted bit-identical at the full optimizer-step level
     and checkpoint restarts verified.

Run directly:  PYTHONPATH=src python tests/helpers/degraded_check.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import auto_mesh
from repro.collectives import (build_program, chip_level_tree,
                               degrade_switches, tree_allreduce)


def check_executor_bitwise():
    assert jax.device_count() == 8, jax.device_count()
    mesh = auto_mesh((8,), ("data",))
    topo = chip_level_tree(n_pods=2, racks_per_pod=2, chips_per_rack=2)
    t = topo.tree
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)

    checked = 0
    for trial in range(8):
        blue = rng.random(t.n) < 0.5
        with mesh:
            ref = np.asarray(tree_allreduce(x, build_program(topo, blue),
                                            mesh, "data"))
        np.testing.assert_allclose(ref, np.asarray(x).sum(0), rtol=1e-5,
                                   atol=1e-5)
        ks = rng.choice(t.n, size=int(rng.integers(1, 4)), replace=False)
        scales = {int(s): float(rng.choice([0.75, 0.5, 0.25, 0.05]))
                  for s in ks}
        td = degrade_switches(topo, scales)
        pd = build_program(td, blue)
        with mesh:
            got = np.asarray(tree_allreduce(x, pd, mesh, "data"))
        assert got.tobytes() == ref.tobytes(), (trial, scales)
        checked += 1
    # degraded root: overflow completes at the destination
    blue = np.ones(t.n, bool)
    with mesh:
        ref = np.asarray(tree_allreduce(x, build_program(topo, blue),
                                        mesh, "data"))
    td = degrade_switches(topo, {int(t.root): 0.05})
    pd = build_program(td, blue)
    assert pd.root_count > 1
    with mesh:
        got = np.asarray(tree_allreduce(x, pd, mesh, "data"))
    assert got.tobytes() == ref.tobytes()
    print(f"executor: {checked + 1} degraded cases bitwise-identical")


def check_training_coupled_chaos():
    from repro.launch.train import dp_fleet
    from repro.runtime import (ChaosHarness, ChaosTrainer, Orchestrator,
                               OrchestratorConfig)
    from repro.runtime.faults import FaultEvent

    topo = dp_fleet(8)
    orch = Orchestrator(topo, OrchestratorConfig(k=2))
    blue = [int(s) for s in np.nonzero(orch.blue)[0]]
    with tempfile.TemporaryDirectory() as d:
        trainer = ChaosTrainer(orch, seq=16, global_batch=8, ckpt_dir=d,
                               ckpt_every=2)
        h = ChaosHarness(orch, trainer=trainer)
        events = [
            FaultEvent("degrade_switch", rates=((blue[0], 0.5),)),
            FaultEvent("degrade_switch", rates=((blue[1], 0.25),)),
            FaultEvent("crash"),
            FaultEvent("recover_switch_capacity", rates=((blue[0], 1.0),)),
            FaultEvent("fail_device", devices=(3,)),
            FaultEvent("crash"),
            FaultEvent("recover_device", devices=(3,)),
            FaultEvent("recover_switch_capacity", rates=((blue[1], 1.0),)),
        ]
        report = h.run(events)
    tr = report.train
    assert tr["steps"] == len(events), tr
    assert tr["restores"] == 2, tr
    # the two blue degrades kept placement + devices -> bitwise-checked
    assert tr["bitwise_checks"] >= 2, tr
    assert report.invariant_checks == len(events)
    print(f"train: {tr['steps']} steps, {tr['bitwise_checks']} bitwise "
          f"checks, {tr['restores']} restarts, loss {tr['first_loss']:.3f} "
          f"-> {tr['last_loss']:.3f}")


def main():
    check_executor_bitwise()
    check_training_coupled_chaos()
    print("DEGRADED_CHECK_OK")


if __name__ == "__main__":
    main()
