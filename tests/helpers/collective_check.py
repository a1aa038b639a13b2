"""Subprocess helper: validate tree_allreduce == psum on 8 fake devices.

Run directly:  PYTHONPATH=src python tests/helpers/collective_check.py
(The forced device count must be set before jax initializes, hence a
separate process from the main pytest run.)
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import auto_mesh
from repro.collectives import (
    build_program, chip_level_tree, fail_devices, plan, tree_allreduce,
)
from repro.core.reduce import all_blue, all_red


def main():
    assert jax.device_count() == 8, jax.device_count()
    mesh = auto_mesh((8,), ("data",))
    topo = chip_level_tree(n_pods=2, racks_per_pod=2, chips_per_rack=2)
    assert topo.n_devices == 8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    want = np.asarray(x).sum(0)

    # every distinct placement of the sweep (k = 0 is all-red and k = n
    # all-blue for every strategy, so each placement runs once)
    progs = {}
    for k in (0, 1, 2, 4, topo.tree.n):
        for strategy in ("soar", "top", "max", "random"):
            blue, prog = plan(topo, k, strategy=strategy)
            progs.setdefault(np.asarray(blue, bool).tobytes(), prog)
    for blue in (all_red(topo.tree), all_blue(topo.tree)):
        progs.setdefault(np.asarray(blue, bool).tobytes(),
                         build_program(topo, blue))
    checked = 0
    for prog in progs.values():
        with mesh:
            got = tree_allreduce(x, prog, mesh, "data")
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)
        checked += 1

    # SOAR cost dominance across programs at equal budget
    _, p_soar = plan(topo, 2, strategy="soar")
    for s in ("top", "max", "random"):
        _, p_other = plan(topo, 2, strategy=s)
        assert p_soar.utilization <= p_other.utilization + 1e-9

    # fault tolerance: kill two chips, re-plan, reduce the survivors
    dead = [3, 6]
    topo2 = fail_devices(topo, dead)
    blue2, prog2 = plan(topo2, 2, strategy="soar")
    x2 = np.asarray(x).copy()
    x2[dead] = 0.0  # dead devices contribute nothing
    with mesh:
        got = tree_allreduce(jnp.asarray(x2), prog2, mesh, "data")
    np.testing.assert_allclose(np.asarray(got), x2.sum(0), rtol=1e-5,
                               atol=1e-5)
    checked += 1
    print(f"COLLECTIVE_CHECK_OK checked={checked}")


if __name__ == "__main__":
    main()
