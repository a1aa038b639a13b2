"""Subprocess helper: EP (shard_map all-to-all) MoE == dense-dispatch MoE.

Run directly:  PYTHONPATH=src python tests/helpers/moe_ep_check.py
Forced device count must precede jax init, hence a separate process.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import auto_mesh
from repro.configs import ARCHS
from repro.models import moe
from repro.parallel.sharding import axis_rules, make_rules


def main():
    assert jax.device_count() == 8
    mesh = auto_mesh((2, 4), ("data", "model"))
    # high capacity factor -> no drops -> EP must match dense exactly
    cfg = ARCHS["deepseek-v2-236b"].reduced(
        n_experts=8, top_k=2, d_ff_expert=32, capacity_factor=8.0,
        n_shared_experts=1, dtype="float32")
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    B, T = 4, 16                                       # N=64, divisible by 8
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model),
                          jnp.float32)

    y_dense, aux_dense = jax.jit(
        lambda p, x: moe._moe_forward_dense(p, x, cfg))(p, x)

    def loss_dense(p):
        y, aux = moe._moe_forward_dense(p, x, cfg)
        return jnp.sum(y ** 2) + 0.01 * aux

    def loss_ep(p):
        y, aux = moe.moe_forward(p, x, cfg)
        return jnp.sum(y ** 2) + 0.01 * aux

    g_dense = jax.jit(jax.grad(loss_dense))(p)
    rules = make_rules(multi_pod=False)
    key = lambda kv: str(kv[0])

    for mode in ("replicated", "a2a"):
        moe.EP_MODE = mode
        with mesh, axis_rules(rules, mesh):
            y_ep, aux_ep = jax.jit(
                lambda p, x: moe.moe_forward(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                                   rtol=2e-5, atol=2e-5, err_msg=mode)
        np.testing.assert_allclose(float(aux_ep), float(aux_dense),
                                   rtol=1e-5, err_msg=mode)
        with mesh, axis_rules(rules, mesh):
            g_ep = jax.jit(jax.grad(loss_ep))(p)
        for (kd, ld), (ke, le) in zip(
                sorted(jax.tree_util.tree_leaves_with_path(g_dense), key=key),
                sorted(jax.tree_util.tree_leaves_with_path(g_ep), key=key)):
            np.testing.assert_allclose(np.asarray(le), np.asarray(ld),
                                       rtol=5e-4, atol=5e-5,
                                       err_msg=f"{mode} {kd}")
    moe.EP_MODE = "replicated"
    print("MOE_EP_CHECK_OK")


if __name__ == "__main__":
    main()
