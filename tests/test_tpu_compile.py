"""Compile the engine's main-path programs for a described TPU v5e.

Nothing runs here: the TPU compiler is installed and compiles for a chip
that is described, not attached, and refuses what the chip's compiler
would refuse (unaligned blocks, unsupported in-kernel ops, too much fast
memory). The topology is described inside a module fixture, so only the
worker that runs this file loads the TPU library; the persistent compile
cache is off around the compiles (such entries cannot be read back
without a chip).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.collectives import build_fleet
from repro.core import bt, sample_load
from repro.core.forest import build_forest
from repro.engine import EngineOptions, batched, congestion, pallas_fold


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the backend dispatch to its TPU branch (the CPU still runs)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_fold(EngineOptions())


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


def _bt_forest(n: int, B: int):
    t = bt(n, "constant")
    return build_forest([t] * B,
                        [sample_load(t, "power-law", seed=s)
                         for s in range(B)])


def _gather_static(f, k, use_pallas):
    return dict(lvl_off=f.lvl_off, lvl_width=f.lvl_width,
                lvl_internal=f.lvl_internal, lvl_sub=f.lvl_sub, k=k,
                cap=True, use_pallas=use_pallas, interpret=False)


@pytest.mark.parametrize("n,B,k", [(128, 64, 16), (4096, 64, 64)],
                         ids=["BT128-B64-k16", "BT4096-B64-k64"])
def test_gather_level_fold_compiles_for_v5e(one_chip, on_tpu, n, B, k):
    f = _bt_forest(n, B)
    ins = _shapes(batched._device_inputs(f, jnp.float32)[:5], one_chip)
    compiled = batched._gather_packed.lower(
        *ins, **_gather_static(f, k, pallas_fold(EngineOptions()))
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is in
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**30


def test_color_compiles_for_v5e(one_chip):
    f, k = _bt_forest(128, 64), 16
    ins = batched._device_inputs(f, jnp.float32)
    blocks = jax.eval_shape(
        functools.partial(batched._gather_packed,
                          **_gather_static(f, k, False)), *ins[:5])
    kid, load, send, avail, R, par, cidx, slot_of, root = ins
    args = _shapes((blocks, kid, par, cidx, load, send, avail, R, root,
                    slot_of), one_chip)
    batched._color_packed.lower(
        *args, lvl_off=f.lvl_off, lvl_width=f.lvl_width,
        lvl_internal=f.lvl_internal, lvl_sub=f.lvl_sub, k=k,
        cap=True).compile()


class _Captured(Exception):
    pass


def test_device_driver_compiles_for_v5e(one_chip, on_tpu, monkeypatch):
    """The penalty loop with in-loop admission at T=16 on the 2-tree
    fleet, as ``Orchestrator.begin_workloads(fleet=[8, 8], ...)`` runs it
    on a TPU: captured from ``solve_fleet``, then compiled."""

    def capture(*args, **kw):
        raise _Captured(args, kw)

    monkeypatch.setattr(congestion, "_device_driver", capture)
    fleet = build_fleet(2, 2, 4, 4, spine_rho=64.0)
    tree_of = [0] * 8 + [1] * 8
    with pytest.raises(_Captured) as got:
        congestion.solve_fleet(
            [tp.tree for tp in fleet.topos],
            [fleet.topos[g].load for g in tree_of], tree_of, 4,
            core_rho=fleet.core_rho, core_path=fleet.core_path,
            residual=[np.full(tp.tree.n, 2, np.int64)
                      for tp in fleet.topos])
    args, kw = got.value.args
    assert kw["use_pallas"] and not kw["interpret"] and kw["admit"]
    monkeypatch.undo()
    compiled = congestion._device_driver.lower(
        *_shapes(args, one_chip), **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_path_loop_compiles_for_v5e(one_chip, on_tpu, monkeypatch):
    """The path-choice loop at its cell's size: a 64-tenant wave on the
    k=16 fat-tree, every tenant's 64 candidate trees (4,096 rows) in the
    fold, with the physical ledger, captured from ``plan_fleet``, then
    compiled."""
    from repro.collectives import fat_tree_fleet, plan_fleet

    def capture(*args, **kw):
        raise _Captured(args, kw)

    monkeypatch.setattr(congestion, "_device_paths", capture)
    fleet = fat_tree_fleet(16)
    counts = 1 + np.random.default_rng(0).multinomial(48, np.full(16, 1 / 16))
    with pytest.raises(_Captured) as got:
        plan_fleet(fleet, 4, counts=counts.tolist(),
                   residual=np.full(fleet.n_switches, 2, np.int64))
    args, kw = got.value.args
    assert kw["use_pallas"] and not kw["interpret"] and kw["admit"]
    assert args[0].shape[0] == 64 * 64
    monkeypatch.undo()
    compiled = congestion._device_paths.lower(
        *_shapes(args, one_chip), **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
