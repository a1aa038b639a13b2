"""End-to-end training driver: SOAR-scheduled gradient reduction + FT.

The driver wires every substrate layer together:

  data/SyntheticLM -> models/api loss -> shard_map(grad + SOAR reduce)
  -> optim/adamw -> checkpoint/CheckpointManager, with runtime/Orchestrator
  re-sowing the blue placement on injected failures or quarantined
  stragglers.

The data-parallel gradient reduction runs the *actual* SOAR reduction
program (collectives.reduce_local) when more than one device is visible;
metrics use plain psum. On a single CPU device the same code path runs with
a trivial mesh (the program degenerates to the identity, as the paper's
model does for a single server).

Usage (CPU example sizes; see examples/train_e2e.py):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --reduced \
      --steps 50 --global-batch 8 --seq 128 --k 2 --ckpt-dir /tmp/ckpt
  # multi-device SOAR reduction (8 fake host devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --arch granite-20b --reduced --steps 20
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint import ckpt
from ..collectives import chip_level_tree
from ..collectives.tree_allreduce import reduce_local
from ..configs import ARCHS
from ..data.pipeline import DataConfig, SyntheticLM
from ..models import api
from ..models.config import ModelConfig
from ..optim import adamw
from ..optim.compression import (CompressionConfig, compress_tree,
                                 init_error_feedback, payload_bytes)
from ..runtime import Orchestrator, OrchestratorConfig
from .mesh import auto_mesh


# --preset-100m: the ~100M-parameter shape of a family (at qwen3-32b, 67M)
PRESET_100M = dict(n_layers=8, d_model=512, n_heads=8, n_kv_heads=8,
                   d_ff=2048, vocab=32_768, head_dim=0)


def dp_fleet(n_devices: int):
    """A chip-level reduction tree whose leaves are the dp devices."""
    # factor n_devices into pods x racks x chips (powers of two preferred)
    chips = 2 if n_devices % 2 == 0 and n_devices > 1 else 1
    rest = n_devices // chips
    pods = 2 if rest % 2 == 0 and rest > 1 else 1
    racks = max(1, rest // pods)
    assert pods * racks * chips == n_devices, (pods, racks, chips, n_devices)
    return chip_level_tree(n_pods=pods, racks_per_pod=racks,
                           chips_per_rack=chips)


def make_step(cfg: ModelConfig, ocfg: adamw.AdamWConfig, mesh, prog,
              grad_scale: float,
              ccfg: CompressionConfig = CompressionConfig(),
              psum: bool = False):
    """jit(shard_map(local grad [+ compress] + SOAR reduce) -> adamw).

    Compression (top-k/int8 with error feedback) happens on each worker's
    LOCAL gradient before the reduction — the paper's PS use case: sparse
    worker messages, in-network union-sum aggregation. ``psum=True``
    reduces the gradients with ``jax.lax.psum`` instead of the SOAR
    program: the reference step the SOAR reduce is checked against.
    """
    lfn = api.loss_fn(cfg)
    n_dev = prog.n_dev

    def local_grads(params, ef, batch):
        if n_dev > 1:  # per-device EF arrives with a leading shard dim of 1
            ef = jax.tree.map(lambda e: e[0], ef)
        (loss, metrics), grads = jax.value_and_grad(
            lfn, has_aux=True)(params, batch)
        grads, ef = compress_tree(grads, ef, ccfg)
        if n_dev > 1:
            ef = jax.tree.map(lambda e: e[None], ef)
            reduce = ((lambda g: jax.lax.psum(g, "data")) if psum else
                      (lambda g: reduce_local(g, prog, "data")))
            grads = jax.tree.map(
                lambda g: reduce(g) * (grad_scale / n_dev), grads)
            loss = jax.lax.pmean(loss, "data")
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "data"), metrics)
        return loss, metrics, grads, ef

    if n_dev > 1:
        sharded = jax.shard_map(
            local_grads, mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P(), P(), P("data")),
        )
    else:
        sharded = local_grads

    @jax.jit
    def step(params, opt_state, ef, batch):
        loss, metrics, grads, ef = sharded(params, ef, batch)
        params, opt_state, gnorm = adamw.update(grads, opt_state, params, ocfg)
        out = {"loss": loss, "grad_norm": gnorm, **metrics}
        return params, opt_state, ef, out

    return step


def mask_dead_batch(batch, alive, global_batch: int, n_dev: int):
    """Zero the batch shards of non-contributing devices.

    Dead/quarantined chips produce no gradient messages; their slice of
    the global batch is zeroed (a zero contribution to the sum) and the
    orchestrator's ``grad_scale`` re-normalizes the mean over survivors.
    """
    dead = np.nonzero(~np.asarray(alive, bool))[0]
    if not len(dead):
        return batch
    per = global_batch // n_dev
    mask = np.ones(global_batch, bool)
    for d in dead:
        mask[d * per:(d + 1) * per] = False
    m = jnp.asarray(mask)
    return {k: jnp.where(m[:, None] if v.ndim > 1 else m, v, 0)
            for k, v in batch.items()}


def parse_failures(spec: str | None) -> dict[int, list[int]]:
    """--fail "30:0,1;60:5" -> {30: [0, 1], 60: [5]}."""
    out: dict[int, list[int]] = {}
    if not spec:
        return out
    for part in spec.split(";"):
        step_s, devs = part.split(":")
        out[int(step_s)] = [int(d) for d in devs.split(",")]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU)")
    ap.add_argument("--preset-100m", action="store_true",
                    help="~100M-param config for the e2e example")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--k", type=int, default=2, help="SOAR blue budget")
    ap.add_argument("--strategy", default="soar")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail", default=None,
                    help='inject failures, e.g. "30:0;60:2,3" (step:devices)')
    ap.add_argument("--compress", default=None,
                    help='gradient compression: "topk:0.01" | "int8"')
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = ARCHS[args.arch]
    if args.preset_100m:
        cfg = cfg.reduced(**PRESET_100M)
    elif args.reduced:
        cfg = cfg.reduced()
    if cfg.param_count() > 1e9:
        raise SystemExit("full-size config on CPU driver; pass --reduced")
    print(f"arch={cfg.name} params={cfg.param_count():,}")

    n_dev = jax.device_count()
    mesh = auto_mesh((n_dev,), ("data",))
    topo = dp_fleet(n_dev)
    orch = Orchestrator(topo, OrchestratorConfig(k=args.k,
                                                 strategy=args.strategy))
    print(f"devices={n_dev} fleet_switches={topo.tree.n} k={args.k} "
          f"phi={orch.program.utilization:.1f} "
          f"msgs={orch.program.total_network_messages}")

    ocfg = adamw.AdamWConfig()
    ccfg = CompressionConfig.parse(args.compress)
    params = api.init_fn(cfg)(jax.random.PRNGKey(args.seed))
    opt_state = adamw.init(params, ocfg)
    if n_dev > 1:
        ef = jax.tree.map(lambda p: jnp.zeros((n_dev,) + p.shape,
                                              jnp.float32), params)
        ef = jax.device_put(ef, NamedSharding(mesh, P("data")))
    else:
        ef = init_error_feedback(params)
    if ccfg.kind != "none":
        dense_b = payload_bytes(params, CompressionConfig())
        comp_b = payload_bytes(params, ccfg)
        print(f"compression={ccfg.kind} worker payload "
              f"{dense_b/1e6:.1f} MB -> {comp_b/1e6:.2f} MB "
              f"({dense_b/comp_b:.0f}x)")
    data = SyntheticLM(cfg, DataConfig(args.global_batch, args.seq,
                                       seed=args.seed))

    mgr = ckpt.CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if mgr and args.resume:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            state, start = ckpt.restore(
                args.ckpt_dir, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            print(f"resumed from step {start}")

    failures = parse_failures(args.fail)
    step_fn = make_step(cfg, ocfg, mesh, orch.program, orch.grad_scale,
                        ccfg)
    if n_dev > 1:
        batch_sharding = NamedSharding(mesh, P("data"))
    losses = []
    t0 = time.perf_counter()
    for step in range(start, args.steps):
        if step in failures:
            orch.on_failure(failures[step])
            print(f"[step {step}] failure {failures[step]} -> replanned "
                  f"phi={orch.program.utilization:.1f} "
                  f"alive={orch.n_alive}")
            step_fn = make_step(cfg, ocfg, mesh, orch.program,
                                orch.grad_scale, ccfg)
        batch = data.batch(step)
        if n_dev > 1:
            batch = jax.tree.map(
                lambda x: jax.device_put(x, batch_sharding), batch)
            batch = mask_dead_batch(batch, orch.alive, args.global_batch,
                                    n_dev)
        params, opt_state, ef, metrics = step_fn(params, opt_state, ef,
                                                 batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t0
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({dt / max(1, step - start + 1):.2f}s/step)")
        if mgr and step > start and step % args.ckpt_every == 0:
            mgr.save(step, {"params": params, "opt": opt_state})
    if mgr:
        mgr.save(args.steps, {"params": params, "opt": opt_state})
        mgr.wait()
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    main()
