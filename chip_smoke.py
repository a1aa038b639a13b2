#!/usr/bin/env python3
"""Smoke run of the SOAR placement service's main path on a TPU.

    python chip_smoke.py                  # one chip
    python chip_smoke.py --four-chips     # four chips: the SOAR collective
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny CPU dry run

One chip, two phases, both through the entry points a user calls:

1. ``solve_batch`` at the paper's largest Fig. 10 size: BT(4096) with
   constant (dyadic) rates, 64 power-law tenants drawn from ``--seed``,
   k = 64 (the sqrt(n) rule), default ``EngineOptions``. Four tenants'
   costs must equal serial ``soar_fast`` exactly and their masks must
   re-measure (``phi``) to the same cost; then the other level-fold path
   must give identical masks and costs.
2. The decision path: an ``Orchestrator`` over ``build_fleet`` (2 trees of
   2 pods x 4 racks x 4 chips, the ``benchmarks/fleet.py`` fleet) with
   per-switch capacity 2 admits 16 tenants through the device-resident
   penalty loop with in-loop admission; the admitted masks must equal the
   host-ledger replay (``device_loop=False``) bit for bit. Then a blue
   switch fails, and the cache-or-solve recovery must return an optimal
   placement that avoids it.

``--four-chips`` runs only the data-parallel gradient collective: the SOAR
program (``tree_allreduce``) against ``jax.lax.psum`` on a 4-device
``data`` mesh over ``dp_fleet(4)`` with a 64 MiB-per-device buffer, and
one ``make_step`` of the ``--preset-100m`` training config (float32
parameters) with the SOAR reduce against the same step with ``psum``.

Each phase prints the level fold it ran, its layout, compile seconds, the
seconds of a warm run, ``peak_bytes_in_use`` and the checks that passed.
These are smoke readings, not benchmark numbers. A failed check or an
exception ends the run non-zero. The last line, printed only on a TPU
after every phase passed, is ``{"ok": true, "device": {...}}``.
``--rehearse`` runs the same phases at a tiny size on whatever backend
JAX finds (on the CPU with the Pallas kernel interpreted) and exits 3
without that line: a rehearsal is never a result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

REHEARSAL_EXIT = 3


def _peak(dev) -> str:
    stats = dev.memory_stats()
    return "n/a" if not stats else str(stats.get("peak_bytes_in_use", "n/a"))


def _report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _timed(fn):
    """(result, wall seconds, compile seconds) of one call."""
    from repro.launch.compile_cache import compile_stats
    c0 = compile_stats()["compile_s"]
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, compile_stats()["compile_s"] - c0


def _check(ok: bool, what: str) -> str:
    if not ok:
        raise AssertionError(f"check failed: {what}")
    return what


def phase_batch(dev, seed: int, rehearse: bool) -> None:
    from repro.core import bt, phi, sample_load, soar_fast
    from repro.core.forest import build_forest
    from repro.engine import EngineOptions, pallas_fold, solve_batch

    n, B, k = (128, 8, 8) if rehearse else (4096, 64, 64)
    t = bt(n, "constant")
    seeds = np.random.default_rng(seed).integers(0, 2**31 - 1, B)
    loads = [sample_load(t, "power-law", seed=int(s)) for s in seeds]
    # the CPU has no Mosaic: a rehearsal runs the kernel interpreted
    opts = (EngineOptions(use_pallas=True, interpret=True) if rehearse
            else EngineOptions())
    fold = "pallas" if pallas_fold(opts) else "jnp"
    f = build_forest([t] * B, loads)
    layout = (f"BT({n}):n={t.n},B={B},k={k},levels={f.h_max + 1},"
              f"max_children={f.max_children}")

    res, first_s, compile_s = _timed(
        lambda: solve_batch([t] * B, loads, k, options=opts))
    again, warm_s, _ = _timed(
        lambda: solve_batch([t] * B, loads, k, options=opts))
    checks = [_check(np.array_equal(again.blue, res.blue)
                     and np.array_equal(again.costs, res.costs),
                     "warm_run_identical")]
    picks = sorted({0, B // 3, (2 * B) // 3, B - 1})
    for b in picks:
        ref = soar_fast(t, loads[b], k)
        blue = res.blue_of(b)
        _check(res.costs[b] == ref.cost,
               f"tenant {b}: engine cost {res.costs[b]} != serial {ref.cost}")
        _check(phi(t, loads[b], blue) == res.costs[b] and blue.sum() <= k,
               f"tenant {b}: mask re-measures to its cost within budget")
    checks.append(f"costs==soar_fast,phi(mask)==cost@tenants{picks}")
    _report("batch", level_fold=fold, layout=layout,
            compile_s=f"{compile_s:.3f}", first_call_s=f"{first_s:.3f}",
            warm_s=f"{warm_s:.3f}", peak_bytes_in_use=_peak(dev),
            bytes_to_host=res.bytes_to_host, checks=",".join(checks))

    other = "jnp" if fold == "pallas" else "pallas"
    if other == "pallas" and not rehearse:
        _report("batch_other_fold", skipped="the jnp fold ran above and "
                "this backend has no Mosaic kernel to compare it with")
        return
    alt_opts = opts.replace(use_pallas=other == "pallas",
                            interpret=rehearse and other == "pallas")
    alt, first_s, compile_s = _timed(
        lambda: solve_batch([t] * B, loads, k, options=alt_opts))
    _check(np.array_equal(alt.blue, res.blue)
           and np.array_equal(alt.costs, res.costs),
           f"{other} fold masks and costs identical to {fold}")
    _report("batch_other_fold", level_fold=other, layout=layout,
            compile_s=f"{compile_s:.3f}", first_call_s=f"{first_s:.3f}",
            peak_bytes_in_use=_peak(dev),
            checks=f"masks_and_costs_identical_to_{fold}")


def phase_orchestrator(dev, rehearse: bool) -> None:
    from repro.collectives import build_fleet
    from repro.core import soar_fast
    from repro.engine import EngineOptions, pallas_fold
    from repro.runtime import Orchestrator, OrchestratorConfig

    pods, racks, chips, per_tree = (2, 2, 2, 3) if rehearse else (2, 4, 4, 8)
    k, capacity = 4, 2
    fleet = build_fleet(2, pods, racks, chips, spine_rho=64.0)
    counts = [per_tree, per_tree]
    kw = dict(fleet=counts, congestion_aware=True, device_admission=True)
    if rehearse:
        kw["options"] = EngineOptions(use_pallas=True, interpret=True)
    fold = "pallas" if pallas_fold(kw.get("options", EngineOptions())) \
        else "jnp"

    def orch():
        return Orchestrator(fleet, OrchestratorConfig(k=k, capacity=capacity))

    _, first_s, compile_s = _timed(lambda: orch().begin_workloads(**kw))
    o = orch()
    _, warm_s, _ = _timed(lambda: o.begin_workloads(**kw))
    ref = orch()
    ref.begin_workloads(**kw, device_loop=False)
    checks = []
    a = o.last_admission
    _check(a["path"] == "device" and a["collisions"] == 0
           and all((r >= 0).all() for r in o._residuals),
           "device admission feasible with no collision fallback")
    got = [j.blue for j in sorted(o.jobs.values(), key=lambda j: j.order)]
    want = [j.blue for j in sorted(ref.jobs.values(), key=lambda j: j.order)]
    _check(len(got) == sum(counts) == len(want)
           and all(np.array_equal(g, w) for g, w in zip(got, want)),
           "admitted masks == host-ledger reference")
    _check(o.last_congestion.history == ref.last_congestion.history,
           "round history == host-ledger reference")
    checks.append(f"admitted_masks==host_ledger({sum(counts)}_tenants,"
                  f"{o.last_congestion.rounds}_rounds,"
                  f"dropped={a['dropped']})")

    blues = np.nonzero(o.blue)[0]
    _check(len(blues) > 0, "the orchestrator's own placement has a blue")
    s = int(blues[0])
    _, fail_s, fail_compile_s = _timed(lambda: o.on_switch_failure([s]))
    ev = o.degraded_events[-1]
    avail = o.topo.candidates(o._replan_avail())
    best = soar_fast(o.topo.tree, o.topo.load, k, avail=avail)
    _check(not o.blue[s], f"recovered placement avoids failed switch {s}")
    _check(o.program.utilization == best.cost,
           f"recovered utilization {o.program.utilization} == serial "
           f"optimum {best.cost}")
    checks.append(f"switch_{s}_failure_recovery_optimal("
                  f"cache_hit={ev['cache_hit']})")
    layout = (f"fleet:2x({pods}x{racks}x{chips}),switches/tree="
              f"{fleet.topos[0].tree.n},T={sum(counts)},k={k},"
              f"capacity={capacity}")
    _report("orchestrator", level_fold=fold, layout=layout,
            compile_s=f"{compile_s:.3f}", first_call_s=f"{first_s:.3f}",
            warm_s=f"{warm_s:.3f}", recovery_s=f"{fail_s:.3f}",
            recovery_compile_s=f"{fail_compile_s:.3f}",
            peak_bytes_in_use=_peak(dev), checks=",".join(checks))


def phase_four_chips(devs, seed: int, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.collectives import tree_allreduce
    from repro.configs import ARCHS
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import auto_mesh
    from repro.launch.train import PRESET_100M, dp_fleet, make_step
    from repro.models import api
    from repro.optim import adamw
    from repro.runtime import Orchestrator, OrchestratorConfig

    _check(len(devs) == 4, f"four devices, found {len(devs)}")
    mesh = auto_mesh((4,), ("data",))
    orch = Orchestrator(dp_fleet(4), OrchestratorConfig(k=2))
    prog = orch.program
    shard = NamedSharding(mesh, P("data"))

    # integer-valued float32 gradients: any summation order is exact, so
    # the SOAR program must equal psum bit for bit
    D = (1 << 12) if rehearse else (16 << 20)          # 64 MiB per device
    x = jax.jit(lambda key: jax.random.randint(key, (4, D), -512, 512)
                .astype(jnp.float32), out_shardings=shard)(
                    jax.random.PRNGKey(seed))
    _check(len(x.sharding.device_set) == 4, "buffer spans all four devices")
    soar = jax.jit(lambda v: tree_allreduce(v, prog, mesh))
    psum = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v.reshape(-1), "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P()))
    got, _, soar_compile = _timed(lambda: soar(x).block_until_ready())
    _, soar_warm, _ = _timed(lambda: soar(x).block_until_ready())
    want = psum(x).block_until_ready()
    _, psum_warm, _ = _timed(lambda: psum(x).block_until_ready())
    _check(bool(jnp.array_equal(got, want)), "tree_allreduce == psum")
    _report("soar_allreduce", layout=f"mesh=data:4,dp_fleet(4):"
            f"switches={orch.topo.tree.n},blue={int(orch.blue.sum())},"
            f"D={D}xfloat32/device", compile_s=f"{soar_compile:.3f}",
            soar_warm_s=f"{soar_warm:.4f}", psum_warm_s=f"{psum_warm:.4f}",
            peak_bytes_in_use=_peak(devs[0]),
            checks="tree_allreduce==psum(bitwise)")

    # float32 parameters: the two steps' updates then compare at float32
    # tolerance (they differ only in the gradient summation order)
    cfg = ARCHS["qwen3-32b"]
    cfg = cfg.reduced(dtype="float32", **({} if rehearse else PRESET_100M))
    ocfg = adamw.AdamWConfig()
    repl = NamedSharding(mesh, P())
    params = jax.device_put(api.init_fn(cfg)(jax.random.PRNGKey(seed)),
                            repl)
    opt_state = jax.device_put(adamw.init(params, ocfg), repl)
    ef = jax.device_put(jax.tree.map(
        lambda p: jnp.zeros((4,) + p.shape, jnp.float32), params), shard)
    batch = jax.device_put(
        SyntheticLM(cfg, DataConfig(8, 32 if rehearse else 128,
                                    seed=seed)).batch(0), shard)
    outs = {}
    for name, use_psum in (("soar", False), ("psum", True)):
        step = make_step(cfg, ocfg, mesh, prog, orch.grad_scale,
                         psum=use_psum)
        outs[name], first_s, compile_s = _timed(
            lambda step=step: jax.block_until_ready(
                step(params, opt_state, ef, batch)))
        outs[name + "_s"] = (first_s, compile_s)
    (p_s, _, _, m_s), (p_p, _, _, m_p) = outs["soar"], outs["psum"]
    _check(float(m_s["loss"]) == float(m_p["loss"]),
           "SOAR step loss == psum step loss")
    worst = 0.0
    for a, b in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_p)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        worst = max(worst, float(np.abs(a - b).max()))
    _report("soar_train_step", layout=f"{cfg.name}:params="
            f"{cfg.param_count()},global_batch=8,mesh=data:4",
            compile_s=f"{outs['soar_s'][1]:.3f}",
            first_call_s=f"{outs['soar_s'][0]:.3f}",
            peak_bytes_in_use=_peak(devs[0]),
            checks=f"loss_equal({float(m_s['loss'])}),"
                   f"params_allclose(max_abs_diff={worst})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip SOAR collective phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never prints a result")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import compile_stats, enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    cache = enable_compile_cache(ROOT)
    _report("device", platform=dev.platform, kind=dev.device_kind,
            count=len(devs), jax=jax.__version__, compile_cache=cache)
    if args.four_chips:
        phase_four_chips(devs, args.seed, args.rehearse)
    else:
        phase_batch(dev, args.seed, args.rehearse)
        phase_orchestrator(dev, args.rehearse)
    _report("compile_cache", **compile_stats())
    if args.rehearse:
        print("chip_smoke: rehearsal passed; no result is printed for a "
              "rehearsal", file=sys.stderr)
        return REHEARSAL_EXIT
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
