"""Typed engine options — the planner API's single options surface.

PR 1–3 threaded a ``**engine_kw`` kwargs-soup through three layers
(``plan_batch`` → ``solve_batch`` → ``solve_forest``): a misspelled option
surfaced as a ``TypeError`` deep inside the engine (or, worse, was
silently swallowed by an intermediate ``**kw``). :class:`EngineOptions`
replaces that with one frozen dataclass validated at the call boundary:

    solve_batch(trees, loads, k, options=EngineOptions(cap=False))
    plan_batch(topos, k, options=EngineOptions(dtype=jnp.float64))

Unknown or misspelled fields fail immediately in the ``EngineOptions``
constructor (with a did-you-mean hint via :func:`resolve_options`), and a
frozen instance hashes/compares by value, so it can key jit caches
directly. The old kwargs spelling had a one-release deprecation window
(PR 4) and is now **removed**: :func:`resolve_options` raises a
``TypeError`` naming the migration. CI keeps the
``-W error::DeprecationWarning`` job as the guard that no new deprecated
spellings creep into the planner surface.
"""
from __future__ import annotations

import dataclasses
import difflib
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Options consumed by ``solve_forest`` / ``solve_batch`` and everything
    layered on top (``solve_congestion``, ``plan`` / ``plan_batch``).

    dtype:        DP table dtype (float32 default; pass ``jnp.float64``
                  under ``jax_enable_x64`` for exactness on arbitrary rates)
    use_pallas:   None = auto (Pallas level-fold kernel on TPU, fused jnp
                  elsewhere — :func:`pallas_fold`); True/False forces one
    interpret:    run the Pallas kernel body in Python (CPU validation)
    cap:          min(k, subtree) per-level budget-width truncation
    color:        False = costs-only mode (no traceback, no masks)
    debug_tables: full-table pullback + host-numpy color (PR 1 path)
    """

    dtype: Any = jnp.float32
    use_pallas: bool | None = None
    interpret: bool = False
    cap: bool = True
    color: bool = True
    debug_tables: bool = False

    def replace(self, **changes) -> "EngineOptions":
        """A copy with ``changes`` applied (validated like the ctor)."""
        return dataclasses.replace(self, **changes)


def pallas_fold(opts: EngineOptions) -> bool:
    """Which level fold a solve runs: the Pallas kernel on TPU, the fused
    jnp fold on every other backend, unless ``use_pallas`` forces one."""
    if opts.use_pallas is None:
        return jax.default_backend() == "tpu"
    return bool(opts.use_pallas)


_FIELDS = tuple(f.name for f in dataclasses.fields(EngineOptions))

_REMOVED = (
    "engine options are no longer accepted as keyword arguments "
    "({names}) — the PR-4 deprecation window has closed; pass "
    "options=EngineOptions({example}) instead"
)


def resolve_options(options: EngineOptions | None,
                    engine_kw: dict,
                    where: str) -> EngineOptions:
    """Validate the ``options=`` spelling at the call boundary.

    * ``options`` alone → returned as-is (defaults when None);
    * any stray keyword argument → ``TypeError`` *here*, at the call
      boundary: a misspelled option gets a did-you-mean hint, a known
      field name gets the ``options=EngineOptions(...)`` migration (the
      PR-4 kwargs shim is gone);
    * both at once → ``TypeError`` (ambiguous precedence is never guessed).
    """
    if not engine_kw:
        if options is None:
            return EngineOptions()
        if not isinstance(options, EngineOptions):
            raise TypeError(f"{where}: options must be an EngineOptions, "
                            f"got {type(options).__name__}")
        return options
    if options is not None:
        raise TypeError(
            f"{where}: got both options= and legacy engine keyword "
            f"arguments {sorted(engine_kw)} — pass everything through "
            "options=EngineOptions(...)")
    unknown = [k for k in engine_kw if k not in _FIELDS]
    if unknown:
        hints = []
        for k in unknown:
            close = difflib.get_close_matches(k, _FIELDS, n=1)
            hints.append(f"{k!r}" + (f" (did you mean {close[0]!r}?)"
                                     if close else ""))
        raise TypeError(
            f"{where}: unknown engine option(s) {', '.join(hints)}; "
            f"valid options: {', '.join(_FIELDS)}")
    raise TypeError(f"{where}: " + _REMOVED.format(
        names=", ".join(sorted(engine_kw)),
        example=", ".join(f"{k}=..." for k in sorted(engine_kw))))
