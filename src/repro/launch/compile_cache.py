"""JAX's persistent compilation cache, placed from outside, plus counters.

Call :func:`enable_compile_cache` at a program's start-up (never at
import), before the first compile:

  * where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    the cache goes there — this module sets no other directory;
  * otherwise the cache goes to the fixed ``<root>/.jax_cache`` (the
    path is part of the cache's key, so it never depends on a temporary
    name, a process id or the time).

Every program is cached, however quick its compile. The listeners count
cache hits and misses and add up compile seconds (lowering to MLIR plus
the backend compile or cache read; tracing is left out, since nested
jits trace inside their caller's span) into the ``compile.hits``,
``compile.misses`` and ``compile.seconds`` counters of
:mod:`repro.telemetry`, which :func:`compile_stats` reports.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from .. import telemetry

_EVENTS = {"/jax/compilation_cache/cache_hits": "compile.hits",
           "/jax/compilation_cache/cache_misses": "compile.misses"}
_DURATIONS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
_installed = False


def _on_event(event: str, **_) -> None:
    if event in _EVENTS:
        telemetry.count(_EVENTS[event])


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _DURATIONS:
        telemetry.count("compile.seconds", duration)


def enable_compile_cache(root: str | os.PathLike) -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    global _installed
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _installed:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
    return path


def compile_stats() -> dict:
    """Cache hits and misses and compile seconds since start-up."""
    return {"hits": int(telemetry.get("compile.hits")),
            "misses": int(telemetry.get("compile.misses")),
            "compile_s": float(telemetry.get("compile.seconds"))}
