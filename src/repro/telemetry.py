"""Spans and counters of the placement path: one home for both.

**Spans** mark the stages of a solve or an admission wave on the host. Each
is a ``jax.profiler.TraceAnnotation``, so it lands on the profiler's host
plane, on the same clock as the device planes, and records only while a
profiler session is active (``jax.profiler.trace``); otherwise it costs
about a microsecond. A span marks a stage, never a single tenant, and adds
no sync. The top span of a call carries the call's sequence number (the
counter it bumps) as its ``call`` argument; the spans inside it nest on the
calling thread.

**Counters** are process-wide sums, always on: :func:`count` adds,
:func:`counters` takes a snapshot, :func:`reset` clears (for tests).
``repro.core.forest.layout_stats``, ``repro.engine.cache_stats`` and
``repro.launch.compile_cache.compile_stats`` are views over them.

The names, and what reads each, are listed in the README's operator
section.
"""
from __future__ import annotations

import functools

import jax

_COUNTS: dict[str, float] = {}
_SEEN: dict[str, set] = {}


def span(name: str, **args):
    """A host span ``name`` around a ``with`` block."""
    return jax.profiler.TraceAnnotation(name, **args)


def traced(name: str):
    """Decorator: run the function inside span ``name``. For functions that
    trace no jitted program: a wrapper frame on the stack while JAX traces
    makes the lowering slower, so top spans are ``with`` blocks."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kw):
            with span(name):
                return fn(*args, **kw)
        return inner
    return wrap


def count(name: str, n: float = 1) -> float:
    """Add ``n`` to counter ``name``; returns its new value."""
    v = _COUNTS.get(name, 0) + n
    _COUNTS[name] = v
    return v


def count_distinct(name: str, key) -> None:
    """Add 1 to counter ``name`` the first time ``key`` is seen."""
    seen = _SEEN.setdefault(name, set())
    if key not in seen:
        seen.add(key)
        count(name)


def get(name: str) -> float:
    return _COUNTS.get(name, 0)


def counters() -> dict:
    """A snapshot of every counter."""
    return dict(_COUNTS)


def reset() -> None:
    _COUNTS.clear()
    _SEEN.clear()
