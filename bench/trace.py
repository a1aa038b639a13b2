"""Profiler trace of a run's window, and its reduction to numbers.

``capture`` records the window with JAX's profiler (Python tracer off).
``load`` reads the ``.xplane.pb`` it writes into a plain dict, the form
that the reduction below and the recorded trace of the tests share:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

All planes are on one clock. Device planes are named ``/device:<KIND>:<i>``
and their ``XLA Ops`` line holds one event per operation that ran, named
by its HLO instruction (``%fusion.12``; a custom call adds its target, so
a Pallas kernel reads ``%<jit name>.<n> tpu_custom_call``); the ``XLA
Modules`` line holds one per program execution, named after the jitted
function (``jit_<fn>(<id>)``). The host plane ``/host:CPU`` holds the
runtime's spans of at least ``HOST_MIN_NS`` and the benchmark's own:
``bench_window`` around the measured window and ``bench_call`` around each
timed call.
"""
from __future__ import annotations

import bisect
import contextlib
import functools
import glob
import os
import re
import tempfile

import numpy as np

OPS, MODULES = "XLA Ops", "XLA Modules"
WINDOW, CALL = "bench_window", "bench_call"
KERNEL = "tpu_custom_call"
HOST_MIN_NS = 10_000
TRACE_SECONDS = 10.0
_DEVICE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@functools.lru_cache(maxsize=1 << 16)
def op_label(hlo: str) -> str:
    """``%name.N`` of an op's HLO text, plus the target of a custom call."""
    head = hlo.split(" = ", 1)[0]
    m = _TARGET.search(hlo)
    return f"{head} {m.group(1)}" if m else head


@contextlib.contextmanager
def capture():
    """Trace what runs inside; yields a list that receives the loaded trace
    dict on exit. The raw files go to a temporary directory that is removed
    afterwards."""
    import jax
    out: list = []
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        out.append(load(files[0]))


def load(path: str) -> dict:
    """Device planes (their op and module lines) and the host plane's
    events that have a duration, as a plain dict."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = _DEVICE.match(plane.name) is not None
        if not device and plane.name != "/host:CPU":
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS, MODULES):
                continue
            if device:
                ev = [[op_label(e.name) if line.name == OPS else e.name,
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events if e.duration_ns > 0]
            else:
                ev = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if e.duration_ns >= HOST_MIN_NS
                      or e.name in (WINDOW, CALL)]
            if ev:
                lines.append({"name": line.name, "events": ev})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- reduction ----------------------------------------------------------------

def _events(plane: dict, line: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == line:
            return ln["events"]
    return []


def devices(trace: dict) -> list[dict]:
    """Device planes that ran anything, ordered by device index."""
    ps = [p for p in trace["planes"] if _DEVICE.match(p["name"])
          and _events(p, OPS)]
    return sorted(ps, key=lambda p: int(_DEVICE.match(p["name"]).group(1)))


def host_spans(trace: dict, name: str) -> np.ndarray:
    """(m, 2) start/end ns of the host events called ``name``, in order."""
    out = [(s, s + d) for p in trace["planes"] if p["name"] == "/host:CPU"
           for ln in p["lines"] for n, s, d in ln["events"] if n == name]
    return np.asarray(sorted(out), np.float64).reshape(-1, 2)


def calls(trace: dict) -> int:
    """``bench_call`` spans that start inside the traced window."""
    lo, hi = window(trace)
    c = host_spans(trace, CALL)
    return int(((c[:, 0] >= lo) & (c[:, 0] < hi)).sum())


def window(trace: dict) -> tuple[float, float]:
    w = host_spans(trace, WINDOW)
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(w)}")
    return float(w[0, 0]), float(w[0, 1])


def union(iv: np.ndarray) -> np.ndarray:
    """Merge (m, 2) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[last]], axis=1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1) \
        if len(iv) else np.zeros((0, 2))
    return iv[iv[:, 1] > iv[:, 0]]


def busy(plane: dict, lo: float, hi: float) -> np.ndarray:
    """Disjoint intervals in [lo, hi] in which an operation ran."""
    ev = np.asarray([(s, s + d) for _, s, d in _events(plane, OPS)],
                    np.float64).reshape(-1, 2)
    return clip(union(ev), lo, hi)


def busy_s(trace: dict) -> float:
    """Seconds of the window in which an operation ran, averaged over the
    devices that ran anything."""
    lo, hi = window(trace)
    devs = devices(trace)
    if not devs:
        return 0.0
    tot = [float(np.diff(busy(p, lo, hi), axis=1).sum()) for p in devs]
    return sum(tot) / len(tot) / 1e9


def window_s(trace: dict) -> float:
    lo, hi = window(trace)
    return (hi - lo) / 1e9


def idle_pct(trace: dict) -> float | None:
    """100 * (1 - busy / window); None where no device ran anything."""
    if not devices(trace):
        return None
    return 100.0 * (1.0 - busy_s(trace) / window_s(trace))


def op_seconds(trace: dict, match, line: str = OPS,
               device: int = 0) -> float:
    """Seconds of the window spent in the events of ``line`` on the
    ``device``-th device plane whose name satisfies ``match``; overlapping
    events are counted once."""
    lo, hi = window(trace)
    devs = devices(trace)
    if len(devs) <= device:
        return 0.0
    ev = np.asarray([(s, s + d) for n, s, d in _events(devs[device], line)
                     if match(n)], np.float64).reshape(-1, 2)
    return float(np.diff(clip(union(ev), lo, hi), axis=1).sum()) / 1e9


def host_self_s(trace: dict) -> np.ndarray:
    """Per ``bench_call`` span: its seconds less the part in which the
    first device ran anything."""
    lo, hi = window(trace)
    calls = clip(host_spans(trace, CALL), lo, hi)
    devs = devices(trace)
    bz = busy(devs[0], lo, hi) if devs else np.zeros((0, 2))
    out = np.empty(len(calls))
    for i, (a, b) in enumerate(calls):
        out[i] = (b - a) - np.diff(clip(bz, a, b), axis=1).sum()
    return out / 1e9


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time on the first device, and
    the longest idle gaps of that device, each named by the innermost
    host event that covers the gap's middle."""
    lo, hi = window(trace)
    devs = devices(trace)
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    mods = sorted((s, s + d, re.sub(r"\(\d+\)$", "", n))
                  for n, s, d in _events(devs[0], MODULES))
    starts = [m[0] for m in mods]
    tot: dict[str, float] = {}
    for n, s, d in _events(devs[0], OPS):
        if s >= lo and s + d <= hi:
            j = bisect.bisect_right(starts, s) - 1
            mod = mods[j][2] if j >= 0 and s < mods[j][1] else "?"
            key = f"{mod}:{op_kind(n)}"
            tot[key] = tot.get(key, 0.0) + d / 1e9
    ops = sorted(tot.items(), key=lambda x: -x[1])[:top]
    bz = busy(devs[0], lo, hi)
    edges = np.r_[lo, bz.ravel(), hi].reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")][:top]
    host = [(n, s, s + d) for p in trace["planes"] if p["name"] == "/host:CPU"
            for ln in p["lines"] for n, s, d in ln["events"]
            if n != WINDOW]
    named = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [(e - s, n) for n, s, e in host if s <= mid <= e]
        what = min(cover)[1] if cover else "outside any host span"
        named.append([what, (b - a) / 1e9])
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": named}


def op_kind(label: str) -> str:
    """``fusion`` for ``%fusion.12``; the custom call's target where there
    is one."""
    parts = label.split(" ")
    if len(parts) > 1:
        return parts[-1]
    return re.sub(r"(\.\d+)+$", "", parts[0].lstrip("%"))
