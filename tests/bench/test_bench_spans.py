"""The program's spans in a trace, and the readers of the metrics that read
them and the program's counters: on a trace taken here on the CPU, and on
one recorded on a v5e (``trace_spans_chip.json``, written by
``record_trace_spans.py``)."""
from __future__ import annotations

import json
import math
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import bench_testlib as L  # noqa: F401  (puts the repository on the path)
from bench import spans, trace as tr
from bench.registry import Registry

HERE = Path(__file__).resolve().parent
TOP = {"engine.solve": ["engine.pack", "engine.upload", "engine.readback"],
       "orchestrator.admit": ["engine.prepare", "engine.pack",
                              "engine.upload", "engine.loop",
                              "engine.remeasure", "schedule.build_programs",
                              "orchestrator.register"]}
SPAN_METRICS = {"pack_ms": "engine.pack", "upload_ms": "engine.upload",
                "readback_ms": "engine.readback",
                "pack_ms.admit": "engine.pack",
                "upload_ms.admit": "engine.upload",
                "remeasure_ms": "engine.remeasure",
                "program_build_ms": "schedule.build_programs",
                "register_ms": "orchestrator.register"}
COUNTER_METRICS = ("h2d_mb_per_call", "penalty_rounds_per_wave")


@pytest.fixture(scope="module")
def cpu_trace():
    """Two small solves and two admission waves traced here, as the
    recorder does on the chip."""
    import record_trace_spans as rec
    run = rec._calls()
    with tr.capture() as got:
        run()
    return got[0]


@pytest.fixture(scope="module")
def chip_trace():
    return json.loads((HERE / "trace_spans_chip.json").read_text())


def _inside(inner: np.ndarray, outer: np.ndarray) -> bool:
    return all(((outer[:, 0] <= a) & (b <= outer[:, 1])).any()
               for a, b in inner)


@pytest.mark.parametrize("which", ["cpu_trace", "chip_trace"])
def test_every_span_nests_under_its_top_span(request, which):
    t = request.getfixturevalue(which)
    for top, inner in TOP.items():
        outer = tr.host_spans(t, top)
        assert len(outer) == 2, top
        for name in inner:
            iv = tr.host_spans(t, name)
            assert len(iv) >= 2, name
            assert _inside(iv, tr.host_spans(t, tr.CALL)), name
    solve, admit = (tr.host_spans(t, n) for n in TOP)
    for name in ("engine.readback",):
        assert _inside(tr.host_spans(t, name), solve)
    for name in ("engine.prepare", "engine.loop", "engine.remeasure",
                 "schedule.build_programs", "orchestrator.register"):
        assert _inside(tr.host_spans(t, name), admit)
    # packing and upload of either kind of call lie under one of the two
    for name in ("engine.pack", "engine.upload"):
        assert _inside(tr.host_spans(t, name), np.r_[solve, admit])
    # release runs between waves, outside the timed call
    rel = tr.host_spans(t, "orchestrator.release")
    assert len(rel) == 2
    assert not any(_inside(iv[None], tr.host_spans(t, tr.CALL))
                   for iv in rel)


@pytest.mark.parametrize("which", ["cpu_trace", "chip_trace"])
def test_every_new_reader_reads_a_finite_number(request, which):
    ctx = SimpleNamespace(trace=request.getfixturevalue(which))
    reg = Registry.load()
    # the counter readers read this process's counters, which the CPU
    # trace's calls have moved
    names = [*SPAN_METRICS] + (
        [*COUNTER_METRICS] if which == "cpu_trace" else [])
    for name in names:
        v = reg.reader(name)(ctx)
        assert v is not None and math.isfinite(v) and v > 0, name


def _naive_ms(t: dict, name: str) -> float:
    """The span's host milliseconds per call, the slow way: walk the
    window in the sorted edges of the spans and the device's operations."""
    lo, hi = tr.window(t)
    sp = np.clip(tr.host_spans(t, name), lo, hi)
    dev = tr.devices(t)
    ops = np.asarray([(s, s + d) for n, s, d in next(
        ln for ln in dev[0]["lines"] if ln["name"] == tr.OPS)["events"]]
        if dev else [], np.float64).reshape(-1, 2)
    ops = ops[(ops[:, 1] > sp[:, 0].min()) & (ops[:, 0] < sp[:, 1].max())]
    edges = np.unique(np.r_[lo, hi, sp.ravel(), np.clip(ops, lo, hi).ravel()])
    mid = 0.5 * (edges[:-1] + edges[1:])
    in_span = ((sp[:, :1] <= mid) & (mid < sp[:, 1:])).any(axis=0)
    in_op = ((ops[:, :1] <= mid) & (mid < ops[:, 1:])).any(axis=0)
    return float(np.diff(edges)[in_span & ~in_op].sum()) / tr.calls(t) / 1e6


def test_span_readers_match_the_chip_trace_by_hand(chip_trace):
    ctx = SimpleNamespace(trace=chip_trace)
    reg = Registry.load()
    for metric, name in SPAN_METRICS.items():
        assert reg.reader(metric)(ctx) == pytest.approx(
            _naive_ms(chip_trace, name), rel=1e-9), metric
    # the penalty loop ran on the device inside ``engine.loop``: that busy
    # time is what the readers leave out
    lo, hi = tr.window(chip_trace)
    loop = tr.union(tr.host_spans(chip_trace, "engine.loop"))
    bz = tr.busy(tr.devices(chip_trace)[0], lo, hi)
    assert spans.overlap_s(loop, bz) > 0


def test_overlap_by_hand():
    iv = np.array([[0.0, 10.0], [20.0, 30.0], [40.0, 50.0]])
    bz = np.array([[5.0, 22.0], [25.0, 26.0], [45.0, 60.0]])
    assert spans.overlap_s(iv, bz) == pytest.approx((5 + 2 + 1 + 5) / 1e9)
    assert spans.overlap_s(iv, np.zeros((0, 2))) == 0.0


def test_readers_read_nothing_where_the_program_has_no_spans():
    """A trace with only the benchmark's own spans, as from a program
    that emits none."""
    t = {"planes": [{"name": "/host:CPU", "lines": [{"name": "python3",
         "events": [[tr.WINDOW, 0.0, 100.0], [tr.CALL, 1.0, 50.0]]}]}]}
    ctx = SimpleNamespace(trace=t)
    reg = Registry.load()
    for metric in SPAN_METRICS:
        assert reg.reader(metric)(ctx) is None, metric
