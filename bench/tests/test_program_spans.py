"""The program's spans on the device trace's clock, and the metrics read
from them: the clock mapping against a real profiler trace on the CPU,
the span tree a tiny traced run records, and each metric file on a
synthetic record whose trace holds crafted device ops."""
import collections
import glob
import os
import threading
import time

import pytest

import harness
import program_spans
from conftest import run_tiny, tiny_cell
from devtrace import Trace
from repro.core import spans as program

import jax


def _metric(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py")).read


def test_program_spans_map_onto_their_trace_events(tmp_path):
    """Under a CPU profiler session, program spans mapped through the
    harness's ``window`` span land within 100 us of their own ``fd.*``
    events in the ``.xplane.pb``."""
    hspans = harness.Spans()
    jax.profiler.start_trace(str(tmp_path))
    with hspans("window"):
        for i in range(5):
            with program.span("clock_outer"):
                time.sleep(0.01)
                with program.span("clock_inner"):
                    time.sleep(0.02)
            time.sleep(0.005)
    jax.profiler.stop_trace()
    rec = harness.Record(cell=None, kind="cpu", peaks={}, setup_s=0.0,
                         window=hspans.items[0][1:], counts={},
                         spans=hspans, program_bytes={},
                         trace=Trace.from_xplane(str(tmp_path)))
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = collections.defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("fd.clock_outer", "fd.clock_inner"):
                    events[e.name[3:]].append((e.start_ns,
                                               e.start_ns + e.duration_ns))
    at = program_spans.to_trace(rec)
    for name in ("clock_outer", "clock_inner"):
        mine = program_spans.in_window(rec, name)
        assert len(mine) == len(events[name]) == 5
        for s, (a, z) in zip(mine, sorted(events[name])):
            assert abs(at(s.start) - a) < 1e5
            assert abs(at(s.end) - z) < 1e5


@pytest.fixture
def harness_spans(monkeypatch):
    """The harness ``Spans`` each run opens, kept for the test."""
    made = []

    class Kept(harness.Spans):
        def __init__(self):
            super().__init__()
            made.append(self)

    monkeypatch.setattr(harness, "Spans", Kept)
    return made


def test_traced_run_records_the_tree_once_per_iteration(harness_spans):
    line = run_tiny(tiny_cell("spinchain22-outer"), traced=True)
    assert line["correct"], line["checks"]
    (hs,) = harness_spans
    ((_, lo, hi),) = [s for s in hs.items if s[0] == "window"]
    inside = [s for s in program.recorded() if lo <= s.start and s.end <= hi]
    kids = collections.defaultdict(list)
    for s in inside:
        kids[s.parent].append(s.name)
    analyses = [s for s in inside if s.name == "step_analyze"]
    filters = [s for s in inside if s.name == "step_filter"]
    assert len(filters) == line["attempted"] >= 1
    # a state that converges ends on an analysis with no filter after it
    assert len(analyses) >= len(filters)
    assert len(analyses) == len([s for s in hs.items if s[0] == "analyze"])
    for s in analyses:
        assert sorted(kids[s.id]) == ["choose_filter", "orthogonalize",
                                      "ritz"]
    for s in filters:
        assert kids[s.id] == ["filter"]
    metrics = line["metrics"]
    for name in ("tsqr_s.outer", "ritz_s.outer", "choose_s.outer",
                 "lanczos_s.setup", "compile_s.setup"):
        assert metrics[name]["value"] >= 0, name
    assert metrics["lanczos_s.setup"]["value"] > 0
    assert metrics["compile_s.setup"]["value"] > 0


# A synthetic record. Host clock (s): set-up (85, 100], the window
# (100, 110), two outer iterations in it. Trace clock (ns): the window
# at 5e9 for 10 s, so host t maps to 5e9 + (t - 100) * 1e9.
_S = program.Span


def _synthetic_record(monkeypatch, with_spans=True):
    spans = [
        _S(1, "lanczos", 80.0, 84.0, None, {}),        # before set-up
        _S(2, "lanczos", 90.0, 92.0, None, {}),
        _S(3, "lanczos", 93.0, 96.0, None, {}),        # the last: 3 s
        _S(4, "orthogonalize", 101.0, 101.2, 7, {}),
        _S(5, "ritz", 101.2, 101.9, 7, {}),
        _S(6, "choose_filter", 101.9, 101.95, 7, {}),
        _S(7, "step_analyze", 101.0, 101.95, None, {}),
        _S(8, "orthogonalize", 105.0, 105.3, 11, {}),
        _S(9, "ritz", 105.3, 105.95, 11, {}),
        _S(10, "choose_filter", 105.95, 106.0, 11, {}),
        _S(11, "step_analyze", 105.0, 106.0, None, {}),
        _S(12, "lanczos", 107.0, 107.5, None, {}),     # in the window
    ]

    def ns(t):
        return 5e9 + (t - 100.0) * 1e9

    def op(name, a, z):
        return (name, ns(a), ns(z) - ns(a))

    ops = {"/device:TPU:0": [
        op("tsqr", 101.0, 101.15),          # 0.15 of the first TSQR span
        op("ritz", 101.25, 101.85),         # 0.60
        op("late", 101.94, 101.945),        # 5 ms inside choose_filter
        op("tsqr", 105.05, 105.3),          # 0.25
        op("ritz", 105.3, 105.9),           # 0.60
    ]}
    trace = Trace(ops=ops, spans=[("window", ns(100.0), ns(110.0) - ns(100.0))])
    hspans = harness.Spans()
    hspans.items.append(("window", 100.0, 110.0))
    clock = object.__new__(program.CompileClock)   # no listeners
    clock.secs = clock.cache_s = 0.0
    clock.events, clock._spans = collections.deque(), {}
    clock._lock = threading.Lock()
    with monkeypatch.context() as m:
        for end, event, secs in [
                (80.0, "backend_compile_duration", 7.0),   # before set-up
                (88.0, "backend_compile_duration", 1.5),
                # a cache read inside the compile that asked for it
                (98.9, "cache_retrieval_time_sec", 2.0),
                (99.0, "backend_compile_duration", 2.5),
                (105.0, "jaxpr_trace_duration", 0.5)]:    # in the window
            m.setattr(time, "perf_counter", lambda: end)
            clock._duration(next(e for e in clock.EVENTS
                                 if e.endswith(event)), secs)
    monkeypatch.setattr(program_spans, "recorded",
                        lambda: spans if with_spans else None)
    monkeypatch.setattr(program_spans, "compile_clock",
                        lambda: clock if with_spans else None)
    return harness.Record(cell=None, kind="TPU v5 lite", peaks={},
                          setup_s=15.0, window=(100.0, 110.0), counts={},
                          spans=hspans, program_bytes={}, trace=trace)


@pytest.mark.parametrize("name, want", [
    ("tsqr_s.outer", (0.15 + 0.25) / 2),
    ("ritz_s.outer", (0.60 + 0.60) / 2),
    ("choose_s.outer", ((0.05 - 0.005) + 0.05) / 2),
    ("lanczos_s.setup", 3.0),
    ("compile_s.setup", 1.5 + 2.5),
])
def test_metric_on_a_synthetic_record(monkeypatch, name, want):
    rec = _synthetic_record(monkeypatch)
    assert _metric(name)(rec) == pytest.approx(want, abs=1e-9)


@pytest.mark.parametrize("name", ["tsqr_s.outer", "ritz_s.outer",
                                  "choose_s.outer", "lanczos_s.setup",
                                  "compile_s.setup"])
def test_metric_is_none_without_program_spans(monkeypatch, name):
    """A program without ``repro.core.spans`` (the tree before it) gives
    no number and raises nothing."""
    rec = _synthetic_record(monkeypatch, with_spans=False)
    assert _metric(name)(rec) is None
