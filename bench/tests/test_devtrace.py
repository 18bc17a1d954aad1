"""The reduction from a profiler trace to busy time, idle share, device
time inside host spans, top operations and idle gaps."""
import os

import pytest

from devtrace import Busy, Trace, merge, self_times

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _synthetic():
    # device ops (ns): [0,10) [5,20) [30,40) [50,55) [90,100)
    ops = {"/device:TPU:0": [("fusion.1", 0, 10), ("fusion.2", 5, 15),
                             ("fusion.1", 30, 10), ("copy", 50, 5),
                             ("fusion.1", 90, 10)]}
    spans = [("window", 0, 100), ("filter", 0, 42), ("analyze", 42, 48),
             ("filter", 88, 12)]
    return Trace(ops=ops, spans=spans)


def test_merge_and_cover():
    m = merge([(5, 20), (0, 10), (30, 40), (40, 45)])
    assert m == [(0, 20), (30, 45)]
    b = Busy(m)
    assert b.covered(0, 100) == 35
    assert b.covered(10, 35) == 15
    assert b.covered(21, 29) == 0
    assert b.covered(32, 33) == 1


def test_busy_idle_and_spans():
    t = _synthetic()
    lo, hi = t.window()
    assert (lo, hi) == (0, 100)
    assert t.busy_s(lo, hi) == pytest.approx(45e-9)
    assert t.span_busy_s("filter") == pytest.approx(40e-9)
    assert t.span_busy_s("analyze") == pytest.approx(5e-9)


def test_top_ops_and_gaps():
    t = _synthetic()
    # fusion.2 starts inside fusion.1's [0, 10): 5 ns of it is not
    # fusion.1's own time
    assert t.top_ops(0, 100) == [["fusion.1", pytest.approx(25e-9)],
                                 ["fusion.2", pytest.approx(15e-9)],
                                 ["copy", pytest.approx(5e-9)]]
    gaps = t.idle_gaps(0, 100)
    # idle (55, 90) mid-analyze, then (20, 30) in a filter span and
    # (40, 50) in analyze: longest first, ties in time order
    assert [g[0] for g in gaps] == ["analyze", "filter", "analyze"]
    assert [g[1] for g in gaps] == pytest.approx([35e-9, 10e-9, 10e-9])


def test_self_times_of_nested_ops():
    # a loop [0, 100) holding two bodies, the second with a child of its own
    events = [("while", 0, 100), ("body", 10, 30), ("body", 50, 40),
              ("inner", 60, 10)]
    got = sorted(self_times(events))
    assert got == [("body", 10, 30), ("body", 50, 30), ("inner", 60, 10),
                   ("while", 0, 30)]


def _sweep_busy(events, lo, hi):
    """Busy ns in [lo, hi] by an event sweep, independent of merge()."""
    edges = sorted([(max(s, lo), 1) for _, s, d in events if s + d > lo
                    and s < hi] + [(min(s + d, hi), -1) for _, s, d in events
                                   if s + d > lo and s < hi])
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


@pytest.mark.parametrize("name", ["v5e_outer_trace", "v5e_edge_trace"])
def test_recorded_v5e_trace(name):
    """A slice of a trace recorded on one v5e (2,500 device ops and the
    harness's spans): of the outer cell, whose ops are long, and of a
    closed loop of small complex64 exciton solves, whose ops take a few
    microseconds each."""
    t = Trace.from_json(os.path.join(DATA, name + ".json"))
    lo, hi = t.window()
    (plane,) = t.ops
    assert plane == "/device:TPU:0"
    busy = t.busy_s(lo, hi)
    assert busy == pytest.approx(_sweep_busy(t.ops[plane], lo, hi) * 1e-9)
    assert 0 < busy <= (hi - lo) * 1e-9
    inside = sum(t.span_busy_s(n) for n in ("lanczos", "analyze", "filter"))
    assert inside <= busy * (1 + 1e-9)
    own = sum(v for _, v in t.top_ops(lo, hi, k=10 ** 6))
    assert own == pytest.approx(sum(d for _, s, d in self_times(t.ops[plane])
                                    if lo <= s < hi) * 1e-9)
    gaps = t.idle_gaps(lo, hi, k=10 ** 6)
    assert sum(g for _, g in gaps) == pytest.approx((hi - lo) * 1e-9 - busy)
