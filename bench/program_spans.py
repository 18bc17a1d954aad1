"""The program's own spans (``repro.core.spans``) on the device trace's
clock.

The solver records each layer's span on the host clock
(``time.perf_counter()``); the profiler's trace keeps the device's
operations on its own clock, in nanoseconds. The harness's ``window`` span
exists on both: its host-clock stamps are in ``rec.spans`` and its event
is in ``rec.trace``. The two clocks run at one rate, so the difference of
the window's two starts maps every program span into the trace, where
the device-busy and device-idle seconds inside it are read
(``Trace.busy_s`` over the mapped interval). The window's ends are not
used: a span's end is stamped inside the annotation's exit, some
microseconds after the host clock's, which over a short window would
skew a fitted rate.

A program without ``repro.core.spans`` records nothing: every function here
then returns None, and so does every metric built on it.
"""
from __future__ import annotations


def recorded():
    """The program's spans in this process, or None where it has none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.recorded()


def compile_clock():
    """The program's compile clock, or None where it has none."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans.COMPILES


def host_window(rec) -> tuple[float, float]:
    """(start, end) of the harness's ``window`` span on the host clock."""
    ((_, lo, hi),) = [s for s in rec.spans.items if s[0] == "window"]
    return lo, hi


def to_trace(rec):
    """Host-clock seconds -> trace nanoseconds, through the window span."""
    h0, _ = host_window(rec)
    t0, _ = rec.trace.window()
    return lambda t: t0 + (t - h0) * 1e9


def in_window(rec, name: str) -> list:
    """The program spans called ``name`` that lie inside the window."""
    spans = recorded()
    if spans is None:
        return []
    lo, hi = host_window(rec)
    return [s for s in spans if s.name == name and lo <= s.start
            and s.end <= hi]


def device_s(rec, name: str) -> tuple[float, float, int] | None:
    """(device-busy s, device-idle s, count) summed over the spans called
    ``name`` inside the traced window; None without a trace or spans."""
    spans = in_window(rec, name)
    if rec.trace is None or not spans:
        return None
    at = to_trace(rec)
    busy = idle = 0.0
    for s in spans:
        b = rec.trace.busy_s(at(s.start), at(s.end))
        busy += b
        idle += (at(s.end) - at(s.start)) * 1e-9 - b
    return busy, idle, len(spans)


def per_iteration(rec, name: str, which: int) -> float | None:
    """Busy (``which`` 0) or idle (1) seconds inside the spans ``name``,
    per ``step_analyze`` span of the window (one an outer iteration)."""
    got = device_s(rec, name)
    n = len(in_window(rec, "step_analyze"))
    if got is None or not n:
        return None
    return got[which] / n


def setup_interval(rec) -> tuple[float, float]:
    """Set-up on the host clock: the ``setup_s`` seconds before the
    window."""
    lo, _ = host_window(rec)
    return lo - rec.setup_s, lo


def last_in_setup_s(rec, name: str) -> float | None:
    """Host-clock seconds of the last span ``name`` that lies in set-up."""
    spans = recorded()
    if spans is None:
        return None
    lo, hi = setup_interval(rec)
    done = [s for s in spans if s.name == name and lo <= s.start
            and s.end <= hi]
    return done[-1].end - done[-1].start if done else None


def setup_compile_s(rec) -> float | None:
    """Compile-clock seconds of the events stamped in set-up."""
    clock = compile_clock()
    if clock is None:
        return None
    return clock.between(*setup_interval(rec))
