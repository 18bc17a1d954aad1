"""Reduction of a profiler trace to the benchmark's device numbers.

A traced run writes JAX's profiler trace (an ``.xplane.pb``). This module
keeps two things from it, both on the trace's one clock, in nanoseconds:

* ``ops``: per device plane (``/device:TPU:0`` ...), every event of its
  ``XLA Ops`` line as (name, start, duration): the operations that ran
  on the device;
* ``spans``: every host event whose name starts with ``bench.``: the
  spans the harness opens around its calls into the solver.

Everything else is arithmetic on those lists: the union of a device's
busy intervals, the busy time inside a window or inside the host spans of
one name, the operations that took most time, and the longest idle gaps
named by the span the host was in. ``tests/test_devtrace.py`` checks it on a
small trace recorded on a v5e.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import itertools
import json
import os

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    ops: dict        # device plane -> [(name, start_ns, dur_ns), ...]
    spans: list      # [(name, start_ns, dur_ns), ...], prefix dropped

    @classmethod
    def from_xplane(cls, log_dir: str) -> "Trace":
        import jax

        paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        data = jax.profiler.ProfileData.from_file(paths[-1])
        ops, spans = {}, []
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        # an op's name is its HLO text: keep "%fusion.12"
                        ops[plane.name] = [
                            (e.name.split(" = ", 1)[0], e.start_ns,
                             e.duration_ns) for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.name[len(SPAN_PREFIX):], e.start_ns,
                               e.duration_ns) for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
        return cls(ops=ops, spans=sorted(spans, key=lambda s: s[1]))

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        """A trace kept as {"ops": {plane: [[name, start, dur], ...]},
        "spans": [[name, start, dur], ...]} (the tests' recorded slices)."""
        with open(path) as f:
            d = json.load(f)
        return cls(ops={k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                   spans=[tuple(s) for s in d["spans"]])

    def window(self) -> tuple[float, float]:
        """(start, end) of the harness's one ``window`` span."""
        (s,) = [s for s in self.spans if s[0] == "window"]
        return s[1], s[1] + s[2]

    def ops_end(self) -> float:
        """When the last recorded device op ended."""
        return max((b.ends[-1] for b in self._busy.values() if b.ends),
                   default=float("-inf"))

    @functools.cached_property
    def _busy(self) -> dict:
        return {p: Busy(merge((s, s + d) for _, s, d in ev))
                for p, ev in self.ops.items()}

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds in [lo, hi] in which an operation ran, averaged over the
        device planes."""
        if not self.ops:
            return 0.0
        return sum(b.covered(lo, hi)
                   for b in self._busy.values()) / len(self.ops) * 1e-9

    def span_busy_s(self, name: str) -> float:
        """Device-busy seconds inside the host spans called ``name``,
        averaged over the device planes."""
        if not self.ops:
            return 0.0
        total = sum(b.covered(s, s + d) for b in self._busy.values()
                    for n, s, d in self.spans if n == name)
        return total / len(self.ops) * 1e-9

    def top_ops(self, lo: float, hi: float, k: int = 10) -> list:
        """The k operation names with the most device self-seconds among
        the operations that start in [lo, hi]: a loop's time less that
        of the operations nested in it."""
        acc = collections.Counter()
        for events in self.ops.values():
            for name, s, d in self_times(events):
                if lo <= s < hi:
                    acc[name] += d * 1e-9
        return [[n, v] for n, v in acc.most_common(k) if v > 0]

    def idle_gaps(self, lo: float, hi: float, k: int = 10) -> list:
        """The k longest gaps in [lo, hi] in which the first device ran
        nothing, each named by the innermost host span around its middle
        ("host" where the harness had none open)."""
        if not self.ops:
            return []
        first = self._busy[sorted(self.ops)[0]]
        b = [(max(a, lo), min(z, hi)) for a, z in
             zip(first.starts, first.ends) if z > lo and a < hi]
        edges = [lo] + [x for iv in b for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._span_at(0.5 * (a + z)), (z - a) * 1e-9]
                for a, z in gaps[:k]]

    def _span_at(self, t: float) -> str:
        inner = [(d, n) for n, s, d in self.spans
                 if s <= t <= s + d and n != "window"]
        return min(inner)[1] if inner else "host"


def self_times(events) -> list:
    """(name, start, self duration) of each event of one line: its duration
    less the part its nested events cover (a ``while`` op holds its body's
    ops on the same line)."""
    out, stack = [], []      # stack: [name, start, end, covered by children]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][2]:
            n0, s0, e0, c0 = stack.pop()
            out.append((n0, s0, e0 - s0 - c0))
        if stack:
            stack[-1][3] += min(s + d, stack[-1][2]) - s
        stack.append([name, s, s + d, 0.0])
    out += [(n0, s0, e0 - s0 - c0) for n0, s0, e0, c0 in reversed(stack)]
    return out


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for a, z in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], z)
        else:
            out.append([a, z])
    return [tuple(iv) for iv in out]


class Busy:
    """Sorted disjoint intervals, with the length of [lo, hi] they cover
    found by bisection."""

    def __init__(self, merged: list):
        self.starts = [a for a, _ in merged]
        self.ends = [z for _, z in merged]
        self.cum = [0.0, *itertools.accumulate(z - a for a, z in merged)]

    def covered(self, lo: float, hi: float) -> float:
        i = bisect.bisect_right(self.ends, lo)
        j = bisect.bisect_left(self.starts, hi)
        if i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, lo - self.starts[i])
                - max(0.0, self.ends[j - 1] - hi))
