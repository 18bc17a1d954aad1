"""Spans and counts of the solver's layers, and the compile clock.

``span(name, **counts)`` is a context manager around one layer's work on
the host: Lanczos, ``step_analyze`` (orthogonalize, Rayleigh-Ritz, the
filter choice), ``step_filter`` (redistributions and the filter). On exit
it appends a :class:`Span` — name, ``time.perf_counter()`` start and end,
the id of the span it opened inside, and its counts (the work the layer
did: degree, columns, steps, host fetches) — to a bounded in-memory ring,
oldest dropped. It also opens ``jax.profiler.TraceAnnotation("fd." +
name)``, so a profiler trace shows the same spans on the host line.

Device work is dispatched asynchronously, so a span's host clock holds
its layer's device time only if it waits for it: ``s.end_on(x)`` waits for
``x`` while a profiler trace records, and returns ``x`` untouched
otherwise. An untraced solve dispatches exactly as it would without spans.

Inside the compiled programs the same layers carry ``jax.named_scope``
names (``fd.local_qr``, ``fd.spmv``, ``fd.halo`` ...): metadata only,
which a trace shows as each device op's name path.

:data:`COMPILES` is the process's one compile clock: the seconds JAX
spends tracing, lowering, compiling or reading programs from the
persistent cache, from ``jax.monitoring``, each event stamped with the
host time it ended and each second counted once though the events nest.
It listens from the first import of this module.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time

import jax

__all__ = ["COMPILES", "CompileClock", "Span", "recorded", "span",
           "tracing"]

#: spans and compile events kept; older ones are dropped
RING = 4096


@dataclasses.dataclass(frozen=True)
class Span:
    """One closed span on the host clock (``time.perf_counter()`` s)."""
    id: int
    name: str
    start: float
    end: float
    parent: int | None       # id of the span it opened inside
    counts: dict


_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count()
_local = threading.local()


def _stack() -> list:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


def tracing() -> bool:
    """Whether a profiler trace is recording."""
    return jax.profiler.TraceAnnotation.is_enabled()


def recorded() -> list[Span]:
    """The spans in the ring, in the order they closed (children first)."""
    return list(_ring)


class span:
    """``with span("ritz", columns=16) as s: ...``; ``s.counts`` may be
    updated inside, and ``s.end_on(x)`` ends the layer on a device result
    while a trace records."""

    def __init__(self, name: str, **counts):
        self.name, self.counts = name, counts

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(_ids)
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation("fd." + self.name)
        # the host clock's stamps sit next to the annotation's own
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def end_on(self, x):
        """``x``; waited for while a trace records, so that the span holds
        its layer's device time."""
        if tracing():
            jax.block_until_ready(x)
        return x

    def __exit__(self, *exc):
        end = time.perf_counter()
        suppress = self._annotation.__exit__(*exc)
        _stack().pop()
        _ring.append(Span(self.id, self.name, self.start, end, self.parent,
                          dict(self.counts)))
        return suppress


class CompileClock:
    """Seconds JAX spends tracing, lowering, compiling or reading compiled
    programs from the persistent cache, read from ``jax.monitoring``.

    The events nest: a persistent-cache read is timed inside the backend
    compile that asked for it, and a ``jit`` traced inside another's trace
    (or an eager op compiled there) inside the outer trace. Each event
    arrives as it ends, with its seconds, so it covers ``[end - secs,
    end]`` on the host clock; it counts only the part of that interval
    that no earlier event of its thread covered, which an enclosing event
    arrives after. ``secs`` and :meth:`between` add these parts, so no
    second is counted twice. ``events`` keeps the newest (host time at
    its end, event, seconds counted, thread); ``cache_s`` adds up the
    cache reads' own seconds, which the compiles around them hold."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    CACHE_READ = EVENTS[3]

    def __init__(self):
        self.secs = 0.0
        self.cache_s = 0.0
        self.hits = 0
        self.misses = 0
        self.events: collections.deque = collections.deque(maxlen=RING)
        self._spans: dict = {}   # thread -> deque of (start, end) events
        self._lock = threading.Lock()   # compiles may run on several threads
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event not in self.EVENTS:
            return
        end = time.perf_counter()
        thread = threading.get_ident()
        with self._lock:
            done = self._spans.setdefault(thread,
                                          collections.deque(maxlen=RING))
            own = secs - _covered(done, end - secs, end)
            done.append((end - secs, end))
            self.secs += own
            if event == self.CACHE_READ:
                self.cache_s += secs
            self.events.append((end, event, own, thread))

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def between(self, lo: float, hi: float) -> float:
        """Compile seconds of the events stamped in [lo, hi], each counted
        once."""
        with self._lock:
            return sum(s for t, _, s, _ in self.events if lo <= t <= hi)


def _covered(done, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the intervals ``done`` cover, in order of
    their ends."""
    parts = []
    for a, b in reversed(done):
        if b <= lo:
            break   # the ends only grow: nothing earlier reaches lo
        parts.append((max(a, lo), min(b, hi)))
    got, reach = 0.0, lo
    for a, b in sorted(parts):
        if b > reach:
            got += b - max(a, reach)
            reach = b
    return got


COMPILES = CompileClock()
