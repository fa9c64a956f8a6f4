"""Host spans and counters of the program, in one in-process registry.

``span(name)`` times a block of host work: it writes a
``jax.profiler.TraceAnnotation`` of that name (so, under the profiler, the
span lands in the trace's host plane on the device trace's clock) and adds
its wall time to per-name totals in memory.  ``count(name, n)`` adds to a
monotonic per-name counter.  ``totals()`` is one JSON-able snapshot of both,
``since(snapshot)`` the difference from an earlier one.

A span's *self* time is its wall time minus the time of the spans opened
inside it on the same thread (its children).  Spans nest per thread; the
totals are shared by every thread of the process.

``repro.core.telemetry`` re-exports everything here; this module imports
nothing from ``repro.core``, so the engine can use it.

>>> reg = Registry()
>>> with reg.span("outer"):
...     with reg.span("inner"):
...         reg.count("items", 3)
>>> t = reg.totals()
>>> t["spans"]["outer"]["calls"], t["counters"]["items"]
(1, 3)
>>> t["spans"]["outer"]["self_s"] <= t["spans"]["outer"]["total_s"]
True
"""
from __future__ import annotations

import collections
import threading
import time

import jax

SCHEMA = "repro.telemetry/v1"


def snapshot_row(kind: str, **payload) -> dict:
    """One telemetry row: the shared envelope every subsystem emits."""
    return {"schema": SCHEMA, "kind": kind, **payload}


class _Span:
    """One open span; ``wall_s`` holds its duration once it has closed."""
    __slots__ = ("_reg", "name", "wall_s", "_child_s", "_t0", "_ann")

    def __init__(self, reg: "Registry", name: str):
        self._reg, self.name = reg, name
        self.wall_s = self._child_s = 0.0

    def __enter__(self) -> "_Span":
        self._reg._stack().append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.wall_s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        stack = self._reg._stack()
        stack.pop()
        if stack:
            stack[-1]._child_s += self.wall_s
        self._reg._add_span(self.name, self.wall_s,
                            self.wall_s - self._child_s)


class Registry:
    """Span totals, counters and a bounded list of recent rows."""

    def __init__(self, keep: int = 128):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans: dict[str, list] = {}        # name -> [calls, total, self]
        self._counters: dict[str, float] = {}
        self._rows = collections.deque(maxlen=keep)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _add_span(self, name: str, wall_s: float, self_s: float) -> None:
        with self._lock:
            s = self._spans.get(name)
            if s is None:
                s = self._spans[name] = [0, 0.0, 0.0]
            s[0] += 1
            s[1] += wall_s
            s[2] += self_s

    def span(self, name: str) -> _Span:
        """Context manager timing one block of host work as ``name``."""
        return _Span(self, name)

    def count(self, name: str, n: float = 1) -> None:
        """Add ``n`` to the counter ``name``."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def totals(self) -> dict:
        """Every span's ``calls``/``total_s``/``self_s`` and every counter,
        as one ``telemetry.totals`` row."""
        with self._lock:
            spans = {k: {"calls": c, "total_s": t, "self_s": s}
                     for k, (c, t, s) in self._spans.items()}
            counters = dict(self._counters)
        return snapshot_row("telemetry.totals", spans=spans,
                            counters=counters)

    def since(self, before: dict) -> dict:
        """What was added after the ``totals()`` snapshot ``before``: the
        spans called and the counters moved since, in the same shape.  Work
        on other threads in that time is included."""
        now = self.totals()
        old_spans, old_counters = before["spans"], before["counters"]
        zero = {"calls": 0, "total_s": 0.0, "self_s": 0.0}
        spans = {}
        for k, v in now["spans"].items():
            o = old_spans.get(k, zero)
            if v["calls"] != o["calls"]:
                spans[k] = {f: v[f] - o[f] for f in zero}
        counters = {k: v - old_counters.get(k, 0)
                    for k, v in now["counters"].items()
                    if v != old_counters.get(k, 0)}
        return snapshot_row("telemetry.totals", spans=spans,
                            counters=counters)

    def record(self, row: dict) -> None:
        """Keep ``row`` among the last ``keep`` rows recorded."""
        with self._lock:
            self._rows.append(row)

    def recent(self, kind: str) -> list:
        """The kept rows of ``kind``, oldest first."""
        with self._lock:
            return [r for r in self._rows if r.get("kind") == kind]


# the process's registry
REGISTRY = Registry()
span = REGISTRY.span
count = REGISTRY.count
totals = REGISTRY.totals
since = REGISTRY.since
record = REGISTRY.record
recent = REGISTRY.recent
