"""Reduce a profiler trace to device busy and idle time, executable time and
the host's activity in the device's idle gaps.

A trace is read into plain tuples, ``[(plane, [(line, [(name, start_ns,
dur_ns), ...]), ...]), ...]``, so the reduction below can be checked on a
trace written by hand.  Device planes are ``/device:<platform>:<n>``; the
device runs a program in each event of its ``XLA Modules`` line, and every
operation on the device runs inside one (the ``XLA Ops`` line, millions of
scan steps long, is not read).  Host spans are the ``TraceAnnotation``
events of the benchmark's own names.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
MODULES_LINE = "XLA Modules"


def read(trace_dir: str, host_names) -> list:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain tuples: the
    devices' program executions and the host events named in
    ``host_names``."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for p in pd.planes:
        if DEVICE_PLANE.match(p.name):
            keep = lambda ln, e: ln == MODULES_LINE
        elif p.name.startswith("/host"):
            keep = lambda ln, e: e.name in host_names
        else:
            continue
        out.append((p.name, [
            (ln.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in ln.events if keep(ln.name, e)])
            for ln in p.lines if not DEVICE_PLANE.match(p.name)
            or ln.name == MODULES_LINE]))
    return out


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` between sorted busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_spans(planes, names) -> list:
    """``(name, start, end)`` of every host event named in ``names``."""
    return [(n, s, s + d) for plane, lines in planes
            if plane.startswith("/host")
            for _, events in lines for n, s, d in events if n in names]


class Stack:
    """The innermost of nested host spans at a given time: the spans'
    boundaries sorted once, and the open spans replayed up to the time."""

    def __init__(self, spans):
        self.bounds = sorted([(s, 1, -(e - s), n) for n, s, e in spans]
                             + [(e, 0, 0.0, n) for n, s, e in spans])
        self._i, self._open, self._t = 0, [], float("-inf")

    def at(self, t: float) -> str:
        """Fast for non-decreasing ``t`` across calls."""
        if t < self._t:
            self._i, self._open = 0, []
        while self._i < len(self.bounds) and self.bounds[self._i][0] <= t:
            _, opens, _, n = self.bounds[self._i]
            if opens:
                self._open.append(n)
            elif n in self._open:
                del self._open[len(self._open) - 1
                               - self._open[::-1].index(n)]
            self._i += 1
        self._t = t
        return self._open[-1] if self._open else "outside any span"


def reduce(planes, window_name: str, span_names, module_pattern: str
           ) -> dict:
    """Per device: busy and idle seconds inside the window, the seconds of
    executions whose program name matches ``module_pattern``, each
    program's seconds, and the idle gaps with the innermost host span that
    was open in each."""
    spans = host_spans(planes, set(span_names) | {window_name})
    windows = [(s, e) for n, s, e in spans if n == window_name]
    if not windows:
        raise ValueError(f"no host span {window_name!r} in the trace")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    inner = Stack([(n, s, e) for n, s, e in spans if n != window_name])
    pattern = re.compile(module_pattern)
    devices = []
    for plane, lines in planes:
        if not DEVICE_PLANE.match(plane):
            continue
        runs = dict(lines).get(MODULES_LINE, [])
        busy = union(clip([(s, s + d) for _, s, d in runs], lo, hi))
        modules = [(n, s, d) for n, s, d in runs if lo <= s < hi]
        op_s: dict = {}
        for n, _, d in modules:
            op_s[n] = op_s.get(n, 0.0) + d * 1e-9
        idle = [(inner.at(0.5 * (s + e)), (e - s) * 1e-9)
                for s, e in gaps(busy, lo, hi)]
        devices.append({
            "plane": plane,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "matched_s": sum(d for n, _, d in modules
                             if pattern.search(n)) * 1e-9,
            "modules": sorted({n for n, _, _ in modules}),
            "op_s": op_s,
            "idle": idle,
        })
    return {"window_s": (hi - lo) * 1e-9, "devices": devices}


def breakdown(red: dict, top: int = 10) -> dict:
    """The device programs that took most time (mean over devices) and the
    idle time by what the host was doing, each as ``[[name, seconds]]``."""
    devs = red["devices"]
    ops: dict = {}
    idle: dict = {}
    for d in devs:
        for n, s in d["op_s"].items():
            ops[n] = ops.get(n, 0.0) + s / len(devs)
        for n, s in d["idle"]:
            idle[n] = idle.get(n, 0.0) + s / len(devs)
    rank = lambda m: [[n, s] for n, s in
                      sorted(m.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(idle)}
