"""What the program's own spans and counters say about the traced studies.

The span readers take the ``dse.phase`` rows that ``dse.explore`` writes
per ``engine.*`` span (the study loop keeps every phase row of a study); the
counter readers take the ``dse.study`` rows that the program keeps in
``repro.core.telemetry``, the last one per study of the window.  A program
without them gives ``None``, and the metric is left out.
"""
from __future__ import annotations


def ms_per_kcell(ctx, phase: str):
    """Host wall of ``phase`` over the traced studies, in ms per 1,000
    cells answered."""
    studies = ctx.data.get("studies")
    if not studies or any(phase not in s["phases"] for s in studies):
        return None
    cells = sum(s["cells"] for s in studies)
    return 1e3 * sum(s["phases"][phase] for s in studies) / (cells / 1e3)


def window_counters(ctx):
    """The program's counters summed over the traced studies, or ``None``:
    its last ``dse.study`` rows, one per study of the window, matched to
    the window's studies by their cell counts."""
    from repro.core import telemetry

    studies = ctx.data.get("studies")
    recent = getattr(telemetry, "recent", None)
    if not studies or recent is None:
        return None
    rows = recent("dse.study")[-len(studies):]
    if [r["cells"] for r in rows] != [s["cells"] for s in studies]:
        return None
    total: dict = {}
    for r in rows:
        for k, v in r["counters"].items():
            total[k] = total.get(k, 0) + v
    return total
