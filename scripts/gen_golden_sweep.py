"""Golden sweep table (24 configs x 10 apps + 10 :asm variants): gen/check.

Two modes:

* default — regenerate ``tests/golden_sweep.json``.  Run after an
  *intentional* recalibration of the timing model, then review the diff.
* ``--check`` — regenerate **in memory** and diff against the checked-in
  table with a per-cell tolerance report (app, cell, got, want, rel err),
  exiting non-zero on drift.  This is what ``tests/test_golden_sweep.py``
  wraps: a drifted table fails with the exact offending cells, not a silent
  full-file mismatch.

    PYTHONPATH=src python scripts/gen_golden_sweep.py [--check] [--rtol R]
"""
from __future__ import annotations

import json
import os

from repro.core import suite

OUT = os.path.join(os.path.dirname(__file__), "..", "tests",
                   "golden_sweep.json")
RTOL = 1e-2  # generous vs float32 platform jitter, tight vs real drift


def apps() -> list[str]:
    """All 10 registered apps plus the 10 RVV-assembly-sourced variants
    (trace source: the generated src/repro/asm corpus via repro.core.rvv)
    — 480 cells, up from 408 when the corpus was the hand-written RiVec
    seven (PR 7 generates all ten from the jaxpr kernel specs).  The
    ``:asm`` cells pin the *decoder* end to end: a decode regression that
    survives the crossval mixes still shows up as a speedup drift here."""
    from repro.core import tracegen
    return sorted(tracegen.APPS) + list(tracegen.ASM_APPS)


def to_payload(table: dict) -> dict:
    """``suite.sweep_all`` output -> the golden file's layout."""
    return {app: {f"{m}x{l}": round(s, 6) for (m, l), s in grid.items()}
            for app, grid in table.items()}


def _payload() -> dict:
    return to_payload(suite.sweep_all(apps()))


def diff_report(got: dict, golden: dict, rtol: float = RTOL) -> list[str]:
    """Per-cell tolerance report between two payloads (empty == clean)."""
    report: list[str] = []
    for app in sorted(set(golden) - set(got)):
        report.append(f"{app}: in golden table but not in sweep")
    for app in sorted(set(got) - set(golden)):
        report.append(f"{app}: swept but missing from golden table "
                      f"(regenerate: PYTHONPATH=src python "
                      f"scripts/gen_golden_sweep.py)")
    for app in sorted(set(got) & set(golden)):
        cells_got, cells_want = got[app], golden[app]
        for cell in sorted(set(cells_want) - set(cells_got)):
            report.append(f"{app} {cell}: missing from sweep")
        for cell in sorted(set(cells_got) - set(cells_want)):
            report.append(f"{app} {cell}: not in golden table")
        for cell in sorted(set(cells_got) & set(cells_want)):
            g, w = cells_got[cell], cells_want[cell]
            rel = abs(g - w) / max(abs(w), 1e-12)
            if rel > rtol:
                report.append(f"{app} {cell}: got={g:.6f} want={w:.6f} "
                              f"rel={rel:.2e} > rtol={rtol:g}")
    return report


def check(rtol: float = RTOL, golden_path: str = OUT) -> list[str]:
    """Regenerate the sweep in memory and diff against the golden file.
    Returns the per-cell report; never writes anything."""
    with open(golden_path) as f:
        golden = json.load(f)
    return diff_report(_payload(), golden, rtol=rtol)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="diff the regenerated sweep against the golden "
                         "table instead of writing it")
    ap.add_argument("--rtol", type=float, default=RTOL)
    args = ap.parse_args(argv)
    if args.check:
        report = check(rtol=args.rtol)
        for line in report:
            print(line)
        print(f"golden check: {len(report)} problem(s) at "
              f"rtol={args.rtol:g}")
        return 1 if report else 0
    payload = _payload()
    with open(OUT, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {os.path.normpath(OUT)}: "
          f"{sum(len(g) for g in payload.values())} cells")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
