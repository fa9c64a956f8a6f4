"""Calibrate the scalar-pipeline model against the paper's §5 anchors.

The event-based scalar model (``repro.core.scalar_pipeline``) has exactly
ONE fitted parameter per app — ``mem_stall_cyc``, the average scalar-load
stall beyond the pipelined L1 hit — plus particlefilter's explicit
``roi_instr_fraction`` correction.  Everything else (op latencies, issue
width, divider structural rate, profile fractions) is fixed and documented
in docs/calibration.md.

Fit mode (default) solves both closed-form:

  * each "eq"-anchored app's implied scalar-runtime target is the geomean
    over its anchors of ``paper_speedup x modeled_vector_runtime``; cycles
    are linear in ``mem_stall_cyc`` (slope = the load count), so the fit is
    one division, clipped to the physical band [0, 40] cycles;
  * particlefilter publishes only "never beats scalar" bounds, so its
    ``mem_stall_cyc`` is FIXED at 4.0 (gather-bound profile) and the ROI
    correction is solved instead: cycles scale linearly in
    ``roi_instr_fraction``, targeted at speedup = 0.95 x the tightest "lt"
    bound;
  * the frontend-only ML workloads have no paper anchors; their targets are
    the frozen modeled baselines (continuity with the pre-PR-9 numbers,
    documented as modeled-not-paper-calibrated).

Output is the ``ScalarProfile`` table to paste into
``tracegen.SCALAR_PROFILES`` — the fit is a fixed point of the committed
values.

``--scorecard`` prints the accuracy scorecard: all 11 §5 anchors with
per-anchor relative error, the per-app event breakdown, the residual-error
budget, and the scorecard wall-clock.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import engine as eng
from repro.core import scalar_pipeline as sp
from repro.core import suite, tracegen
from repro.core.anchors import ANCHORS, EQ_HI, EQ_LO, LT_SLACK

# particlefilter's gather-bound load stall is fixed, not fitted (its anchors
# are bounds, not targets — they pin the ROI correction instead)
PF_MEM_STALL = 4.0
# target speedup at PF's tightest "lt" bound: just under the bound
PF_LT_MARGIN = 0.95

# Frozen pre-PR-9 modeled scalar baselines for the anchor-less ML workloads
# (ns).  These came from the retired SCALAR_BASELINE_MULT entries that were
# *modeled* (chosen for a plausible best-config band), not paper-fitted;
# refitting against them keeps the ML numbers continuous across the scalar
# model replacement.
ML_TARGET_NS = {
    "flash_attention": 3.0424e10,
    "decode_attention": 1.7848e9,
    "ssd_scan": 2.4750e8,
}

MEM_STALL_LO, MEM_STALL_HI = 0.0, 40.0


def _cycles_split(app: str) -> tuple[float, float, float]:
    """(cycles at mem_stall=0, load count, current roi) — cycles are linear
    in both fitted parameters: ``cyc = roi_scale x (cyc0 + n_load x ms)``
    where the segment counts already include the committed roi."""
    seg = sp.segments_for(app)
    n_load = float(seg[4, 0])
    seg0 = seg.copy()
    seg0[4, 5] = 0.0
    cyc0, _ = sp._fold_one(seg0, sp.cfg_scalar_params(None))
    roi = tracegen.scalar_profile_for(app).roi_instr_fraction
    return float(cyc0), n_load, roi


def _anchor_targets() -> dict:
    """Per-app implied scalar-runtime targets (ns): geomean over "eq"
    anchors of ``paper_speedup x modeled_vector_runtime``; for apps with
    only "lt" anchors, ``PF_LT_MARGIN x`` the tightest bound."""
    eq, lt = {}, {}
    for app, mvl, lanes, target, kind in ANCHORS:
        cfg = eng.VectorEngineConfig(mvl=mvl, lanes=lanes)
        v = suite.vector_runtime_ns(app, cfg)
        (eq if kind == "eq" else lt).setdefault(app, []).append(target * v)
    out = {a: float(np.exp(np.mean(np.log(ts)))) for a, ts in eq.items()}
    for a, ts in lt.items():
        if a not in out:
            out[a] = PF_LT_MARGIN * min(ts)
    out.update(ML_TARGET_NS)
    return out


def fit() -> dict:
    """Solve every app's fitted parameter closed-form; returns
    ``{app: (mem_stall_cyc, roi_instr_fraction)}``."""
    freq = eng.VectorEngineConfig().scalar_freq_ghz
    fitted = {}
    for app, target_ns in sorted(_anchor_targets().items()):
        target_cyc = target_ns * freq
        cyc0, n_load, roi = _cycles_split(app)
        if app == "particlefilter":
            # mem stall fixed; solve roi (cycles linear in roi):
            # target = (roi/roi_now) x (cyc0 + n_load x PF_MEM_STALL)
            cyc_roi1 = (cyc0 + n_load * PF_MEM_STALL) / roi
            fitted[app] = (PF_MEM_STALL, target_cyc / cyc_roi1)
        else:
            ms = (target_cyc - cyc0) / n_load
            fitted[app] = (float(np.clip(ms, MEM_STALL_LO, MEM_STALL_HI)),
                           1.0)
    return fitted


def print_fit(fitted: dict) -> None:
    print("fitted ScalarProfile parameters (paste into "
          "tracegen.SCALAR_PROFILES):")
    print(f"  {'app':16s} {'mem_stall_cyc':>13s} {'roi_frac':>9s} "
          f"{'committed':>21s}")
    drift = 0.0
    for app, (ms, roi) in sorted(fitted.items()):
        prof = tracegen.scalar_profile_for(app)
        drift = max(drift, abs(ms - prof.mem_stall_cyc),
                    abs(roi - prof.roi_instr_fraction))
        print(f"  {app:16s} {ms:13.2f} {roi:9.4f} "
              f"  ({prof.mem_stall_cyc:6.2f}, {prof.roi_instr_fraction:.4f})")
    print(f"max |fit - committed| = {drift:.3g} "
          f"({'fixed point: committed values reproduce the fit' if drift < 0.05 else 'STALE — update tracegen.SCALAR_PROFILES'})")


def scorecard() -> int:
    """The accuracy scorecard: anchors + rel-err, event breakdown, residual
    budget, wall-clock.  Returns a process exit code."""
    t0 = time.perf_counter()
    rows = []
    for app, mvl, lanes, target, kind in ANCHORS:
        cfg = eng.VectorEngineConfig(mvl=mvl, lanes=lanes)
        rows.append((app, mvl, lanes, target, kind, suite.speedup(app, cfg)))
    wall = time.perf_counter() - t0

    print("== anchor scorecard (11 paper §5 points) ==")
    print(f"  {'app':16s} {'cfg':>9s} {'model':>6s} {'paper':>6s} "
          f"{'rel-err':>8s}  verdict")
    misses = 0
    for app, mvl, lanes, target, kind, got in rows:
        rel = got / target - 1.0
        if kind == "eq":
            ok = EQ_LO <= got / target <= EQ_HI
            verdict = "ok" if ok else "MISS"
        else:
            ok = got <= target * LT_SLACK
            verdict = "ok (bound)" if ok else "MISS"
        misses += not ok
        print(f"  {app:16s} mvl={mvl:3d}x{lanes} {got:6.2f} {target:6.2f} "
              f"{rel:+8.1%}  [{kind}] {verdict}")
    eq_errs = [abs(np.log(got / target))
               for app, _, _, target, kind, got in rows if kind == "eq"]
    print(f"  geomean |log-err| over eq anchors: "
          f"{float(np.exp(np.mean(eq_errs))) - 1.0:.1%}")

    print("\n== per-app event breakdown (cycles per ROI instruction) ==")
    print(f"  {'app':16s} {'issue':>6s} {'raw':>6s} {'struct':>6s} "
          f"{'bmiss':>6s} {'mem':>6s} {'CPI':>6s}")
    for app in sorted(tracegen.APPS):
        ev = sp.scalar_events(app)
        prof = tracegen.scalar_profile_for(app)
        n = tracegen.app_for(app).counts(8).scalar_code_total \
            * prof.roi_instr_fraction
        bmp = eng.VectorEngineConfig().branch_miss_penalty
        parts = (ev["issue"], ev["raw"], ev["struct"], ev["bmiss"] * bmp,
                 ev["mem"])
        print(f"  {app:16s} " + " ".join(f"{p / n:6.3f}" for p in parts)
              + f" {sum(parts) / n:6.3f}")

    print("\n== residual-error budget ==")
    print(f"  eq anchors: model/paper within [{EQ_LO}, {EQ_HI}] — covers "
          "figure read-off error, the fitted mem_stall_cyc's one-knob "
          "coarseness, and vector-side abstraction (no OoO scalar window).")
    print("  lt anchors: hard bounds (paper's qualitative claims), "
          "no tolerance.")
    pf = tracegen.scalar_profile_for("particlefilter")
    print(f"  particlefilter ROI correction: roi_instr_fraction = "
          f"{pf.roi_instr_fraction:.4f} — the named term for the Table-6 "
          "(instruction counts) vs Figure-7 (timed ROI) accounting "
          "difference; replaces the retired 0.104 multiplier "
          f"(implied CPI {sp.scalar_cycles('particlefilter') / (tracegen.app_for('particlefilter').counts(8).scalar_code_total * pf.roi_instr_fraction):.2f}, physical).")
    print("  ML workloads: no paper anchors; baselines are modeled "
          "(frozen pre-PR-9 continuity targets), excluded from the anchor "
          "budget.")
    print(f"\nscorecard wall-clock: {wall:.2f} s ({len(rows)} anchors)")
    return 1 if misses else 0


if __name__ == "__main__":
    import argparse
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scorecard", action="store_true",
                    help="print the anchor scorecard instead of fitting")
    args = ap.parse_args()
    if args.scorecard:
        sys.exit(scorecard())
    print_fit(fit())
