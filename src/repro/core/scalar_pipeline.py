"""Event-based dual-issue in-order scalar-pipeline model (paper §3.1).

The paper's speedup baseline is a real 2 GHz dual-issue in-order scalar core
measured in gem5.  This module models it the way cycle-approximate perf
models score real cores — per-instruction-class *events* — and retires the
per-app ``SCALAR_BASELINE_MULT`` magic multipliers that used to stand in for
it (one of which, particlefilter's 0.104, was documented as non-physical).

The dynamic instruction stream of an app's scalar ROI is summarized into six
class segments (simple / mul / div / trans / load / branch) from the app's
published instruction counts, FU-class mix and its ``ScalarProfile``
(``tracegen.SCALAR_PROFILES``).  A float32 fold over the segments fills the
per-event-kind cycle and count accumulators:

  * ``issue``  — issue slots consumed (1/issue_width per instruction;
                 macro-op fusion removes one slot per fused pair)
  * ``raw``    — RAW-dependence stalls: a consumer waits the producer's
                 remaining latency, ``raw_frac x (lat - 1)`` per instruction
  * ``struct`` — structural stalls on the unpipelined divider
  * ``bmiss`` / ``bhit`` — branch events; each miss costs
                 ``branch_miss_penalty`` cycles
  * ``mem``    — scalar load stalls beyond the pipelined L1 hit
                 (``mem_stall_cyc`` per load, the fitted profile parameter)

Everything configuration-dependent (``issue_width``, ``branch_miss_penalty``,
``fusion``, the scalar clock) is a per-row parameter of the fold, which runs
on the host in numpy over a batch axis (``scalar_runtime_ns_batch`` is
bitwise-equal to the sequential path: both are the same elementwise
float32 operations in the same order).

>>> from repro.core import engine as eng
>>> t2 = scalar_runtime_ns("pathfinder")                  # default dual-issue
>>> t1 = scalar_runtime_ns("pathfinder",
...                        eng.VectorEngineConfig(issue_width=1))
>>> t1 > t2
True
>>> ev = scalar_events("pathfinder")
>>> ev["bhit"] > ev["bmiss"] > 0
True

Accuracy is pinned by the anchor scorecard: ``python -m
repro.core.scalar_pipeline --check`` verifies all 11 paper §5 anchors plus
batched-vs-sequential bitwise equivalence (the scripts/ci.sh
``scalar-scorecard`` gate); ``benchmarks/calibrate.py --scorecard`` prints
the per-anchor relative errors and the residual-error budget.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.core import tracegen

# Event kinds, in accumulator order (cva6 perf-model style: issue / hazard /
# branch events scored per instruction class).
EVENT_KINDS = ("issue", "raw", "struct", "bmiss", "bhit", "mem", "fused")

# Segment rows, fixed order; every app shares this (6, N_COLS) shape.
SEG_CLASSES = ("simple", "mul", "div", "trans", "load", "branch")

# FIXED architectural latencies (scalar-core cycles; not fitted): fully
# bypassed ALU, pipelined 4-cycle FP-MAC and L1 hit, 20-cycle unpipelined
# divide, 24-cycle transcendental sequence.  docs/calibration.md documents
# fitted-vs-fixed in full.
OP_LATENCY = np.array([1.0, 4.0, 20.0, 24.0, 4.0, 1.0], np.float32)

# FIXED: back-to-back occupancy rate of the single unpipelined divider
# (structural-hazard events beyond the RAW stalls already counted).
DIV_STRUCT_RATE = 0.25

# segment feature columns
_COLS = ("count", "lat", "raw_frac", "fusible", "bmiss_rate", "mem_stall",
         "is_branch", "struct_frac")
N_COLS = len(_COLS)


def segments_for(app_name: str) -> np.ndarray:
    """The (6, 8) event-segment array of one app's scalar-version ROI.

    Row counts decompose ``counts.scalar_code_total`` (scaled by the
    profile's ``roi_instr_fraction``): branches and loads per the profile
    fractions, FP work per the app's FU-class mix over its element-op total,
    the remainder simple-class ALU.
    """
    app = tracegen.app_for(app_name)
    prof = tracegen.scalar_profile_for(app_name)
    counts = app.counts(8)               # element ops at MVL=8 (min overhead)
    n = counts.scalar_code_total * prof.roi_instr_fraction
    work = counts.vector_ops * prof.roi_instr_fraction
    n_branch = prof.branch_frac * n
    n_load = prof.load_frac * n
    n_mul = work * app.mix.get("mul", 0.0)
    n_div = work * app.mix.get("div", 0.0)
    n_trans = work * app.mix.get("trans", 0.0)
    n_simple = max(n - n_branch - n_load - n_mul - n_div - n_trans, 0.0)
    seg = np.zeros((len(SEG_CLASSES), N_COLS), np.float32)
    seg[:, 0] = (n_simple, n_mul, n_div, n_trans, n_load, n_branch)
    seg[:, 1] = OP_LATENCY
    seg[:, 2] = prof.raw_frac
    seg[0, 3] = prof.fusible_frac        # fusion pairs are simple-class
    seg[5, 4] = prof.branch_miss_rate
    seg[4, 5] = prof.mem_stall_cyc
    seg[5, 6] = 1.0
    seg[2, 7] = DIV_STRUCT_RATE
    return seg


def cfg_scalar_params(cfg=None) -> tuple:
    """The scalar-core parameter vector ``(issue_width, branch_miss_penalty,
    fusion, scalar_freq_ghz)`` of a config (np scalars, stackable for the
    batch axis); ``None`` selects the Table-10 default core."""
    if cfg is None:
        from repro.core import engine as eng
        cfg = eng.VectorEngineConfig()
    return (np.float32(cfg.issue_width), np.float32(cfg.branch_miss_penalty),
            np.float32(1.0 if cfg.fusion else 0.0),
            np.float32(cfg.scalar_freq_ghz))


def _fold(seg, params):
    """Fold the segment events into (total cycles, per-kind accumulators).

    ``seg`` is ``[B, 6, N_COLS]`` and each of the four ``params`` is ``[B]``
    (float32).  The fold is 6 rows of elementwise float32 numpy on the host:
    every operation rounds once, in a fixed order, so a batch of N and N
    batches of one give the same bits, and no device dispatch is spent on
    48 numbers."""
    issue_w, bmp, fusion_f, _freq = (np.asarray(p, np.float32)
                                     for p in params)
    seg = np.asarray(seg, np.float32)
    one = np.float32(1.0)
    cyc = np.zeros(seg.shape[0], np.float32)
    ev = np.zeros((seg.shape[0], len(EVENT_KINDS)), np.float32)
    for r in range(seg.shape[1]):
        count, lat, raw, fusible, bmr, mem, is_br, struct = seg[:, r].T
        fused = count * fusible * fusion_f        # fused pairs: 1 slot each
        slots = (count - fused) / issue_w
        stall_lat = np.maximum(lat - one, np.float32(0.0))
        raw_st = count * raw * stall_lat
        struct_st = count * struct * stall_lat
        n_miss = count * bmr
        bmiss_st = n_miss * bmp
        n_hit = count * is_br - n_miss
        mem_st = count * mem
        cyc = cyc + slots + raw_st + struct_st + bmiss_st + mem_st
        ev = ev + np.stack([slots, raw_st, struct_st, n_miss, n_hit,
                            mem_st, fused], axis=1)
    return cyc, ev


def _fold_one(seg, params: tuple):
    """``_fold`` of one ``(6, N_COLS)`` segment array -> (cycles, events)."""
    cyc, ev = _fold(np.asarray(seg, np.float32)[None],
                    tuple(np.asarray([p], np.float32) for p in params))
    return cyc[0], ev[0]


def scalar_cycles(app_name: str, cfg=None) -> float:
    """Total modeled scalar-core cycles of the app's scalar-version ROI."""
    return float(_fold_one(segments_for(app_name), cfg_scalar_params(cfg))[0])


def scalar_events(app_name: str, cfg=None) -> dict:
    """Per-event-kind accumulators (cycles for stall kinds, counts for
    ``bmiss``/``bhit``/``fused``) — the scorecard's breakdown view."""
    _, ev = _fold_one(segments_for(app_name), cfg_scalar_params(cfg))
    return dict(zip(EVENT_KINDS, (float(v) for v in ev)))


@functools.lru_cache(maxsize=None)
def _runtime_cached(base_app: str, params: tuple) -> float:
    return float(_fold_one(segments_for(base_app), params)[0]) \
        / float(params[3])


def scalar_runtime_ns(app_name: str, cfg=None) -> float:
    """Modeled scalar-version runtime (ns) on the config's scalar core
    (``None``: the default 2 GHz dual-issue core).  Memoized per
    (base app, scalar-core knobs): trace-source variants (``"<app>:asm"``)
    share the base app's scalar code, and sweeps over vector-side knobs all
    hit one cache entry."""
    base = tracegen.split_variant(app_name)[0]
    return _runtime_cached(base, cfg_scalar_params(cfg))


def scalar_runtime_ns_batch(apps, cfgs) -> list[float]:
    """Batched ``scalar_runtime_ns``: N (app, config) pairs through one
    fold.  Bitwise-equal to the sequential path (the fold is shared;
    ``--check`` asserts it)."""
    if len(apps) != len(cfgs):
        raise ValueError(f"{len(apps)} apps vs {len(cfgs)} configs")
    if not apps:
        return []
    segs = np.stack([segments_for(a) for a in apps])
    cols = list(zip(*(cfg_scalar_params(c) for c in cfgs)))
    cyc, _ = _fold(segs, cols)
    freqs = np.asarray(cols[3], np.float32)
    return [float(c) / float(f) for c, f in zip(cyc, freqs)]


# --------------------------------------------------------------------------
# --check: the CI scalar-scorecard gate
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse

    from repro.core import engine as eng
    from repro.core import suite

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="verify the §5 anchors, batched-vs-sequential "
                         "bitwise equivalence and knob monotonicity "
                         "(the scripts/ci.sh scalar-scorecard gate)")
    args = ap.parse_args(argv)
    if not args.check:
        ap.print_help()
        return 0

    failures = []
    # 1. all 11 paper §5 anchors within the documented tolerance
    from repro.core.anchors import ANCHORS, EQ_LO, EQ_HI, LT_SLACK
    print("== anchors ==")
    for app, mvl, lanes, target, kind in ANCHORS:
        cfg = eng.VectorEngineConfig(mvl=mvl, lanes=lanes)
        got = suite.speedup(app, cfg)
        if kind == "eq":
            ok = EQ_LO <= got / target <= EQ_HI
        else:
            ok = got <= target * LT_SLACK
        mark = "ok" if ok else "MISS"
        print(f"  {app:16s} mvl={mvl:3d} L={lanes} model={got:5.2f} "
              f"paper={target:5.2f} [{kind}] {mark}")
        if not ok:
            failures.append(f"anchor {app}@{mvl}x{lanes}")

    # 2. batched == sequential, bitwise
    apps = sorted(tracegen.APPS)
    cfgs = [eng.VectorEngineConfig(issue_width=1 + i % 3,
                                   branch_miss_penalty=float(4 + 2 * (i % 4)),
                                   fusion=bool(i % 2))
            for i in range(len(apps))]
    batched = scalar_runtime_ns_batch(apps, cfgs)
    seq = [scalar_runtime_ns(a, c) for a, c in zip(apps, cfgs)]
    if batched == seq:
        print("== batched-vs-sequential: bitwise-equal "
              f"({len(apps)} pairs) ==")
    else:
        failures.append("batched != sequential")

    # 3. knob monotonicity + physical-CPI floor on every app
    for a in apps:
        t1 = scalar_runtime_ns(a, eng.VectorEngineConfig(issue_width=1))
        t2 = scalar_runtime_ns(a)
        t4 = scalar_runtime_ns(a, eng.VectorEngineConfig(issue_width=4))
        bp = scalar_runtime_ns(
            a, eng.VectorEngineConfig(branch_miss_penalty=20.0))
        fu = scalar_runtime_ns(a, eng.VectorEngineConfig(fusion=True))
        if not (t1 > t2 >= t4 and bp > t2 and fu < t2):
            failures.append(f"monotonicity {a}")
        prof = tracegen.scalar_profile_for(a)
        counts = tracegen.app_for(a).counts(8)
        n_roi = counts.scalar_code_total * prof.roi_instr_fraction
        cpi = scalar_cycles(a) / n_roi
        if cpi < 0.5:
            failures.append(f"non-physical CPI {a}: {cpi:.3f}")
    if not any(f.startswith(("monotonicity", "non-physical"))
               for f in failures):
        print("== knob monotonicity + CPI floor: ok "
              f"({len(apps)} apps) ==")

    if failures:
        print("FAILURES:", ", ".join(failures))
        return 1
    print("scalar-scorecard: PASS")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
