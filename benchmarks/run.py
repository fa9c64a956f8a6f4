"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  For the characterization tables
(3-9) `derived` is the max relative error vs the published cells; for the
scalability figures (4-10) it is the modeled speedup; for kernels it is
throughput.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _t(fn, *args, reps=3):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6


# machine-readable results collected while the driver runs; main() writes
# them to --bench-json (BENCH_pr10.json by default)
_BENCH: dict = {}


def table_3_to_9_characterization():
    from repro.core import characterize as ch
    rows = []
    for app in ch.PAPER_TABLES:
        t0 = time.perf_counter()
        errs = ch.compare_to_paper(app)
        us = (time.perf_counter() - t0) * 1e6
        worst = max(v for r in errs for k, v in r.items() if k.startswith("err"))
        rows.append((f"table_characterization_{app}", us, f"max_err={worst:.4f}"))
        vao = ch.characterize(app, 8).vao_speedup
        rows.append((f"vao_speedup_{app}", 0.0, f"{vao:.3f}"))
    return rows


def figures_4_to_10_scalability():
    """Figures 4-10 grid, one batched dispatch set instead of per-point sims."""
    from repro.core import engine as eng
    from repro.core import suite
    apps = ("blackscholes", "canneal", "jacobi-2d", "particlefilter",
            "pathfinder", "streamcluster", "swaptions")
    pairs = [(app, eng.VectorEngineConfig(mvl=mvl, lanes=lanes))
             for app in apps for mvl in (8, 64, 256) for lanes in (1, 8)]
    # Fig 10: swaptions LLC study rides in the same batch
    pairs += [("swaptions", eng.VectorEngineConfig(mvl=256, lanes=8, l2_kb=l2))
              for l2 in (256, 1024)]
    t0 = time.perf_counter()
    speedups = suite.speedup_batch(pairs)
    us_each = (time.perf_counter() - t0) * 1e6 / len(pairs)
    rows = []
    for (app, cfg), s in zip(pairs[:-2], speedups[:-2]):
        rows.append((f"fig_scalability_{app}_mvl{cfg.mvl}_l{cfg.lanes}",
                     us_each, f"speedup={s:.2f}"))
    for (app, cfg), s in zip(pairs[-2:], speedups[-2:]):
        rows.append((f"fig10_swaptions_l2_{cfg.l2_kb}kb", us_each,
                     f"speedup={s:.2f}"))
    return rows


def sweep_llc():
    """Fig-10 as a first-class batched study: the full LLC grid {256 KB, 1 MB}
    for the memory-stressed apps vs a compute-bound control, one batch."""
    from repro.core import engine as eng
    from repro.core import suite
    apps = ("streamcluster", "canneal", "swaptions", "blackscholes")
    l2s = (256, 1024)
    pairs = [(a, eng.VectorEngineConfig(mvl=mvl, lanes=8, l2_kb=l2))
             for a in apps for l2 in l2s for mvl in (64, 256)]
    t0 = time.perf_counter()
    vals = suite.speedup_batch(pairs)
    us_each = (time.perf_counter() - t0) * 1e6 / len(pairs)
    return [(f"sweep_llc_{a}_{c.label()}", us_each, f"speedup={s:.2f}")
            for (a, c), s in zip(pairs, vals)]


def sweep_mshr():
    """MSHR saturation: mshrs=1 serializes indexed-pattern (gather) misses —
    canneal degrades, the unit-stride apps stay within noise."""
    from repro.core import engine as eng
    from repro.core import suite
    apps = ("canneal", "blackscholes", "jacobi-2d")
    pairs = [(a, eng.VectorEngineConfig(mvl=64, lanes=4, mshrs=m))
             for a in apps for m in (1, 4, 16)]
    t0 = time.perf_counter()
    vals = suite.speedup_batch(pairs)
    us_each = (time.perf_counter() - t0) * 1e6 / len(pairs)
    return [(f"sweep_mshr_{a}_{c.label()}", us_each, f"speedup={s:.2f}")
            for (a, c), s in zip(pairs, vals)]


def sweep_wallclock(quick: bool = False):
    """The acceptance benchmark: the full 24-config x 10-app sweep (7 RiVec
    + 3 frontend-derived ML workloads), batched engine vs the sequential
    per-(app, config) seed path."""
    from repro.core import engine as eng
    from repro.core import suite
    from repro.core import tracegen
    if quick:
        apps, mvls, lanes = ["blackscholes", "ssd_scan"], (8, 64), (1, 8)
    else:
        apps, mvls, lanes = sorted(tracegen.APPS), (8, 16, 32, 64, 128, 256), (1, 2, 4, 8)
    n = len(apps) * len(mvls) * len(lanes)
    t0 = time.perf_counter()
    batched = suite.sweep_all(apps, mvls=mvls, lanes=lanes)
    t_batched = time.perf_counter() - t0
    t0 = time.perf_counter()
    seq = {a: {(m, l): suite.speedup(a, eng.VectorEngineConfig(mvl=m, lanes=l))
               for m in mvls for l in lanes} for a in apps}
    t_seq = time.perf_counter() - t0
    worst = max(abs(batched[a][k] - seq[a][k]) / seq[a][k]
                for a in apps for k in seq[a])
    label = "quick" if quick else "full"
    _BENCH["sweep"] = {
        "mode": label, "n_cells": n, "apps": list(apps),
        "wall_s_batched": t_batched, "wall_s_sequential": t_seq,
        "batched_speedup": t_seq / t_batched, "max_rel_diff": worst,
        "jit_cache": eng.jit_cache_size(),
    }
    return [
        (f"sweep_{label}_{n}cfg_batched", t_batched * 1e6,
         f"wall_s={t_batched:.2f}"),
        (f"sweep_{label}_{n}cfg_sequential", t_seq * 1e6,
         f"wall_s={t_seq:.2f}"),
        (f"sweep_{label}_batched_speedup", 0.0,
         f"{t_seq / t_batched:.1f}x|max_rel_diff={worst:.2e}"
         f"|jit_cache={eng.jit_cache_size()}"),
    ]


def steady_state_table():
    """Per-app steady-state loop-body times at the reference config — the
    per-app entry of the bench JSON, one batched dispatch set.  PR 10 adds
    the marginal lane/VMU utilization over the measurement window."""
    from repro.core import engine as eng
    from repro.core import suite, tracegen
    cfg = eng.VectorEngineConfig(mvl=64, lanes=4)
    apps = sorted(tracegen.APPS)
    bodies = [tracegen.body_for(a, suite.effective_mvl(a, cfg), cfg)
              for a in apps]
    t0 = time.perf_counter()
    rows = eng.steady_state_time_batch(bodies, [cfg] * len(apps),
                                       with_util=True)
    us_each = (time.perf_counter() - t0) * 1e6 / len(apps)
    _BENCH["steady_state_ns"] = {a: r["steady_ns"]
                                 for a, r in zip(apps, rows)}
    _BENCH["steady_state_util"] = {
        a: {"lane_util": r["lane_util"], "vmu_util": r["vmu_util"]}
        for a, r in zip(apps, rows)}
    _BENCH["steady_state_config"] = cfg.label()
    return [(f"steady_state_{a}_{cfg.label()}", us_each,
             f"{r['steady_ns']:.1f}ns|lane_util={r['lane_util']:.3f}"
             f"|vmu_util={r['vmu_util']:.3f}")
            for a, r in zip(apps, rows)]


def profile_rows(quick: bool = False, timeline_path: str | None = None):
    """Mechanistic cycle-attribution rows (ISSUE 10): the per-app telemetry
    scorecard at the reference config (plus the ooo/crossbar corner in full
    mode) and a committed example Chrome-trace timeline.

    Each row prints the top bottleneck module, the module fractions, and the
    event-sum identity error (attributed cycles must reconstruct the total
    runtime to float32 tolerance)."""
    from repro.core import engine as eng
    from repro.core import suite, telemetry, tracegen
    cfgs = [eng.VectorEngineConfig(mvl=64, lanes=4)]
    if not quick:
        cfgs.append(eng.VectorEngineConfig(mvl=256, lanes=8, ooo_issue=True,
                                           interconnect="crossbar"))
    t0 = time.perf_counter()
    rep = telemetry.scorecard(cfgs=cfgs)
    wall = time.perf_counter() - t0
    us_each = wall * 1e6 / len(rep.rows)
    worst_ident = max(r["identity_rel_err"] for r in rep.rows)
    rows = []
    for r in rep.rows:
        fracs = "|".join(f"{m}={r['modules'][m]:.3f}"
                         for m in telemetry.MODULES)
        rows.append((f"profile_{r['app']}_{r['config']}", us_each,
                     f"top={r['top']}|{fracs}"
                     f"|ident_err={r['identity_rel_err']:.1e}"))
    if timeline_path is None:
        timeline_path = os.path.join(os.path.dirname(__file__), "..",
                                     "examples",
                                     "timeline_blackscholes.json")
    os.makedirs(os.path.dirname(timeline_path), exist_ok=True)
    app, cfg = "blackscholes", cfgs[0]
    body = tracegen.body_for(app, suite.effective_mvl(app, cfg), cfg)
    doc = telemetry.write_chrome_trace(timeline_path, body.tile(2), cfg,
                                       label=app)
    rows.append(("profile_timeline_blackscholes", 0.0,
                 f"{len(doc['traceEvents'])}events"
                 f"|{os.path.normpath(timeline_path)}"))
    _BENCH["profile"] = {
        "scorecard": rep.to_dict(), "wall_s": wall,
        "worst_identity_rel_err": worst_ident,
        "timeline": os.path.normpath(timeline_path),
        "jit_cache": eng.jit_cache_size(),
    }
    return rows


def frontend_crossval():
    """Jaxpr-frontend cross-validation (derived vs hand-coded bodies): the
    static mixes must match exactly, steady-state time within 5%."""
    from repro.core import frontend as fe
    t0 = time.perf_counter()
    reports = fe.cross_validate_all()
    us_each = (time.perf_counter() - t0) * 1e6 / len(reports)
    _BENCH["frontend_crossval"] = {
        "all_ok": all(r.ok for r in reports),
        "worst_time_rel_err": max(r.time_rel_err for r in reports),
        "apps": sorted({r.app for r in reports}),
    }
    return [(f"frontend_crossval_{r.app}", us_each,
             f"time_err={r.time_rel_err:.4f}|{'ok' if r.ok else 'FAIL'}")
            for r in reports]


def rvv_rows(quick: bool = False):
    """RVV assembly frontend rows: per-app decode wall-clock (corpus ->
    isa.Trace through the abstract interpreter), asm-vs-hand cross-validation
    verdicts, and asm-variant sweep parity against the hand-coded suite.

    ``--quick`` cross-validates at the two PR-3 reference configs; the full
    run uses the per-MVL grid the ci.sh ``rvv-crossval`` gate enforces."""
    from repro.core import engine as eng
    from repro.core import rvv, suite, tracegen
    rows = []
    cfg = eng.VectorEngineConfig(mvl=64, lanes=4)
    corpus = [a for a in sorted(tracegen.APPS) if tracegen.APPS[a].asm]
    rvv._DECODE_CACHE.clear()
    t0 = time.perf_counter()
    for app in corpus:
        ta = time.perf_counter()
        d = rvv.decode_app(app, suite.effective_mvl(app, cfg), cfg)
        us = (time.perf_counter() - ta) * 1e6
        rows.append((f"rvv_decode_{app}", us,
                     f"{len(d.trace)}entries|chunks={d.chunks:g}"))
    decode_wall = time.perf_counter() - t0
    cfgs = [cfg, eng.VectorEngineConfig(mvl=16, lanes=2)] if quick else None
    t0 = time.perf_counter()
    reports = rvv.cross_validate_all(cfgs=cfgs)
    crossval_wall = time.perf_counter() - t0
    worst = max(r.time_rel_err for r in reports)
    n_bitwise = sum(r.fingerprint_eq for r in reports)
    for r in reports:
        rows.append((f"rvv_crossval_{r.app}_{r.cfg_label}", 0.0,
                     f"time_err={r.time_rel_err:.4f}"
                     f"|{'bitwise' if r.fingerprint_eq else 'mix-exact'}"
                     f"|{'ok' if r.ok else 'FAIL'}"))
    # asm-variant sweep parity: the :asm suite through the batched engine
    t0 = time.perf_counter()
    asm_tab = suite.sweep_all(tracegen.ASM_APPS, mvls=(8, 64, 256),
                              lanes=(1, 8))
    hand_tab = suite.sweep_all(corpus, mvls=(8, 64, 256), lanes=(1, 8))
    sweep_wall = time.perf_counter() - t0
    worst_sweep = max(
        abs(asm_tab[f"{a}:asm"][k] - hand_tab[a][k]) / hand_tab[a][k]
        for a in corpus for k in hand_tab[a])
    rows.append(("rvv_asm_sweep_parity", sweep_wall * 1e6,
                 f"max_rel_diff={worst_sweep:.2e}|cells="
                 f"{sum(len(v) for v in asm_tab.values())}"))
    _BENCH["rvv"] = {
        "decode_wall_s": decode_wall,
        "crossval_wall_s": crossval_wall,
        "all_ok": all(r.ok for r in reports),
        "worst_time_rel_err": worst,
        "n_reports": len(reports),
        "n_bitwise_identical": n_bitwise,
        "asm_sweep_max_rel_diff": worst_sweep,
    }
    return rows


def codegen_rows(quick: bool = False):
    """RVV codegen rows: per-app emit wall-clock (jaxpr kernel spec ->
    generated assembly) and emit->decode round-trip verdicts vs the direct
    lowering (bitwise fingerprints + exact chunk counts).

    ``--quick`` round-trips at the grid extremes {8, 256}; the full run
    uses every MVL the ci.sh ``codegen-roundtrip`` gate enforces."""
    from repro.core import codegen, crossval, tracegen
    rows = []
    apps = [a for a in sorted(tracegen.APPS)
            if tracegen.APPS[a].kernel is not None]
    texts = {}
    for app in apps:
        t0 = time.perf_counter()
        texts[app] = codegen.emit_app(app)
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"codegen_emit_{app}", us,
                     f"{len(texts[app].splitlines())}lines"))
    mvls = (8, 256) if quick else None
    t0 = time.perf_counter()
    reports = []
    for app in apps:
        reports += crossval.round_trip_app(app, text=texts[app], mvls=mvls)
    wall = time.perf_counter() - t0
    for r in reports:
        rows.append((f"codegen_roundtrip_{r.app}_mvl{r.mvl}", 0.0,
                     f"{'bitwise' if r.fingerprint_eq else 'DIVERGED'}"
                     f"|{'ok' if r.ok else 'FAIL'}"))
    _BENCH["codegen"] = {
        "roundtrip_wall_s": wall,
        "all_ok": all(r.ok for r in reports),
        "n_reports": len(reports),
        "n_bitwise": sum(r.fingerprint_eq for r in reports),
        "emitted_lines": {a: len(t.splitlines()) for a, t in texts.items()},
    }
    return rows


def dse_study(quick: bool = False, cache_path: str | None = None,
              budget_kb: float = 512.0):
    """Design-space exploration acceptance rows: enumerate a DSE space
    (quick: the 384-point ``SPACE_QUICK``; full: the 1536-point
    ``SPACE_FULL`` over all 10 apps), shard the config axis across local
    devices, dedup dispatches through the persistent result cache, and
    reduce to per-app Pareto frontiers + best-config-under-budget.

    A repeated invocation with the same ``--dse-cache`` must report >=99%
    cache hits and an identical ``frontier_fingerprint`` in the bench JSON
    (the DSE determinism contract)."""
    from repro.configs import vector_engine as vcfg
    from repro.core import dse
    space = vcfg.SPACE_QUICK if quick else vcfg.SPACE_FULL
    apps = vcfg.SPACE_PRESET_APPS["quick" if quick else "full"]
    cache = dse.ResultCache(cache_path)
    t0 = time.perf_counter()
    res = dse.explore(space, apps, cache=cache)
    wall = time.perf_counter() - t0
    frontiers = res.frontiers()
    fp = dse._frontier_fingerprint(res)
    _BENCH["dse"] = {
        "space": res.space, "n_configs": res.n_configs,
        "apps": list(res.apps), "n_cells": len(res.records),
        "wall_s": wall, "cache": res.stats, "cache_path": cache_path,
        "frontier_fingerprint": fp,
        "frontiers": dse.frontier_summary(res, budgets=(256.0, budget_kb,
                                                        1024.0)),
    }
    rows = [(f"dse_{res.space}_{res.n_configs}cfg_{len(res.apps)}apps",
             wall * 1e6,
             f"wall_s={wall:.2f}|simulated={res.stats['simulated']}"
             f"|hit_rate={res.stats['hit_rate']:.3f}"
             f"|devices={res.stats['devices']}|frontier_fp={fp}")]
    by_app = res.by_app()
    for app in res.apps:
        best = dse.best_under_budget(by_app[app], budget_kb)
        rows.append((f"dse_frontier_{app}", 0.0,
                     f"{len(frontiers[app])}pts|best{budget_kb:g}kb="
                     f"{best.label if best else 'none'}"))
    return rows


def surrogate_rows(quick: bool = False, cache_path: str | None = None,
                   seed: int = 0):
    """Surrogate-guided search acceptance rows (ISSUE 8).

    Full mode: exhaustively explore the 1536-point ``SPACE_FULL`` over all
    10 apps (the truth frontiers AND the ~15k training rows), fit the MLP
    surrogate, then surrogate-search the 1,244,160-point ``SPACE_HUGE`` and
    measure (a) wall-clock vs the exact explore, (b) surrogate scoring
    throughput vs exact simulation throughput, and (c) recall of each
    exact-verified search frontier against the exhaustive truth frontier
    (acceptance: >= 0.9).  A second model trained WITHOUT the last app
    provides the honest held-out-app error CDF.  Quick mode: the same
    pipeline on SPACE_QUICK -> SPACE_10K with 3 apps.
    """
    from repro.configs import vector_engine as vcfg
    from repro.core import dse, surrogate, search, tracegen
    if quick:
        truth_space, search_space = vcfg.SPACE_QUICK, vcfg.SPACE_10K
        apps = vcfg.SPACE_PRESET_APPS["quick"]
        steps = 800
    else:
        truth_space, search_space = vcfg.SPACE_FULL, vcfg.SPACE_HUGE
        apps = tuple(sorted(tracegen.APPS))
        steps = 2000
    cache = dse.ResultCache(cache_path)

    t0 = time.perf_counter()
    truth = dse.explore(truth_space, apps, cache=cache)
    t_exact = time.perf_counter() - t0
    rows_lab = cache.export_training_rows(apps, truth_space)

    t0 = time.perf_counter()
    model = surrogate.fit(rows_lab, steps=steps, seed=seed)
    t_fit = time.perf_counter() - t0
    fit_card = surrogate.scorecard(model, rows_lab)

    # honest generalization: a second model that never saw the last app
    holdout = apps[-1]
    t0 = time.perf_counter()
    ho_model = surrogate.fit([r for r in rows_lab if r["app"] != holdout],
                             steps=steps, seed=seed)
    t_fit_ho = time.perf_counter() - t0
    ho_rows = [r for r in rows_lab if r["app"] == holdout]
    # the error CDF over ONLY the never-seen app's cells — the honest
    # unseen-workload generalization number
    ho_card = surrogate.scorecard(ho_model, ho_rows, holdout_app=holdout)

    # pure scoring throughput: one app across the whole search space
    scorer = surrogate.SpaceScorer(model, search_space, apps[0])
    idx = np.arange(search_space.size(), dtype=np.int64)
    scorer.score(idx[: surrogate.SCORE_BATCH])          # compile
    t0 = time.perf_counter()
    scorer.score(idx)
    t_score = time.perf_counter() - t0
    score_pts_s = search_space.size() / t_score
    exact_cells_s = len(truth.records) / t_exact

    t0 = time.perf_counter()
    res = search.search(search_space, apps, model, cache=cache, seed=seed)
    t_search = time.perf_counter() - t0
    n_checked = search._verify_exact(res, cache)

    tf = truth.frontiers()
    recall = {a: search.frontier_recall(res.frontiers[a], tf[a])
              for a in apps}
    rmean = float(np.mean(list(recall.values())))
    rmin = min(recall.values())
    t_pipeline = t_fit + t_search
    _BENCH["surrogate"] = {
        "truth_space": truth_space.name,
        "search_space": search_space.name,
        "search_space_size": search_space.size(),
        "apps": list(apps),
        "n_training_rows": len(rows_lab),
        "exact_wall_s": t_exact,
        "train_s": t_fit,
        "train_holdout_s": t_fit_ho,
        "search_wall_s": t_search,
        "pipeline_wall_s": t_pipeline,
        "score_throughput_pts_s": score_pts_s,
        "exact_throughput_cells_s": exact_cells_s,
        "recall_at_frontier": recall,
        "recall_mean": rmean,
        "recall_min": rmin,
        "frontier_points_exact_verified": n_checked,
        "frontier_fingerprint": search.frontier_fingerprint(res),
        "search_stats": res.stats,
        "fit_error_cdf": {k: fit_card[k] for k in
                          ("rel_err_p50", "rel_err_p90", "rel_err_p99",
                           "rel_err_max", "spearman_all")},
        "holdout_app": holdout,
        "holdout_error_cdf": {k: ho_card[k] for k in
                              ("rel_err_p50", "rel_err_p90", "rel_err_p99",
                               "rel_err_max", "spearman_all")},
    }
    rows = [
        (f"surrogate_train_{len(rows_lab)}rows", t_fit * 1e6,
         f"steps={steps}|final_loss={model.meta['final_loss']:.2e}"
         f"|p50={fit_card['rel_err_p50']:.4f}"
         f"|p90={fit_card['rel_err_p90']:.4f}"),
        (f"surrogate_score_{search_space.name}", t_score * 1e6,
         f"{score_pts_s:,.0f}pts/s_vs_exact_{exact_cells_s:.0f}cells/s"
         f"|x{score_pts_s / exact_cells_s:,.0f}"),
        (f"surrogate_search_{search_space.name}_{search_space.size()}cfg",
         t_search * 1e6,
         f"pipeline_s={t_pipeline:.1f}|exact_s={t_exact:.1f}"
         f"|scored={res.stats['n_scored']}|verified={n_checked}"),
        (f"surrogate_recall_{truth_space.name}_truth", 0.0,
         f"mean={rmean:.3f}|min={rmin:.3f}"
         f"|holdout_{holdout}_p50={ho_card['rel_err_p50']:.4f}"
         f"|holdout_spearman={ho_card['spearman_all']:.4f}"),
    ]
    return rows


def serve_rows(quick: bool = False, cache_path: str | None = None,
               seed: int = 0):
    """Simulation-service acceptance rows: sustained throughput and p50/p99
    latency under a (seeded) Poisson arrival workload with zero steady-state
    recompiles; the repeated identical stream must answer >= 99 % of
    requests from the ResultCache with bitwise-identical times."""
    try:
        from benchmarks import serve_bench
    except ImportError:
        import serve_bench
    rows, bench = serve_bench.serve_study(quick=quick, cache_path=cache_path,
                                          seed=seed)
    _BENCH["serve"] = bench
    return rows


# Pallas kernels that the TPU compiler accepts at these shapes; the other
# six (blackscholes, pathfinder, canneal, particlefilter, decode_attention,
# ssd_scan) are refused by Mosaic and are pinned as strict xfails in
# tests/test_tpu_compile.py.
def kernel_microbench():
    """Per-kernel device time of the Pallas kernels that compile for the
    TPU.  A kernel timing from the interpreter says nothing about the chip,
    so off the TPU this raises instead of falling back."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(f"kernel_microbench needs a TPU; backend is "
                           f"{backend!r}")
    from repro.kernels import ops
    k = jax.random.key
    rows = []
    a = jax.random.normal(k(5), (258, 512))
    us = _t(lambda x: ops.jacobi2d_step(x, rows_per_block=64), a)
    rows.append(("kernel_jacobi2d", us, f"{a.size/us:.0f}Melem_s"))
    p = jax.random.normal(k(7), (1024, 128))
    c = jax.random.normal(k(8), (512, 128))
    us = _t(ops.streamcluster_dist, p, c)
    gf = 2 * p.shape[0] * c.shape[0] * 128 / us / 1e3
    rows.append(("kernel_streamcluster_dist", us, f"{gf:.2f}GFLOP_s"))
    u = jax.random.uniform(k(9), (16384,), minval=1e-5, maxval=1 - 1e-5)
    us = _t(ops.cum_normal_inv, u)
    rows.append(("kernel_swaptions_cni", us, ""))
    q = jax.random.normal(k(15), (1, 512, 4, 64), jnp.float32)
    us = _t(lambda q: ops.flash_attention(q, q, q, bq=128, bk=128), q)
    rows.append(("kernel_flash_attention", us, ""))
    return rows


# Pre-PR-9 scalar baselines (ns): the retired SCALAR_BASELINE_MULT model's
# per-app runtimes, frozen so `--scalar` can report old-vs-new drift across
# the event-model replacement.
_OLD_SCALAR_NS = {
    "blackscholes": 7.857e9, "canneal": 6.160e9, "jacobi-2d": 7.835e9,
    "particlefilter": 2.172e9, "pathfinder": 7.115e9,
    "streamcluster": 3.999e10, "swaptions": 2.669e10,
    "flash_attention": 3.042e10, "decode_attention": 1.785e9,
    "ssd_scan": 2.475e8,
}


def scalar_rows():
    """Scalar-baseline rows: per-app old-vs-new runtime, the 11-anchor
    rel-err table, and the scorecard wall-clock."""
    from repro.core import engine as eng
    from repro.core import scalar_pipeline as sp
    from repro.core import suite, tracegen
    from repro.core.anchors import ANCHORS

    rows = []
    bench = _BENCH.setdefault("scalar", {})
    for app in sorted(_OLD_SCALAR_NS):
        t0 = time.perf_counter()
        new = sp.scalar_runtime_ns(app)
        us = (time.perf_counter() - t0) * 1e6
        old = _OLD_SCALAR_NS[app]
        prof = tracegen.scalar_profile_for(app)
        n = tracegen.app_for(app).counts(8).scalar_code_total \
            * prof.roi_instr_fraction
        cpi = sp.scalar_cycles(app) / n
        rows.append((f"scalar_baseline_{app}", us,
                     f"old={old:.4g}ns|new={new:.4g}ns|"
                     f"ratio={new / old:.4f}|cpi={cpi:.3f}"))
        bench[app] = {"old_ns": old, "new_ns": new, "cpi": cpi}

    t0 = time.perf_counter()
    anchor_rows = []
    for app, mvl, lanes, target, kind in ANCHORS:
        cfg = eng.VectorEngineConfig(mvl=mvl, lanes=lanes)
        got = suite.speedup(app, cfg)
        anchor_rows.append((f"scalar_anchor_{app}_mvl{mvl}_l{lanes}", 0.0,
                            f"model={got:.3f}|paper={target:.3f}|"
                            f"rel_err={got / target - 1.0:+.3f}|{kind}"))
        bench.setdefault("anchors", {})[f"{app}@{mvl}x{lanes}"] = {
            "model": got, "paper": target, "kind": kind}
    wall = time.perf_counter() - t0
    rows += anchor_rows
    rows.append(("scalar_scorecard_wallclock", wall * 1e6,
                 f"{len(anchor_rows)}_anchors"))
    bench["scorecard_wallclock_s"] = wall
    return rows


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: characterization + batched figures + "
                         "frontend cross-validation + a small batched-vs-"
                         "sequential sweep.  With --dse: the 384-point "
                         "SPACE_QUICK instead of the 1536-point SPACE_FULL")
    ap.add_argument("--dse", action="store_true",
                    help="design-space exploration rows only: enumerate the "
                         "DSE space, shard across devices, dedup through "
                         "--dse-cache, report Pareto frontiers + cache-hit "
                         "stats (a repeat run must be >=99%% hits with an "
                         "identical frontier fingerprint)")
    ap.add_argument("--scalar", action="store_true",
                    help="scalar-baseline rows only: per-app old-vs-new "
                         "runtime across the event-model replacement, the "
                         "11-anchor rel-err table, scorecard wall-clock")
    ap.add_argument("--rvv", action="store_true",
                    help="RVV assembly frontend rows only: per-app decode "
                         "wall-clock, asm-vs-hand cross-validation "
                         "verdicts, and asm-variant sweep parity")
    ap.add_argument("--profile", action="store_true",
                    help="mechanistic cycle-attribution rows only: the "
                         "telemetry scorecard (top bottleneck + module "
                         "fractions + event-sum identity error per app) and "
                         "the committed example Chrome-trace timeline "
                         "(examples/timeline_blackscholes.json)")
    ap.add_argument("--serve", action="store_true",
                    help="simulation-service rows only: Poisson arrival "
                         "workload through repro.serve.sim_service — "
                         "sustained throughput, p50/p99 latency, zero "
                         "steady-state recompiles; the repeat pass must be "
                         ">=99%% ResultCache hits, bitwise-identical")
    ap.add_argument("--surrogate", action="store_true",
                    help="surrogate-guided search rows only: exhaustive "
                         "truth explore (SPACE_QUICK/--quick or SPACE_FULL), "
                         "train the MLP cost model on the mined cache rows, "
                         "search SPACE_10K/SPACE_HUGE, report train "
                         "wall-clock, scoring throughput, recall@frontier "
                         "vs exhaustive truth, and the held-out-app error "
                         "CDF")
    ap.add_argument("--kernels", action="store_true",
                    help="Pallas kernel microbenchmark rows only (the four "
                         "kernels that compile for the TPU); needs a TPU")
    ap.add_argument("--dse-cache", default=os.path.join(
        os.path.dirname(__file__), "..", "results", "dse_cache.jsonl"),
        help="persistent DSE result cache (JSONL)")
    ap.add_argument("--surrogate-cache", default=os.path.join(
        os.path.dirname(__file__), "..", "results", "surrogate_cache.jsonl"),
        help="persistent result cache for the surrogate truth explore + "
             "exact re-simulation (JSONL)")
    ap.add_argument("--serve-cache", default=os.path.join(
        os.path.dirname(__file__), "..", "results", "serve_cache.jsonl"),
        help="persistent simulation-service result cache (JSONL)")
    ap.add_argument("--dse-budget-kb", type=float, default=512.0)
    ap.add_argument("--bench-json", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_pr10.json"),
        help="machine-readable results path (sweep wall-clock, batched "
             "speedup, per-app steady-state times + lane/VMU utilization, "
             "crossval verdicts incl. the RVV frontend, DSE frontiers + "
             "cache stats, serving throughput/latency, surrogate "
             "train/score/recall, scalar-baseline old-vs-new + anchor "
             "scorecard, mechanistic profile scorecard)")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    if args.kernels:
        fns = (kernel_microbench,)
    elif args.surrogate:
        fns = (lambda: surrogate_rows(quick=args.quick,
                                      cache_path=args.surrogate_cache),)
    elif args.dse:
        fns = (lambda: dse_study(quick=args.quick,
                                 cache_path=args.dse_cache,
                                 budget_kb=args.dse_budget_kb),)
    elif args.profile:
        fns = (lambda: profile_rows(quick=args.quick),)
    elif args.serve:
        fns = (lambda: serve_rows(quick=args.quick,
                                  cache_path=args.serve_cache),)
    elif args.rvv:
        fns = (lambda: rvv_rows(quick=args.quick),)
    elif args.scalar:
        fns = (scalar_rows,)
    elif args.quick:
        fns = (table_3_to_9_characterization, figures_4_to_10_scalability,
               sweep_llc, sweep_mshr, frontend_crossval,
               lambda: rvv_rows(quick=True),
               lambda: codegen_rows(quick=True), steady_state_table,
               scalar_rows, lambda: profile_rows(quick=True),
               lambda: sweep_wallclock(quick=True))
    else:
        fns = (table_3_to_9_characterization, figures_4_to_10_scalability,
               sweep_llc, sweep_mshr, frontend_crossval,
               lambda: rvv_rows(), lambda: codegen_rows(),
               steady_state_table, scalar_rows, lambda: profile_rows(),
               lambda: sweep_wallclock(quick=False))
    print("name,us_per_call,derived")
    for fn in fns:
        for name, us, derived in fn():
            print(f"{name},{us:.1f},{derived}")
    # Merge into an existing snapshot so single-mode runs (--scalar,
    # --surrogate, ...) layer their sections instead of clobbering the rest.
    merged = {}
    if os.path.exists(args.bench_json):
        try:
            with open(args.bench_json) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
    merged.update(_BENCH)
    with open(args.bench_json, "w") as f:
        json.dump(merged, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {os.path.normpath(args.bench_json)}")


if __name__ == "__main__":
    main()
