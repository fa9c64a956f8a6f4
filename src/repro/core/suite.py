"""RiVec suite timing API: end-to-end modeled runtimes and speedups (§5).

``speedup(app, cfg)`` reproduces the paper's Figures 4-10 quantity: scalar
runtime / vectorized runtime on a given vector-engine configuration.  The
scalar side is the event-based dual-issue in-order pipeline model
(``repro.core.scalar_pipeline``, §3.1) driven by the config's scalar-core
knobs; the vector side is ``chunks x steady-state(loop body)`` from the
cycle-level engine.

A compute-bound app beats the scalar core and an LLC upgrade helps the
memory-stressed ones (docs/calibration.md has the full fidelity table):

>>> from repro.core import engine as eng
>>> speedup("blackscholes", eng.VectorEngineConfig(mvl=64, lanes=8)) > 2.0
True
>>> small = speedup("streamcluster", eng.VectorEngineConfig(mvl=64, lanes=4))
>>> big = speedup("streamcluster",
...               eng.VectorEngineConfig(mvl=64, lanes=4, l2_kb=1024))
>>> big > small
True
"""
from __future__ import annotations

from repro.core import engine as eng
from repro.core import tracegen

from repro.core import scalar_pipeline as _sp


def effective_mvl(app_name: str, cfg: eng.VectorEngineConfig) -> int:
    """The MVL a body actually runs at: the configured MVL clamped to the
    app's largest requested VL.  Both the loop-body trace and the chunk
    count use this one value (they previously disagreed: bodies were built
    at the raw ``cfg.mvl`` while ``chunks`` clamped)."""
    return min(cfg.mvl, tracegen.app_for(app_name).max_vl)


def scalar_runtime_ns(app_name: str,
                      cfg: eng.VectorEngineConfig | None = None) -> float:
    """Modeled scalar-version runtime (ns) from the event-based scalar
    pipeline model (``repro.core.scalar_pipeline``): per-instruction-class
    issue/RAW/branch/structural/memory events on the config's scalar core
    (``None``: the default 2 GHz dual-issue core).  Trace-source variants
    (``"<app>:asm"``) share the base app's scalar baseline — the scalar
    version of the program is the same either way."""
    return _sp.scalar_runtime_ns(app_name, cfg)


def vector_runtime_from_per_chunk(app_name: str, cfg: eng.VectorEngineConfig,
                                  body, per_chunk: float) -> float:
    """Whole-app modeled vector runtime from one cached/steady per-chunk time:
    ``chunks x per_chunk`` plus the residual (non-amortized) scalar work.

    This is the derivation half of the suite's timing pipeline — pure
    arithmetic over the (app, cfg, body) cell, shared by ``speedup_batch``,
    ``dse.explore`` and the simulation service so cached and simulated
    answers agree bitwise.
    """
    app = tracegen.app_for(app_name)
    mvl = effective_mvl(app_name, cfg)
    chunks = tracegen.chunks_for(app_name, mvl, cfg)
    # counts at the *effective* MVL — body_for/chunks_for clamp to the app's
    # max VL, so the residual derivation must too (cfg.mvl here made the
    # residual inconsistent whenever cfg.mvl > app.max_vl)
    counts = app.counts(mvl)
    # residual scalar work not amortized per chunk (s0-like constant part)
    per_chunk_scalar = sum(body.scalar_count)  # instrs already inside the body
    residual = max(counts.scalar_instrs - per_chunk_scalar * chunks, 0.0)
    # ns per residual instruction on the config's scalar core:
    # cycles / scalar clock / issue width (0.25 on the default 2 GHz
    # dual-issue core)
    res_scale = 1.0 / (cfg.scalar_freq_ghz * cfg.issue_width)
    return float(chunks * per_chunk
                 + residual * eng.SCALAR_CYCLES[0] * res_scale)


# back-compat alias (pre-PR-6 name)
_vector_runtime_from_per_chunk = vector_runtime_from_per_chunk


def vector_runtime_ns(app_name: str, cfg: eng.VectorEngineConfig) -> float:
    body = tracegen.body_for(app_name, effective_mvl(app_name, cfg), cfg)
    per_chunk = eng.steady_state_time(body, cfg)
    return vector_runtime_from_per_chunk(app_name, cfg, body, per_chunk)


def speedup(app_name: str, cfg: eng.VectorEngineConfig) -> float:
    return scalar_runtime_ns(app_name, cfg) / vector_runtime_ns(app_name, cfg)


def speedup_batch(pairs: list[tuple[str, eng.VectorEngineConfig]]) -> list[float]:
    """Speedups for N (app, config) pairs via the batched engine: the whole
    list is two ``simulate_batch`` calls (a handful of XLA dispatches),
    not 2N sequential simulations.  The scalar side is per-pair (the
    config's scalar-core knobs matter) but memoized per (app, scalar knobs),
    so a sweep over vector-side knobs still computes each scalar runtime
    once."""
    bodies = [tracegen.body_for(a, effective_mvl(a, c), c) for a, c in pairs]
    per_chunk = eng.steady_state_time_batch(bodies, [c for _, c in pairs])
    return [scalar_runtime_ns(a, c) / vector_runtime_from_per_chunk(a, c, b, pc)
            for (a, c), b, pc in zip(pairs, bodies, per_chunk)]


def speedup_util_batch(
        pairs: list[tuple[str, eng.VectorEngineConfig]]) -> list[dict]:
    """``speedup_batch`` plus the lane/VMU utilization the engine carry was
    already accumulating (and every caller used to drop): one row dict per
    pair with ``speedup``, ``lane_util``, ``vmu_util``.  Utilization is
    marginal over the steady-state measurement window, read from the same
    fused scan — the speedups are bitwise-identical to ``speedup_batch``.

    >>> r = speedup_util_batch(
    ...     [("blackscholes", eng.VectorEngineConfig(mvl=64, lanes=4))])[0]
    >>> sorted(r) == ['lane_util', 'speedup', 'vmu_util']
    True
    >>> 0.0 <= r["vmu_util"] <= 1.0 and r["lane_util"] > 0.1
    True
    """
    bodies = [tracegen.body_for(a, effective_mvl(a, c), c) for a, c in pairs]
    rows = eng.steady_state_time_batch(bodies, [c for _, c in pairs],
                                       with_util=True)
    return [{
        "speedup": scalar_runtime_ns(a, c) / vector_runtime_from_per_chunk(
            a, c, b, r["steady_ns"]),
        "lane_util": r["lane_util"],
        "vmu_util": r["vmu_util"],
    } for (a, c), b, r in zip(pairs, bodies, rows)]


def sweep(app_name: str, mvls=(8, 16, 32, 64, 128, 256), lanes=(1, 2, 4, 8),
          utilization: bool = False, **overrides) -> dict:
    """The paper's 24-configuration sweep (Table 10), batched.

    Cell values are speedups; with ``utilization=True`` each cell is instead
    a row dict ``{"speedup", "lane_util", "vmu_util"}`` (same speedups —
    the utilization columns ride the same fused scan)."""
    grid = [(m, l) for m in mvls for l in lanes]
    pairs = [(app_name, eng.VectorEngineConfig(mvl=m, lanes=l, **overrides))
             for m, l in grid]
    vals = speedup_util_batch(pairs) if utilization else speedup_batch(pairs)
    return dict(zip(grid, vals))


def sweep_all(apps=None, mvls=(8, 16, 32, 64, 128, 256), lanes=(1, 2, 4, 8),
              utilization: bool = False, **overrides) -> dict:
    """Full paper study — every app x the 24-config grid — in one batch."""
    apps = list(apps) if apps is not None else sorted(tracegen.APPS)
    grid = [(m, l) for m in mvls for l in lanes]
    pairs = [(a, eng.VectorEngineConfig(mvl=m, lanes=l, **overrides))
             for a in apps for m, l in grid]
    flat = speedup_util_batch(pairs) if utilization else speedup_batch(pairs)
    return {a: dict(zip(grid, flat[i * len(grid):(i + 1) * len(grid)]))
            for i, a in enumerate(apps)}


def dse_explore(space, apps=None, cache=None, warmup: int = 8,
                measure: int = 24):
    """Design-space exploration over the suite: evaluate ``apps`` (default:
    all 10) on every config of ``space``, sharded across devices and deduped
    through ``cache`` — ``repro.core.dse.explore`` with the suite's timing
    pipeline.  Returns a ``dse.DseResult``; ``.frontiers()`` gives the
    per-app Pareto frontier (runtime vs. area proxy)."""
    from repro.core import dse
    return dse.explore(space, apps=apps, cache=cache, warmup=warmup,
                       measure=measure)


def dse_best_under_budget(space, budget_kb: float, apps=None,
                          cache=None) -> dict:
    """Per-app "best config under an area budget" report: the fastest
    explored config whose ``dse.area_proxy_kb`` fits ``budget_kb``
    (``None`` when nothing fits)."""
    from repro.core import dse
    res = dse.explore(space, apps=apps, cache=cache)
    return {a: dse.best_under_budget(recs, budget_kb)
            for a, recs in res.by_app().items()}
