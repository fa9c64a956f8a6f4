"""The comparison that decides ``correct``.

Each answer of the window, a cell's steady-state loop-body time, whole-app
runtime and speedup, is compared with the plain reference's answer for the
same cell.  The numbers compared are the widest relative gap of each
quantity over the answers compared, and the count of requested cells that
got no answer; each has its limit in the configuration's file.  A run
compares every answer of up to ``SAMPLE`` distinct cells, drawn from the
seed, and of all of them where the window answered no more.
"""
from __future__ import annotations

import math

import numpy as np

QUANTITIES = ("steady_gap", "runtime_gap", "speedup_gap")
SAMPLE = 512     # distinct cells compared per run


def sample(keys, app_of, seed: int, n: int = SAMPLE) -> list:
    """Up to ``n`` of the distinct answered ``keys``, drawn from the seed
    with every app in equal measure, so the longest bodies are always in
    it; all of them where there are no more."""
    keys = sorted(set(keys))
    if len(keys) <= n:
        return keys
    rng = np.random.default_rng((seed + 1) % 2 ** 64)
    by_app: dict = {}
    for k in keys:
        by_app.setdefault(app_of(k), []).append(k)
    pools = [list(rng.permutation(len(v))) for v in by_app.values()]
    lists = list(by_app.values())
    out = []
    while len(out) < n:
        for pool, ks in zip(pools, lists):
            if pool and len(out) < n:
                out.append(ks[pool.pop()])
    return sorted(out)


def widest_gap(got, want) -> float:
    """max |got - want| / |want|; a non-finite answer reads infinite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return math.inf
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(got - want) / np.abs(want)
    gap = np.where(got == want, 0.0, gap)
    gap = np.where(np.isfinite(got) & np.isfinite(gap), gap, math.inf)
    return float(gap.max())


def check(values, ref, missing: int, limits: dict) -> dict:
    """``{name: (reading, limit)}``: ``values`` is ``[n, 3]`` of the
    program's answers, ``ref`` the reference's three arrays in the same
    order."""
    values = np.asarray(values, np.float64).reshape(-1, 3)
    out = {name: (widest_gap(values[:, i], ref[i]), limits[name])
           for i, name in enumerate(QUANTITIES)}
    out["missing"] = (missing, limits["missing"])
    return out


def passed(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())


def to_json(checks: dict) -> dict:
    return {name: {"value": v if math.isfinite(v) else str(v), "limit": lim}
            for name, (v, lim) in checks.items()}
