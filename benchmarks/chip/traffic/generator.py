"""Seeded open-loop request streams and their exact latency percentiles.

A copy, kept with the benchmark, of the simulation service's Poisson
workload (``repro.serve.sim_service.poisson_arrivals``) and its percentile
arithmetic, so that neither moves when the service does.  Departures keep
the work of every seed the same, so that a seed changes only the order:

- the stream holds exactly ``round(rate_hz * seconds)`` requests, and its
  gaps are the same for every seed: the quantiles of the exponential
  distribution at that rate, scaled to fill the window, in a seeded order;
- every app gets the same share of the requests, and with ``strata`` > 1
  every (app, stratum) pair does, where a stratum is one slice of the flat
  index (the values of the space's first, slowest axis);
- within its stratum a configuration is drawn uniformly by flat index.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int) -> np.random.Generator:
    """A generator for any whole-number seed, negative or past 32 bits."""
    return np.random.default_rng(seed % 2 ** 64)


def poisson_stream(seed: int, rate_hz: float, seconds: float, n_apps: int,
                   space_size: int, strata: int = 1):
    """``(t, app, flat)`` arrays: scheduled offsets (s) from the window's
    start, app indices and flat configuration indices."""
    if space_size % strata:
        raise ValueError(f"{strata} strata do not divide {space_size}")
    rng = rng_for(seed)
    n = max(1, int(round(rate_hz * seconds)))
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(q * (seconds / q.sum()))
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    k = rng.permutation(n)
    app = k % n_apps
    stratum = (k // n_apps) % strata
    width = space_size // strata
    flat = stratum * width + rng.integers(0, width, n)
    return t, app, flat


def percentile(values, q: float) -> float:
    """Exact ``q``-th percentile of the records (linear interpolation
    between the two nearest ranks, as ``numpy.percentile``)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("no records")
    pos = (v.size - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, v.size - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))
