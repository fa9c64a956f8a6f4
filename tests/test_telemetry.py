"""Mechanistic cycle-attribution profiler contracts (ISSUE 10).

Three load-bearing guarantees:

  * the profiling scan is the default scan: every shared metric is
    bitwise-identical with and without ``collect_stats`` on random traces
    and configs (the attribution reads the step's intermediates, it never
    rewrites them),
  * the event-sum identity: the attributed cycles over ``STALL_KINDS``
    reconstruct the total runtime to float32 association tolerance on every
    app at a config sample (nothing double-counted, nothing dropped),
  * cost containment: turning profiling on adds at most one jit executable
    per trace shape (the single ``_profile_jit`` key).

Plus schema/scorecard/timeline/histogram/utilization sanity for the
telemetry layer itself.
"""
import json

import numpy as np
import pytest

try:  # hypothesis is optional (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover
    from repro.testing.hypothesis_shim import given, settings, strategies as st

from repro.core import engine as eng
from repro.core import suite, telemetry, tracegen
from test_properties import random_config, random_trace

seeds = st.integers(min_value=0, max_value=10 ** 9)

CFG_REF = eng.VectorEngineConfig(mvl=64, lanes=4)
CFG_CORNER = eng.VectorEngineConfig(mvl=256, lanes=8, ooo_issue=True,
                                    interconnect="crossbar")


# --------------------------------------------------------------------------
# contract 1: the default path is untouched
# --------------------------------------------------------------------------
@settings(max_examples=8, deadline=None, derandomize=True)
@given(seeds)
def test_collect_stats_timing_bitwise(seed):
    """simulate(collect_stats=True) returns the exact default metrics —
    bitwise — on random traces and random configs."""
    tr, cfg = random_trace(seed), random_config(seed)
    base = eng.simulate(tr, cfg)
    prof = eng.simulate(tr, cfg, collect_stats=True)
    for k, v in base.items():
        assert prof[k] == v, (k, v, prof[k])


# --------------------------------------------------------------------------
# contract 2: event-sum identity across the whole suite
# --------------------------------------------------------------------------
@pytest.mark.parametrize("app", sorted(tracegen.APPS))
def test_event_sum_identity(app):
    """sum(stalls) == time to float32 tolerance, every app, both the
    reference config and the ooo/crossbar corner."""
    for cfg in (CFG_REF, CFG_CORNER):
        body = tracegen.body_for(app, suite.effective_mvl(app, cfg), cfg)
        prof = eng.simulate(body.tile(8), cfg, collect_stats=True)
        total = sum(prof["stalls"].values())
        assert abs(total - prof["time"]) <= 1e-4 * prof["time"], (
            app, cfg.label(), total, prof["time"])
        assert all(v >= 0.0 for v in prof["stalls"].values())


def test_records_timeline_sane():
    body = tracegen.body_for("blackscholes", 64, CFG_REF)
    prof = eng.simulate(body.tile(4), CFG_REF, collect_stats=True)
    rec = prof["records"]
    n = len(body.tile(4))
    assert all(rec[k].shape == (n,) for k in ("start", "issue", "complete"))
    assert np.all(rec["issue"] <= rec["complete"] + 1e-6)
    assert np.all(rec["complete"] <= prof["time"] + 1e-6)
    assert rec["cause"].min() >= 0 and rec["cause"].max() < eng.N_STALL


# --------------------------------------------------------------------------
# contract 3: one extra executable per trace shape
# --------------------------------------------------------------------------
def test_profiling_adds_at_most_one_executable():
    tr = random_trace(12345)
    cfg_a, cfg_b = random_config(1), random_config(2)
    eng.simulate(tr, cfg_a)                     # warm the default key
    n0 = eng.jit_cache_size()
    eng.simulate(tr, cfg_a, collect_stats=True)
    eng.simulate(tr, cfg_b, collect_stats=True)  # flags are traced args
    assert eng.jit_cache_size() - n0 <= 1


# --------------------------------------------------------------------------
# telemetry layer: schema, rollup, scorecard, timeline, histogram
# --------------------------------------------------------------------------
def test_schema_envelope():
    row = telemetry.snapshot_row("x.y", a=1)
    assert row["schema"] == telemetry.SCHEMA
    assert row["kind"] == "x.y" and row["a"] == 1


def test_module_rollup_total():
    """Every stall kind maps to exactly one module and the module fractions
    sum to ~1 (they partition the event-sum identity)."""
    assert set(telemetry._KIND_TO_MODULE) == set(eng.STALL_KINDS)
    for app in ("blackscholes", "canneal"):
        r = telemetry.profile_app(app, CFG_REF, tiles=8)
        assert r["kind"] == "engine.profile"
        assert abs(sum(r["modules"].values()) - 1.0) < 1e-3
        assert r["top"] in telemetry.MODULES
        assert r["identity_rel_err"] < 1e-4


def test_scorecard_roundtrip():
    rep = telemetry.scorecard(apps=["blackscholes", "pathfinder"],
                              cfgs=[CFG_REF], tiles=4)
    doc = json.loads(rep.to_json())
    assert doc["schema"] == telemetry.SCHEMA and len(doc["rows"]) == 2
    assert "blackscholes" in rep.table()
    assert set(rep.by_app()) == {"blackscholes", "pathfinder"}


def test_chrome_trace_valid(tmp_path):
    body = tracegen.body_for("jacobi-2d", 64, CFG_REF)
    path = tmp_path / "timeline.json"
    doc = telemetry.write_chrome_trace(str(path), body.tile(2), CFG_REF,
                                       label="jacobi-2d")
    on_disk = json.loads(path.read_text())
    assert on_disk == doc
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" for e in evs)
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans, "no complete-event spans"
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["tid"] in (0, 1, 2)
    assert any(e["name"].startswith("stall:") for e in spans)


def test_latency_histogram():
    h = telemetry.LatencyHistogram()
    for v in (2e-6, 5e-5, 1e-3, 1e-3, 2.0):
        h.add(v)
    assert h.count == 5
    p50 = h.percentile(0.5)
    assert 5e-5 <= p50 <= 2e-3
    assert h.percentile(1.0) >= h.percentile(0.5) >= h.percentile(0.0)
    d = h.to_dict()
    assert d["kind"] == "latency.hist" and d["count"] == 5
    # per-window deltas: since() only sees what was added after snapshot()
    snap = h.snapshot()
    h.add(1e-2)
    delta = h.since(snap)
    assert delta.count == 1
    assert abs(delta.percentile(0.5) - 1e-2) / 1e-2 < 0.2
    # out-of-range values land in the clamp bins, not off the end
    h.add(1e-9), h.add(1e6)
    assert h.count == 8


def test_sweep_utilization_columns():
    """suite.sweep(utilization=True) rides the same fused scan: speedups
    bitwise-equal to the default sweep, utilizations physically sane."""
    mvls, lanes = (8, 64), (1, 4)
    plain = suite.sweep("blackscholes", mvls=mvls, lanes=lanes)
    rich = suite.sweep("blackscholes", mvls=mvls, lanes=lanes,
                       utilization=True)
    for cell, row in rich.items():
        assert row["speedup"] == plain[cell]
        assert 0.0 <= row["lane_util"] <= 1.0 + 1e-6
        assert 0.0 <= row["vmu_util"] <= 1.0 + 1e-6
    # 1 lane saturates on a compute-heavy body; 4 lanes has more headroom
    assert rich[(64, 1)]["lane_util"] >= rich[(64, 4)]["lane_util"] - 1e-6


def test_steady_state_with_util():
    body = tracegen.body_for("blackscholes", 64, CFG_REF)
    plain = eng.steady_state_time_batch([body], [CFG_REF])
    rich = eng.steady_state_time_batch([body], [CFG_REF], with_util=True)
    assert rich[0]["steady_ns"] == plain[0]
    assert 0.0 <= rich[0]["lane_util"] <= 1.0 + 1e-6
    assert 0.0 <= rich[0]["vmu_util"] <= 1.0 + 1e-6


def test_dep_scalar_attribution_matches_table2():
    """Coupling cycles (dep_scalar) surface for exactly the scalar-
    communication apps of the paper's Table 2."""
    scalar_comm = {"canneal", "particlefilter", "streamcluster",
                   "flash_attention", "decode_attention"}
    for app in sorted(tracegen.APPS):
        body = tracegen.body_for(app, suite.effective_mvl(app, CFG_REF),
                                 CFG_REF)
        prof = eng.simulate(body.tile(8), CFG_REF, collect_stats=True)
        has = prof["stalls"]["dep_scalar"] > 0
        assert has == (app in scalar_comm), (app, prof["stalls"]["dep_scalar"])
