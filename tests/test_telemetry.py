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


@pytest.mark.parametrize("app", sorted(tracegen.APPS))
def test_dep_scalar_attribution_matches_table2(app):
    """Coupling cycles (dep_scalar) surface for exactly the scalar-
    communication apps of the paper's Table 2."""
    scalar_comm = {"canneal", "particlefilter", "streamcluster",
                   "flash_attention", "decode_attention"}
    body = tracegen.body_for(app, suite.effective_mvl(app, CFG_REF), CFG_REF)
    prof = eng.simulate(body.tile(8), CFG_REF, collect_stats=True)
    has = prof["stalls"]["dep_scalar"] > 0
    assert has == (app in scalar_comm), (app, prof["stalls"]["dep_scalar"])


# --------------------------------------------------------------------------
# host spans and counters (repro.core.registry, re-exported by telemetry)
# --------------------------------------------------------------------------
def _busy(seconds):
    import time
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def test_spans_nest_and_self_time_excludes_children():
    reg = telemetry.Registry()
    with reg.span("outer") as outer:
        _busy(0.01)
        with reg.span("inner") as inner:
            _busy(0.02)
        with reg.span("inner"):
            pass
    t = reg.totals()["spans"]
    assert t["outer"]["calls"] == 1 and t["inner"]["calls"] == 2
    assert t["outer"]["total_s"] == outer.wall_s
    assert t["inner"]["total_s"] >= inner.wall_s >= 0.02
    assert t["inner"]["self_s"] == t["inner"]["total_s"]   # no children
    assert t["outer"]["self_s"] == pytest.approx(
        t["outer"]["total_s"] - t["inner"]["total_s"], abs=1e-12)
    assert 0.01 <= t["outer"]["self_s"] < t["outer"]["total_s"]


def test_span_still_counts_when_its_block_raises():
    reg = telemetry.Registry()
    with pytest.raises(KeyError):
        with reg.span("fails"):
            raise KeyError("x")
    with reg.span("after"):
        pass
    t = reg.totals()["spans"]
    assert t["fails"]["calls"] == 1
    assert t["after"]["self_s"] == t["after"]["total_s"]  # stack unwound


def test_totals_since_and_recent_rows():
    reg = telemetry.Registry(keep=3)
    reg.count("a", 2)
    before = reg.totals()
    assert before["schema"] == telemetry.SCHEMA
    assert before["kind"] == "telemetry.totals"
    json.dumps(before)
    reg.count("a", 3)
    reg.count("b")
    with reg.span("s"):
        pass
    d = reg.since(before)
    assert d["counters"] == {"a": 3, "b": 1}
    assert set(d["spans"]) == {"s"} and d["spans"]["s"]["calls"] == 1
    assert reg.since(reg.totals()) == telemetry.snapshot_row(
        "telemetry.totals", spans={}, counters={})
    for i in range(5):
        reg.record(telemetry.snapshot_row("x.row", i=i))
    reg.record(telemetry.snapshot_row("y.row", i=9))
    assert [r["i"] for r in reg.recent("x.row")] == [3, 4]   # bounded
    assert [r["i"] for r in reg.recent("y.row")] == [9]


def test_two_threads_keep_their_own_span_stacks():
    """Spans on two threads at once: no count is lost, and a span on one
    thread never becomes a child of a span on the other."""
    import sys
    import threading

    reg = telemetry.Registry()
    n = 200
    barrier = threading.Barrier(2, timeout=30)

    def work(name):
        barrier.wait()
        for _ in range(n):
            with reg.span(name):
                with reg.span(name + ".child"):
                    reg.count("ticks")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in ("t1", "t2")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    tot = reg.totals()
    assert tot["counters"]["ticks"] == 2 * n
    for k in ("t1", "t2"):
        s, c = tot["spans"][k], tot["spans"][k + ".child"]
        assert s["calls"] == c["calls"] == n
        assert s["self_s"] == pytest.approx(s["total_s"] - c["total_s"],
                                            abs=1e-9)


def test_module_registry_is_the_engines():
    """telemetry's span/count/totals are the process registry the engine
    and dse.explore write to."""
    before = telemetry.totals()
    body = tracegen.body_for("pathfinder", 64, CFG_REF)
    eng.steady_state_time_batch([body] * 3, [CFG_REF])
    d = telemetry.since(before)
    assert {"engine.build", "engine.stack", "engine.copy", "engine.launch",
            "engine.wait", "engine.readback"} <= set(d["spans"])
    c = d["counters"]
    length = eng.trace_len_bucket(
        eng.trace_len_bucket(8 * len(body)) + 24 * len(body))
    assert c["engine.launches"] == length // eng.CHUNK
    assert c["engine.lane_steps_scanned"] == eng.batch_bucket(3) * length
    assert c["engine.lane_steps_batch_pad"] == (eng.batch_bucket(3) - 3) \
        * length
    assert c["engine.lane_steps_real"] == 3 * 32 * len(body)


def test_jit_cache_size_counts_what_the_jit_caches_hold():
    """The public trace counter moves exactly as the jitted programs' own
    cache sizes do, over new batch and row buckets, repeats and the
    sequential, profiling and gather programs."""
    def cache_sizes():
        n = (eng._simulate_jit._cache_size()
             + eng._chunk_batch_jit._cache_size()
             + eng._profile_jit._cache_size()
             + eng._gather_jit._cache_size())
        return n + sum(p.scan._cache_size() + p.gather._cache_size()
                       for p in eng._SHARDED_JITS.values())

    body = tracegen.body_for("jacobi-2d", 64, CFG_REF)
    steps = [lambda b=b: eng.steady_state_time_batch([body] * b, [CFG_REF])
             for b in (1, 3, 9, 20, 40, 9, 1)]
    # distinct body objects: gathers at row buckets above the first
    steps += [lambda n=n: eng.steady_state_time_batch(
        [body.tile(1) for _ in range(n)], [CFG_REF]) for n in (20, 9, 20)]
    steps += [lambda: eng.warm_gather(16)]
    steps += [lambda: eng.simulate(body.tile(3), CFG_REF),
              lambda: eng.simulate(body.tile(3), CFG_CORNER),
              lambda: eng.simulate(body.tile(5), CFG_REF,
                                   collect_stats=True)]
    for step in steps:
        n0, c0 = eng.jit_cache_size(), cache_sizes()
        step()
        assert eng.jit_cache_size() - n0 == cache_sizes() - c0


def test_trace_rows_count_distinct_objects_per_group():
    """``engine.trace_rows`` adds, per batch group, the distinct trace
    objects among its lanes: shared objects count once, content-equal
    copies count apart, padding lanes add none."""
    short = tracegen.body_for("pathfinder", 64, CFG_REF)
    copy = short.tile(1)
    long = tracegen.body_for("particlefilter", 64, CFG_REF).tile(2)
    traces = [short, long, short, copy, long, short]
    assert len({eng.trace_len_bucket(len(t)) for t in traces}) == 2
    before = telemetry.totals()
    eng.simulate_batch(traces, [CFG_REF])
    assert telemetry.since(before)["counters"]["engine.trace_rows"] == 2 + 1


def test_gather_span_runs_once_per_chunk():
    body = tracegen.body_for("blackscholes", 64, CFG_REF)
    before = telemetry.totals()
    eng.steady_state_time_batch([body, body, body.tile(1)],
                                [CFG_REF, CFG_CORNER, CFG_REF])
    d = telemetry.since(before)
    length = eng.trace_len_bucket(
        eng.trace_len_bucket(8 * len(body)) + 24 * len(body))
    assert d["spans"]["engine.gather"]["calls"] == length // eng.CHUNK \
        == d["counters"]["engine.launches"]
    assert d["counters"]["engine.trace_rows"] == 2
