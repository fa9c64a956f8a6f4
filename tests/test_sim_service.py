"""Simulation-as-a-service behaviour: hit/cold/coalesce paths, bounded-queue
degradation, Poisson workloads, and the bitwise-equivalence contract against
the batched engine."""
import json
import math

import numpy as np
import pytest

from repro.core import dse
from repro.core import engine as eng
from repro.core import isa, suite, telemetry, tracegen
from repro.serve.sim_service import (
    SimService, poisson_arrivals, run_workload)

CFG_A = eng.VectorEngineConfig(mvl=64, lanes=4)
CFG_B = eng.VectorEngineConfig(mvl=16, lanes=2, mshrs=1)


# ----------------------------------------------------------- serving paths

def test_cold_path_is_bitwise_the_batched_engine():
    svc = SimService()
    svc.submit("blackscholes", CFG_A)
    svc.submit("canneal", CFG_B)
    svc.drain()
    direct = {}
    for app, cfg in (("blackscholes", CFG_A), ("canneal", CFG_B)):
        body = tracegen.body_for(app, suite.effective_mvl(app, cfg), cfg)
        direct[app] = eng.steady_state_time_batch([body], [cfg])[0]
    by_app = {r.app: r for r in svc.completed}
    assert by_app["blackscholes"].steady_ns == direct["blackscholes"]
    assert by_app["canneal"].steady_ns == direct["canneal"]
    # derived quantities agree with the suite pipeline bitwise
    for app, cfg in (("blackscholes", CFG_A), ("canneal", CFG_B)):
        body = tracegen.body_for(app, suite.effective_mvl(app, cfg), cfg)
        want = suite.vector_runtime_from_per_chunk(app, cfg, body,
                                                   direct[app])
        assert by_app[app].runtime_ns == want
        assert by_app[app].speedup == suite.scalar_runtime_ns(app, cfg) / want


def test_hit_path_answers_without_dispatch_and_bitwise():
    svc = SimService()
    svc.submit("blackscholes", CFG_A)
    svc.drain()
    cold = svc.completed[0]
    n_batches = svc.n_batches
    hit = svc.submit("blackscholes", CFG_A)
    assert hit is not None and hit.source == "cache"
    assert hit.steady_ns == cold.steady_ns
    assert hit.runtime_ns == cold.runtime_ns
    assert svc.n_batches == n_batches           # no dispatch on the hit path


def test_identical_cold_requests_coalesce_into_one_dispatch():
    svc = SimService()
    for _ in range(4):
        svc.submit("blackscholes", CFG_A)
    assert svc.pending_requests() == 4
    svc.drain()
    assert svc.n_dispatched == 1
    assert svc.n_coalesced == 3
    vals = {r.steady_ns for r in svc.completed}
    assert len(vals) == 1                       # all riders, one answer
    sources = sorted(r.source for r in svc.completed)
    assert sources == ["batched", "coalesced", "coalesced", "coalesced"]


def test_mvl_alias_configs_share_a_cell():
    # streamcluster caps at max_vl=128: mvl=128 and mvl=256 produce the same
    # clamped body and timing params, so the second request coalesces onto
    # the first (canneal would NOT alias — its body reads cfg.mvl directly)
    svc = SimService()
    svc.submit("streamcluster", eng.VectorEngineConfig(mvl=128, lanes=4))
    svc.submit("streamcluster", eng.VectorEngineConfig(mvl=256, lanes=4))
    assert svc.pending_requests() == 2
    svc.drain()
    assert svc.n_dispatched == 1 and svc.n_coalesced == 1
    a, b = svc.completed
    assert a.steady_ns == b.steady_ns


def test_asm_variant_and_kernel_trace_requests():
    svc = SimService()
    svc.submit("pathfinder:asm", CFG_A)
    body = tracegen.body_for("blackscholes",
                             suite.effective_mvl("blackscholes", CFG_A),
                             CFG_A)
    svc.submit(body, CFG_A)                     # raw kernel trace
    svc.drain()
    by_src = {r.app: r for r in svc.completed}
    asm = by_src["pathfinder:asm"]
    assert asm.steady_ns > 0 and np.isfinite(asm.runtime_ns)
    (kernel,) = [r for r in svc.completed if r.app.startswith("kernel:")]
    assert kernel.steady_ns > 0
    assert math.isnan(kernel.runtime_ns) and math.isnan(kernel.speedup)
    # the raw trace IS blackscholes' body, so the cells dedup via the key
    hit = svc.submit("blackscholes", CFG_A)
    assert hit is not None and hit.source == "cache"
    assert hit.steady_ns == kernel.steady_ns


def test_batch_fills_trigger_dispatch_without_flush():
    svc = SimService(max_batch=2)
    svc.submit("blackscholes", CFG_A)
    assert svc.n_batches == 0
    svc.submit("canneal", CFG_A)                # fills the batch
    assert svc.n_batches == 1 and svc.pending_requests() == 0
    assert len(svc.completed) == 2


def test_queue_wait_runs_from_enqueue_to_the_flush():
    """Each dispatched cell's wait from its enqueue to the start of its
    flush is summed in stats() and in the process counters; the flush is a
    span, with the engine's spans inside it."""
    t = [100.0]
    svc = SimService(max_batch=8, clock=lambda: t[0])
    before = telemetry.totals()
    svc.submit("blackscholes", CFG_A, now=100.0)
    svc.submit("canneal", CFG_B, now=100.5)
    svc.submit("canneal", CFG_B, now=100.7)     # rides the queued cell
    t[0] = 101.0
    assert svc.flush() == 2
    s = svc.stats()
    assert s["queued_cells"] == 2
    assert s["queue_wait_s"] == pytest.approx(1.5)
    d = telemetry.since(before)
    assert d["counters"]["serve.queued_cells"] == 2
    assert d["counters"]["serve.queue_wait_s"] == pytest.approx(1.5)
    flush = d["spans"]["serve.flush"]
    assert flush["calls"] == 1 and flush["self_s"] < flush["total_s"]
    assert "engine.launch" in d["spans"]
    svc.submit("jacobi-2d", CFG_A, now=102.0)
    svc.flush(now=102.25)
    assert svc.stats()["queue_wait_s"] == pytest.approx(1.75)


# ----------------------------------------------------- bounded queue limits

def test_bounded_queue_shed_policy():
    svc = SimService(max_queue=2, overflow="shed", max_batch=64)
    apps = ["blackscholes", "canneal", "jacobi-2d", "pathfinder"]
    results = [svc.submit(a, CFG_A) for a in apps]
    assert results[0] is None and results[1] is None
    assert results[2] is not None and results[2].source == "shed"
    assert math.isnan(results[2].steady_ns)
    assert svc.n_shed == 2
    svc.drain()
    assert len(svc.completed) == 2              # shed ones never dispatched
    assert svc.result_for(results[2].uid).source == "shed"


def test_bounded_queue_serialize_policy_never_loses_requests():
    svc = SimService(max_queue=2, overflow="serialize", max_batch=64)
    for a in ["blackscholes", "canneal", "jacobi-2d", "pathfinder"]:
        svc.submit(a, CFG_A)
    svc.drain()
    assert svc.n_shed == 0 and svc.n_serialized >= 1
    assert len(svc.completed) == 4
    assert svc.pending_requests() == 0


# --------------------------------------------------------------- workloads

def test_poisson_arrivals_deterministic_and_sorted():
    cfgs = (CFG_A, CFG_B)
    a = poisson_arrivals(32, 100.0, ("blackscholes", "canneal"), cfgs, seed=3)
    b = poisson_arrivals(32, 100.0, ("blackscholes", "canneal"), cfgs, seed=3)
    assert a == b
    assert [x.t for x in a] == sorted(x.t for x in a)
    assert {x.app for x in a} <= {"blackscholes", "canneal"}
    c = poisson_arrivals(32, 100.0, ("blackscholes", "canneal"), cfgs, seed=4)
    assert a != c


def test_workload_repeat_pass_is_all_hits_and_bitwise(tmp_path):
    path = str(tmp_path / "serve_cache.jsonl")
    cfgs = (CFG_A, CFG_B)
    arrivals = poisson_arrivals(24, 1000.0, ("blackscholes", "canneal"),
                                cfgs, seed=0)
    svc = SimService(cache=dse.ResultCache(path), max_batch=8)
    rep1 = run_workload(svc, arrivals, realtime=False)
    assert rep1.hits == 0 and rep1.dispatched >= 1
    assert rep1.n == 24 and len(rep1.results) == 24

    svc2 = SimService(cache=dse.ResultCache(path), max_batch=8)
    rep2 = run_workload(svc2, arrivals, realtime=False)
    assert rep2.hit_fraction == 1.0 and rep2.dispatched == 0
    r1 = sorted(rep1.results, key=lambda r: r.uid)
    r2 = sorted(rep2.results, key=lambda r: r.uid)
    assert [r.steady_ns for r in r1] == [r.steady_ns for r in r2]
    assert [r.app for r in r1] == [r.app for r in r2]


def test_prewarm_covers_every_service_batch_bucket():
    svc = SimService(max_batch=16)
    assert svc.prewarm() == 2                   # buckets 8 and 16
    jc0 = eng.jit_cache_size()
    arrivals = poisson_arrivals(
        20, 1000.0, ("blackscholes", "canneal"),
        (CFG_A, CFG_B, eng.VectorEngineConfig(mvl=32, lanes=8)), seed=1)
    run_workload(svc, arrivals, realtime=False)
    assert eng.jit_cache_size() == jc0          # zero steady-state recompiles
    assert svc.recompiles == 0


def test_report_serializes_to_json():
    svc = SimService()
    arrivals = poisson_arrivals(6, 1000.0, ("blackscholes",), (CFG_A,),
                                seed=0)
    rep = run_workload(svc, arrivals, realtime=False)
    d = rep.to_dict()
    json.dumps(d)
    assert d["n"] == 6 and d["hits"] + d["coalesced"] + d["dispatched"] == 6
    assert rep.p99_ms >= rep.p50_ms >= 0.0
    json.dumps(svc.stats())


def test_invalid_service_parameters_rejected():
    with pytest.raises(ValueError):
        SimService(overflow="drop-oldest")
    with pytest.raises(ValueError):
        SimService(max_batch=0)
