"""DSE subsystem tests: spaces, cache/dedup, Pareto reductions, sharding.

The expensive end-to-end behavior (384-point sweep, repeat-run cache hits)
lives in ``benchmarks/run.py --dse`` and the ``scripts/ci.sh`` dse-smoke
gate; here the spaces are kept tiny so the suite stays fast.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import dse
from repro.core import engine as eng
from repro.core import suite
from repro.core import telemetry
from repro.configs import vector_engine as vcfg


# ------------------------------------------------------------- DesignSpace

def test_design_space_size_and_enumeration_order():
    sp = dse.DesignSpace.of("t", mvl=(8, 64), lanes=(1, 4), mshrs=(1, 16))
    assert sp.size() == 8
    cfgs = sp.configs()
    assert len(cfgs) == 8
    # last axis fastest, and config_at agrees with configs()
    assert (cfgs[0].mvl, cfgs[0].lanes, cfgs[0].mshrs) == (8, 1, 1)
    assert (cfgs[1].mvl, cfgs[1].lanes, cfgs[1].mshrs) == (8, 1, 16)
    assert (cfgs[-1].mvl, cfgs[-1].lanes, cfgs[-1].mshrs) == (64, 4, 16)
    for i, c in enumerate(cfgs):
        assert sp.config_at(i) == c


def test_design_space_validates_fields_and_choices():
    with pytest.raises(ValueError, match="unknown"):
        dse.DesignSpace.of("bad", not_a_knob=(1, 2))
    with pytest.raises(ValueError, match="no choices"):
        dse.DesignSpace.of("bad", mvl=())
    with pytest.raises(IndexError):
        dse.DesignSpace.of("t", mvl=(8, 64)).config_at(2)


def test_design_space_sampling_is_deterministic_and_distinct():
    sp = vcfg.SPACE_FULL
    a = sp.sample(50, seed=3)
    b = sp.sample(50, seed=3)
    c = sp.sample(50, seed=4)
    assert a == b
    assert a != c
    assert len({cfg.label() for cfg in a}) == 50
    # n == size returns the full enumeration
    tiny = dse.DesignSpace.of("t", mvl=(8, 64))
    assert tiny.sample(2) == tiny.configs()


def test_design_space_sample_seed_pin():
    """The search loop depends on seeded sampling never drifting: pin the
    exact configs sample(seed=7) picks today (ISSUE-8 satellite)."""
    sp = dse.DesignSpace.of("pin", mvl=(8, 64, 256), lanes=(1, 4),
                            mshrs=(1, 16))
    picked = [(c.mvl, c.lanes, c.mshrs) for c in sp.sample(4, seed=7)]
    assert picked == [(8, 4, 1), (64, 1, 16), (64, 4, 16), (256, 4, 1)]
    # sorted flat indices: the sample preserves enumeration order
    flat = [sp.configs().index(c) for c in sp.sample(4, seed=7)]
    assert flat == sorted(flat)


def test_design_space_sample_rejects_oversampling():
    """n > size() must raise, not silently duplicate or shrink — a caller
    believing it explored n points must actually have n distinct configs."""
    tiny = dse.DesignSpace.of("t", mvl=(8, 64))
    with pytest.raises(ValueError, match="sample\\(10\\).*only 2"):
        tiny.sample(10)


def test_space_presets_have_documented_sizes():
    assert vcfg.SPACE_SMOKE.size() == 64
    assert vcfg.SPACE_QUICK.size() == 384
    assert vcfg.SPACE_FULL.size() == 1536
    # every axis is a real config field; the spaces construct cleanly
    assert len(vcfg.SPACE_FULL.configs()) == 1536


def test_labels_unique_over_space_full():
    """ISSUE satellite: the result-cache/result keys over the full DSE space
    (incl. the dram_bw_bytes_cycle axis) must never alias."""
    cfgs = vcfg.SPACE_FULL.configs()
    labels = [c.label() for c in cfgs]
    assert len(set(labels)) == len(cfgs)
    # the DRAM-bandwidth axis specifically is keyed
    base = eng.VectorEngineConfig(mvl=64, lanes=4, dram_bw_bytes_cycle=8.0)
    assert "dram_bw" in base.label()
    # float knobs that %g would round together stay distinct
    a = eng.VectorEngineConfig(dram_bw_bytes_cycle=4.0000001)
    b = eng.VectorEngineConfig(dram_bw_bytes_cycle=4.0000002)
    assert a.label() != b.label()


def test_labels_unique_over_scalar_knob_extension():
    """ISSUE-9 satellite: the space extended by the scalar-core knobs
    (issue_width / branch_miss_penalty / fusion) must keep labels unique —
    the PR-4 float-aliasing bug showed silent key collisions are real."""
    import dataclasses
    base = vcfg.SPACE_FULL.configs()[:64]
    extended = list(base)
    for cfg in base:
        extended += [dataclasses.replace(cfg, issue_width=1),
                     dataclasses.replace(cfg, branch_miss_penalty=12.0),
                     dataclasses.replace(cfg, fusion=True)]
    labels = [c.label() for c in extended]
    assert len(set(labels)) == len(extended)
    assert "_fusion" in eng.VectorEngineConfig(fusion=True).label()


def test_config_fingerprint_distinguishes_scalar_knobs():
    """The new knobs change the vector engine's scalar-block timing, so
    they MUST enter config_fingerprint — a stale cache hit across them
    would silently serve the wrong per-chunk time."""
    import dataclasses
    base = eng.VectorEngineConfig(mvl=64, lanes=4)
    fps = {eng.config_fingerprint(base)}
    for up in (dict(issue_width=1), dict(issue_width=4),
               dict(branch_miss_penalty=12.0), dict(fusion=True)):
        fps.add(eng.config_fingerprint(dataclasses.replace(base, **up)))
    assert len(fps) == 5


def test_cache_misses_on_new_scalar_knob():
    """End-to-end: a cache warmed at the default scalar core must MISS (and
    re-simulate) when a scalar knob changes, not serve the stale cell."""
    import dataclasses
    cache = dse.ResultCache()
    sp1 = dse.DesignSpace.of("t_iw", mvl=(16,), lanes=(2,))
    r1 = dse.explore(sp1, apps=("blackscholes",), cache=cache)
    assert r1.stats["simulated"] == 1
    cfg_f = dataclasses.replace(sp1.configs()[0], fusion=True)
    r2 = dse.explore([cfg_f], apps=("blackscholes",), cache=cache)
    assert r2.stats["simulated"] == 1      # miss: fusion is its own cell
    _, k1 = dse.cell_key("blackscholes", sp1.configs()[0], 8, 24)
    _, k2 = dse.cell_key("blackscholes", cfg_f, 8, 24)
    assert k1 != k2
    # the scalar side sees the knob too: same vector cell, new baseline
    assert r2.records[0].speedup != r1.records[0].speedup


# ----------------------------------------------------------- area/cost proxy

def test_area_proxy_monotone_in_capability():
    base = eng.VectorEngineConfig(mvl=64, lanes=4)
    for up in (dict(mvl=256), dict(lanes=8), dict(phys_regs=64),
               dict(l2_kb=1024), dict(mshrs=64), dict(l1_kb=64)):
        import dataclasses
        bigger = dataclasses.replace(base, **up)
        assert dse.area_proxy_kb(bigger) > dse.area_proxy_kb(base), up


# ------------------------------------------------------------- ResultCache

def test_result_cache_roundtrip_and_stats(tmp_path):
    path = str(tmp_path / "cache.jsonl")
    c = dse.ResultCache(path)
    assert c.get("k1") is None and c.misses == 1
    c.put("k1", 1.25)
    c.flush()
    assert c.get("k1") == 1.25 and c.hits == 1
    # a fresh object re-reads the file: persistence at full float precision
    c2 = dse.ResultCache(path)
    assert len(c2) == 1 and c2.get("k1") == 1.25
    c2.put("k2", 3.0000000000000004)
    c2.flush()
    assert dse.ResultCache(path).get("k2") == 3.0000000000000004


def test_result_cache_skips_corrupt_trailing_line(tmp_path):
    """A process killed mid-append leaves a truncated trailing JSONL line;
    loading must skip it with a warning, not crash (the PR-6 crash-safety
    regression)."""
    import warnings
    path = str(tmp_path / "cache.jsonl")
    c = dse.ResultCache(path)
    c.put("k1", 1.5)
    c.put("k2", 2.5)
    c.flush()
    with open(path, "a") as f:
        f.write('{"k": "k3", "v": 3.')      # truncated mid-flush
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c2 = dse.ResultCache(path)
    assert any("malformed" in str(x.message) for x in w)
    assert c2.corrupt_lines == 1
    assert len(c2) == 2                     # intact records survive
    assert c2.get("k1") == 1.5 and c2.get("k2") == 2.5
    # appending after recovery still round-trips
    c2.put("k4", 4.5)
    c2.flush()
    assert dse.ResultCache(path).get("k4") == 4.5


def test_result_cache_tolerates_non_record_lines(tmp_path):
    path = str(tmp_path / "cache.jsonl")
    with open(path, "w") as f:
        f.write('{"not_k": 1}\n')           # valid JSON, wrong schema
        f.write('[1, 2, 3]\n')              # not an object
        f.write('{"k": "good", "v": 7.0}\n')
    import warnings
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        c = dse.ResultCache(path)
    assert c.corrupt_lines == 2 and c.get("good") == 7.0


def test_result_cache_concurrent_flush_never_interleaves(tmp_path):
    """Many writers appending concurrently to one JSONL: every line must
    still parse and every record must survive (the single locked O_APPEND
    write contract)."""
    from concurrent.futures import ThreadPoolExecutor
    path = str(tmp_path / "cache.jsonl")
    n_writers, n_each = 8, 50

    def writer(w):
        c = dse.ResultCache(path)
        for i in range(n_each):
            # long-ish keys make torn writes visible if they ever happen
            c.put(f"writer{w}_rec{i}_" + "x" * 64, float(w * 1000 + i))
            if i % 7 == 0:
                c.flush()
        c.flush()

    with ThreadPoolExecutor(n_writers) as ex:
        list(ex.map(writer, range(n_writers)))

    merged = dse.ResultCache(path)
    assert merged.corrupt_lines == 0
    assert len(merged) == n_writers * n_each
    for w in range(n_writers):
        for i in range(n_each):
            assert merged.get(f"writer{w}_rec{i}_" + "x" * 64) == float(
                w * 1000 + i)


def test_result_cache_records_iterates_without_stats():
    c = dse.ResultCache()
    c.put("a", 1.0)
    c.put("b", 2.0)
    h, m = c.hits, c.misses
    assert list(c.records()) == [("a", 1.0), ("b", 2.0)]
    assert (c.hits, c.misses) == (h, m)      # pure read


def test_export_training_rows_joins_cache_to_cells_bitwise():
    """ISSUE-8 satellite: the cache's opaque-keyed values join back to
    (app, config) rows without re-simulating, and the derived runtime is
    bitwise-equal to the DseRecord explore() produced."""
    cache = dse.ResultCache()
    res = dse.explore(SP_TINY, apps=("blackscholes", "canneal"), cache=cache)
    sims = res.stats["simulated"]
    rows = cache.export_training_rows(("blackscholes", "canneal"), SP_TINY)
    assert len(rows) == len(res.records) == 16
    want = {(r.app, r.label): r for r in res.records}
    for row in rows:
        rec = want[(row["app"], row["label"])]
        assert row["steady_ns"] == rec.steady_ns
        assert row["runtime_ns"] == rec.runtime_ns
        assert row["speedup"] == rec.speedup
        assert row["area_kb"] == rec.area_kb
        assert row["cfg"] == rec.cfg
    # the join is a pure read: nothing new was simulated, no stats motion
    h, m = cache.hits, cache.misses
    cache.export_training_rows(("blackscholes",), SP_TINY)
    assert (cache.hits, cache.misses) == (h, m)
    assert dse.explore(SP_TINY, apps=("blackscholes", "canneal"),
                       cache=cache).stats["simulated"] == 0
    assert sims == 16


def test_export_training_rows_skips_unlabeled_cells():
    cache = dse.ResultCache()
    dse.explore(SP_TINY, apps=("blackscholes",), cache=cache)
    # canneal was never explored -> no rows for it, no invention
    rows = cache.export_training_rows(("canneal",), SP_TINY)
    assert rows == []
    # a config list (not a DesignSpace) works too
    rows = cache.export_training_rows(("blackscholes",),
                                      SP_TINY.configs()[:3])
    assert len(rows) == 3


def test_cell_key_matches_result_cache_key():
    """dse.cell_key (the serve layer's entry point) and ResultCache.key (the
    documented contract) must produce the same key for the same cell."""
    from repro.core import suite, tracegen
    cfg = eng.VectorEngineConfig(mvl=64, lanes=4)
    body, key = dse.cell_key("blackscholes", cfg, 8, 24)
    eff = suite.effective_mvl("blackscholes", cfg)
    ref_body = tracegen.body_for("blackscholes", eff, cfg)
    assert key == dse.ResultCache.key(ref_body, cfg, 8, 24)
    assert len(body) == len(ref_body)


def test_cache_key_separates_workloads_and_configs():
    from repro.core import tracegen
    cfg = eng.VectorEngineConfig(mvl=64, lanes=4)
    b1 = tracegen.body_for("blackscholes", 64, cfg)
    b2 = tracegen.body_for("canneal", 64, cfg)
    k = dse.ResultCache.key
    assert k(b1, cfg, 8, 24) != k(b2, cfg, 8, 24)
    assert k(b1, cfg, 8, 24) != k(b1, cfg, 4, 24)
    cfg2 = eng.VectorEngineConfig(mvl=64, lanes=8)
    assert k(b1, cfg, 8, 24) != k(b1, cfg2, 8, 24)


# ----------------------------------------------------------------- explore

SP_TINY = dse.DesignSpace.of("tiny", mvl=(16, 64), lanes=(2, 8),
                             l2_kb=(256, 1024))


def test_explore_matches_suite_speedup():
    res = dse.explore(SP_TINY, apps=("blackscholes",))
    assert len(res.records) == 8
    for r in res.records[:2]:
        want = suite.speedup("blackscholes", r.cfg)
        assert abs(r.speedup - want) <= 1e-5 * want


def test_explore_repeat_is_bitwise_and_fully_cached(tmp_path):
    path = str(tmp_path / "c.jsonl")
    r1 = dse.explore(SP_TINY, apps=("blackscholes", "canneal"),
                     cache=dse.ResultCache(path))
    assert r1.stats["simulated"] == 16 and r1.stats["hit_rate"] == 0.0
    r2 = dse.explore(SP_TINY, apps=("blackscholes", "canneal"),
                     cache=dse.ResultCache(path))
    assert r2.stats["simulated"] == 0 and r2.stats["hit_rate"] == 1.0
    assert [ (a.label, a.steady_ns, a.runtime_ns, a.speedup, a.area_kb)
             for a in r1.records ] == \
           [ (a.label, a.steady_ns, a.runtime_ns, a.speedup, a.area_kb)
             for a in r2.records ]
    assert dse._frontier_fingerprint(r1) == dse._frontier_fingerprint(r2)


def test_explore_phase_rows_counters_and_records():
    """The key/dispatch/derive rows keep their names; one row per engine
    span follows, and with dispatch's self time they make up its wall.
    The study's counters are reported and kept, and every record carries
    the batched engine's answer for its cell."""
    from repro.core import telemetry

    res = dse.explore(SP_TINY, apps=("blackscholes", "pathfinder"),
                      cache=dse.ResultCache())
    rows = res.stats["phases"]
    assert [r["phase"] for r in rows[:3]] == ["key", "dispatch", "derive"]
    spans = {r["phase"]: r for r in rows[3:]}
    assert set(spans) == {"engine.build", "engine.stack", "engine.gather",
                          "engine.copy", "engine.launch", "engine.wait",
                          "engine.readback"}
    assert all(r["kind"] == "dse.phase" and r["parent"] == "dispatch"
               and r["calls"] >= 1 for r in spans.values())
    dispatch = rows[1]
    assert dispatch["self_s"] + sum(r["wall_s"] for r in spans.values()) \
        == pytest.approx(dispatch["wall_s"], rel=1e-9)
    c = res.stats["counters"]
    assert c["dse.cells"] == len(res.records) == 16
    assert c["engine.lane_steps_real"] <= c["engine.lane_steps_scanned"]
    assert telemetry.recent("dse.study")[-1]["counters"] == c
    need = {}
    for r in res.records:
        body, key = dse.cell_key(r.app, r.cfg)
        need.setdefault(key, (body, r.cfg))
    want = dict(zip(need, eng.steady_state_time_batch(
        [b for b, _ in need.values()], [c for _, c in need.values()])))
    assert [r.steady_ns for r in res.records] == \
        [want[dse.cell_key(r.app, r.cfg)[1]] for r in res.records]


def test_explore_dedups_mvl_aliases_within_a_run():
    """streamcluster caps at max_vl=128: mvl=128 and mvl=256 induce the same
    clamped body AND the same timing parameters, so the cache dedups them to
    one dispatch and the records agree exactly."""
    sp = dse.DesignSpace.of("alias", mvl=(128, 256), lanes=(4,))
    res = dse.explore(sp, apps=("streamcluster",))
    assert res.stats["in_run_dedup"] == 1
    assert res.stats["simulated"] == 1
    r128, r256 = res.records
    assert r128.steady_ns == r256.steady_ns
    assert r128.label != r256.label          # results still keyed apart


# -------------------------------------------------- reductions: Pareto etc.

def _rec(app, label, runtime, area):
    return dse.DseRecord(app=app, label=label, cfg=None, steady_ns=runtime,
                         runtime_ns=runtime, speedup=1.0, area_kb=area)


def test_pareto_frontier_drops_dominated_points():
    recs = [_rec("a", "slow_small", 10.0, 1.0),
            _rec("a", "fast_big", 1.0, 10.0),
            _rec("a", "dominated", 10.0, 10.0),
            _rec("a", "mid", 5.0, 5.0),
            _rec("a", "mid_dup", 5.0, 5.0)]   # tie resolves by label
    labels = [r.label for r in dse.pareto_frontier(recs)]
    assert labels == ["fast_big", "mid", "slow_small"]


def test_best_under_budget():
    recs = [_rec("a", "fast_big", 1.0, 10.0),
            _rec("a", "mid", 5.0, 5.0),
            _rec("a", "slow_small", 10.0, 1.0)]
    assert dse.best_under_budget(recs, 100.0).label == "fast_big"
    assert dse.best_under_budget(recs, 6.0).label == "mid"
    assert dse.best_under_budget(recs, 0.5) is None


def test_explored_frontier_is_nondominated_and_summary_serializes():
    res = dse.explore(SP_TINY, apps=("canneal",))
    frontier = res.frontiers()["canneal"]
    assert frontier
    for i, r in enumerate(frontier):
        for s in frontier[i + 1:]:   # sorted: runtime up, area strictly down
            assert s.runtime_ns >= r.runtime_ns and s.area_kb < r.area_kb
        for other in res.records:    # nothing dominates a frontier point
            assert not (other.runtime_ns < r.runtime_ns
                        and other.area_kb < r.area_kb
                        and other.app == r.app)
    js = json.dumps(dse.frontier_summary(res, budgets=(256.0,)))
    assert "canneal" in js


def test_suite_entry_points():
    res = suite.dse_explore(SP_TINY, apps=("blackscholes",))
    assert res.n_configs == 8
    best = suite.dse_best_under_budget(SP_TINY, 1e9, apps=("blackscholes",))
    assert best["blackscholes"] is not None
    assert best["blackscholes"].runtime_ns == min(
        r.runtime_ns for r in res.records)


# ------------------------------------------------------ sharded dispatch

_SHARD_SCRIPT = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
assert jax.local_device_count() == 4, jax.local_device_count()
from repro.core import dse, engine as eng, telemetry, tracegen
cfg0 = eng.VectorEngineConfig(mvl=64, lanes=4)
tr = tracegen.body_for("blackscholes", 64, cfg0).tile(2)
cfgs = [eng.VectorEngineConfig(mvl=m, lanes=l)
        for m in (8, 64, 128, 256) for l in (2, 8)]
rows = eng.simulate_batch([tr], cfgs)
assert eng._SHARDED_JITS, "sharded path never engaged"
for c, r in zip(cfgs, rows):
    w = eng.simulate(tr, c)
    for k in w:
        assert abs(r[k] - w[k]) <= 1e-5 * max(abs(w[k]), 1.0), (c.label(), k)
print("SHARDED_OK")

# placement: a study over both knobs no other study sets, its chunk inputs
# and carry seen at the dispatch, against the single-device program
space = dse.DesignSpace.of("wide", mvl=(8, 64), lanes=(1, 4),
                           interconnect=("ring", "crossbar"),
                           vrf_read_ports=(1, 2))
apps = ("jacobi-2d", "pathfinder")
seen = {k: set() for k in ("inputs", "carry_out", "gather_table",
                           "gather_rows", "gather_out")}
dispatch = eng._dispatch_chunk_batch
placed = eng._SHARDED_JITS[4]


def where(key, arrays):
    for a in arrays:
        seen[key].add((repr(a.sharding), len(a.sharding.device_set)))


def spy(carry, xs, params, bb):
    where("inputs", carry + xs + params)
    out = dispatch(carry, xs, params, bb)
    where("carry_out", out)
    return out


def gather_spy(chunk, rows):
    where("gather_table", chunk)
    where("gather_rows", [rows])
    out = placed.gather(chunk, rows)
    where("gather_out", out)
    return out


# each lane's row taken on the host and put on the default device
def host_gather(chunk, rows):
    rows = np.asarray(rows)
    return tuple(jnp.asarray(np.take(np.asarray(a), rows, axis=0))
                 for a in chunk)


def study():
    before = telemetry.totals()
    res = dse.explore(space, apps=apps, cache=dse.ResultCache())
    rows = sorted((r.app, r.cfg.label(), r.cfg.interconnect,
                   r.cfg.vrf_read_ports, r.steady_ns, r.runtime_ns,
                   r.speedup) for r in res.records)
    return rows, telemetry.since(before)["counters"]


eng._dispatch_chunk_batch = spy
eng._SHARDED_JITS[4] = placed._replace(gather=gather_spy)
sharded, counters = study()
eng._SHARDED_JITS[4] = placed
eng._dispatch_chunk_batch = dispatch
n0 = eng.jit_cache_size()
again, _ = study()
repeat_compiles = eng.jit_cache_size() - n0
# the single-device programs on device 0, as on a one-chip host, and the
# same with each chunk gathered on the host
placement = eng._placement
eng._placement = lambda bb: eng._ONE_DEVICE
one, one_counters = study()
eng._placement = lambda bb: eng._ONE_DEVICE._replace(gather=host_gather)
host, _ = study()
eng._placement = placement
print(json.dumps({
    "sharded": sharded, "again": again, "one_device": one,
    "host_gathered": host,
    "counters": counters, "one_device_counters": one_counters,
    "repeat_compiles": repeat_compiles,
    "mesh": repr(placed.lanes), "replicated": repr(placed.table),
    **{k: sorted(v) for k, v in seen.items()},
}))
"""


@pytest.fixture(scope="module")
def four_devices():
    """One subprocess on four forced host devices (XLA flags are read at
    jax import) for every test of the sharded path below."""
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"),
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                          cwd=os.path.join(os.path.dirname(__file__), ".."),
                          env=env, capture_output=True, text=True,
                          timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


@pytest.fixture(scope="module")
def placement(four_devices):
    return json.loads(four_devices.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_dispatch_matches_sequential_subprocess(four_devices):
    """The DSE sharding contract: with >1 device the config axis runs
    through shard_map and results equal the sequential path."""
    assert "SHARDED_OK" in four_devices


def test_sharded_study_answers_bitwise_as_device_0(placement):
    """Both knobs no earlier study set (crossbar, two VRF read ports) are
    in the space, and every answer equals the single-device program's."""
    rows = placement["sharded"]
    assert len(rows) == 2 * 16
    assert {(r[2], r[3]) for r in rows} == {
        ("ring", 1), ("ring", 2), ("crossbar", 1), ("crossbar", 2)}
    assert placement["sharded"] == placement["one_device"]
    assert placement["again"] == placement["sharded"]
    # the chunks gathered on the devices answer as a host gather does
    assert placement["sharded"] == placement["host_gathered"]


def test_chunk_inputs_and_carry_span_four_devices(placement):
    mesh = placement["mesh"]
    assert "'cfg': 4" in mesh and "PartitionSpec('cfg',)" in mesh
    for key in ("inputs", "carry_out", "gather_rows", "gather_out"):
        assert placement[key] == [[mesh, 4]], key
    # the table chunk goes whole to every device, none resharded from one
    replicated = placement["replicated"]
    assert "'cfg': 4" in replicated and "PartitionSpec()" in replicated
    assert placement["gather_table"] == [[replicated, 4]]


def test_every_launch_of_the_study_is_sharded(placement):
    c = placement["counters"]
    assert c["engine.launches"] > 0
    assert c["engine.sharded_launches"] == c["engine.launches"]
    assert c["engine.lane_steps_sharded"] == c["engine.lane_steps_scanned"]
    # a repeat of the study compiles nothing: the carry the sharded
    # program returns has the layout its inputs were given
    assert placement["repeat_compiles"] == 0


def test_single_device_program_counts_no_sharded_launch(placement):
    c = placement["one_device_counters"]
    assert c["engine.launches"] == placement["counters"]["engine.launches"]
    assert "engine.sharded_launches" not in c
    assert "engine.lane_steps_sharded" not in c


def test_single_device_fallback_never_builds_sharded_jit():
    """On one device (the default CI environment) every dispatch takes the
    chunked single-device path — the fallback half of the contract — and
    the sharded-launch counters count 0, not nothing."""
    import jax
    if jax.local_device_count() != 1:
        pytest.skip("multi-device environment")
    before = telemetry.totals()["counters"]
    dse.explore(dse.DesignSpace.of("t1", mvl=(16,), lanes=(2, 4),
                                   interconnect=("ring", "crossbar")),
                apps=("pathfinder",), cache=dse.ResultCache())
    after = telemetry.totals()["counters"]
    assert after["engine.launches"] > before.get("engine.launches", 0)
    for name in ("engine.sharded_launches", "engine.lane_steps_sharded"):
        assert after[name] == 0, name
    assert eng._SHARDED_JITS == {}
