"""The program's own spans and counters in a traced run of the CPU-sized
sweep cell: the engine batch path's metrics are read, the counters agree
with the reference's work count and with the engine's own bucketing, and
the spans land in the profiler trace inside the harness's spans."""
import glob
import os
import time

import pytest

from chipbench import harness, reference, spec, xplane

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
NEW = {f"sweep.{k}_ms_per_kcell" for k in (
    "build", "stack", "copy", "launch", "wait", "readback")} | {
    "sweep.real_step_share", "sweep.launches_per_kcell"}


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    import jax
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def traced_sweep(root):
    """One traced run of ``tiny-sweep``: its cell, its result line and the
    program's counters of its one study."""
    from repro.core import telemetry

    cell = spec.Cell("tiny-sweep", root=str(root))
    run = harness.Run(cell, 2 ** 31 + 913, 1.0, True, time.perf_counter())
    res = harness.execute(run, CPU, compile_cache=False)
    return cell, res, telemetry.recent("dse.study")[-1]["counters"]


def distinct_cells(cell):
    """The (body, config) pairs a study simulates: one per cache key."""
    from repro.core import dse
    from repro.core import engine as eng

    cfg = cell.config
    need = {}
    for app in cfg["apps"]:
        for knobs in spec.all_configs(cfg):
            c = eng.VectorEngineConfig(**knobs)
            body, key = dse.cell_key(app, c, cfg["warmup_tiles"],
                                     cfg["measure_tiles"])
            need.setdefault(key, body)
    return list(need.values())


def test_traced_run_reads_the_program_spans(checkout):
    _, res, _ = traced_sweep(checkout)
    assert res["correct"] is True, res["checks"]
    assert NEW <= set(res["metrics"])
    for name in NEW:
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["sweep.real_step_share"]["value"] <= 100.0


def test_real_lane_steps_are_the_reference_work(checkout):
    cell, _, counters = traced_sweep(checkout)
    cfg = cell.config
    work = reference.work_bytes(cfg["apps"], spec.all_configs(cfg),
                                cfg["warmup_tiles"], cfg["measure_tiles"])
    assert counters["engine.lane_steps_real"] * reference.BYTES_PER_RECORD \
        == work


def test_launches_and_scanned_steps_follow_the_buckets(checkout):
    from repro.core import engine as eng

    cell, _, counters = traced_sweep(checkout)
    w, m = cell.config["warmup_tiles"], cell.config["measure_tiles"]
    groups: dict = {}
    for body in distinct_cells(cell):
        fused = eng.trace_len_bucket(w * len(body)) + m * len(body)
        length = eng.trace_len_bucket(fused)
        groups[length] = groups.get(length, 0) + 1
    assert counters["engine.launches"] == sum(
        length // eng.CHUNK for length in groups)
    assert counters["engine.lane_steps_scanned"] == sum(
        eng.batch_bucket(n) * length for length, n in groups.items())
    assert counters.get("engine.lane_steps_batch_pad", 0) == sum(
        (eng.batch_bucket(n) - n) * length for length, n in groups.items())


def test_program_spans_nest_inside_the_harness_spans(checkout):
    cell, _, _ = traced_sweep(checkout)
    trace_dir = os.path.join(cell.bench_dir, ".traces", cell.name)
    assert glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    names = {"engine_batch", "engine.stack", "engine.copy"}
    spans = xplane.host_spans(xplane.read(trace_dir, names), names)
    outer = [(s, e) for n, s, e in spans if n == "engine_batch"]
    assert outer
    for kind in ("engine.stack", "engine.copy"):
        inner = [(s, e) for n, s, e in spans if n == kind]
        assert inner, kind
        for s, e in inner:
            assert any(lo <= s and e <= hi for lo, hi in outer), (kind, s, e)
