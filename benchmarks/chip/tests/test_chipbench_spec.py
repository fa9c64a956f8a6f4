"""The benchmark's definition: BENCHMARK.json, the files it names, the
traffic generator, the trace reduction and the roofline's work count."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from chipbench import reference, spec, xplane

BENCH = spec.BENCH_DIR
REPO = spec.ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load_spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units_use_allowed_characters():
    b = load_spec()
    assert set(b) == TOP_KEYS
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(set(names)) == len(names), group
        for n in names:
            assert NAME.match(n), n
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in b["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))


def test_every_layer_metric_cell_reports_the_metric_it_moves():
    b = load_spec()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)
    for cell in cells:
        c = spec.Cell(cell, root=REPO)
        names = {m["name"] for m in c.end_to_end()}
        assert "setup_s" in names and len(names) >= 2, cell
        assert c.per_layer(), cell


@pytest.mark.parametrize("cell", [w["name"] for w in load_spec()["workloads"]])
def test_cell_files_are_found_by_name(cell):
    c = spec.Cell(cell, root=REPO)
    assert c.config["name"] == c.entry["config"]
    assert os.path.isfile(os.path.join(
        BENCH, "chipbench", "drivers", c.traffic["driver"] + ".py"))
    for m in c.per_layer():
        assert callable(c.reader(m["name"]))
    assert set(c.config["limits"]) == {"steady_gap", "runtime_gap",
                                       "speedup_gap", "missing"}


def test_added_cell_and_metric_are_picked_up(checkout):
    """A new cell, configuration, traffic mix and per-layer metric that are
    only new files and new BENCHMARK.json entries."""
    chip = checkout / "benchmarks" / "chip"
    (chip / "metrics" / "sweep.cells_per_study.py").write_text(
        "def read(ctx):\n"
        "    s = ctx.data.get('studies')\n"
        "    return s[0]['cells'] if s else None\n")
    b = json.loads((checkout / "BENCHMARK.json").read_text())
    b["per_layer"].append({
        "name": "sweep.cells_per_study", "unit": "cells", "better": "higher",
        "source": "program_counter", "layer": "study driver",
        "moves": "cells_per_s", "workloads": ["tiny-sweep"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(b))
    c = spec.Cell("tiny-sweep", root=str(checkout))
    assert "sweep.cells_per_study" in [m["name"] for m in c.per_layer()]
    read = c.reader("sweep.cells_per_study")
    assert read(type("Ctx", (), {"data": {"studies": [{"cells": 8}]}})) == 8
    assert c.config["axes"][0] == ["mvl", [8, 64]]
    s = spec.Cell("tiny-serve", root=str(checkout))
    assert s.traffic["rate_hz"] == 60.0
    assert {m["name"] for m in s.end_to_end()} == {
        "serve_p95_ms", "serve_p50_ms", "setup_s"}
    assert {m["name"] for m in s.per_layer()} == {
        "serve.cells_per_batch", "serve.gen_late_ms_p95",
        "device_idle_share.serve"}


def test_generator_is_deterministic_in_its_seed():
    gen = spec.load_module(os.path.join(BENCH, "traffic", "generator.py"),
                           "generator")
    big = 2 ** 31 + 12345
    a = gen.poisson_stream(big, 150.0, 30.0, 7, 1_244_160)
    b = gen.poisson_stream(big, 150.0, 30.0, 7, 1_244_160)
    c = gen.poisson_stream(big + 1, 150.0, 30.0, 7, 1_244_160)
    for x, y in zip(a, b):
        assert (x == y).all()
    assert not (a[2] == c[2]).all()
    t, app, flat = a
    assert len(t) == len(c[0]) == 4500
    assert (t[1:] >= t[:-1]).all() and 0 <= t[0] and t[-1] < 30.0
    # every seed asks the same number of times for each app
    assert sorted(app.tolist()) == sorted(c[1].tolist())
    assert flat.min() >= 0 and flat.max() < 1_244_160
    # ... at the same gaps, in another order
    gaps = [sorted(np.diff(np.append(s[0], 30.0))) for s in (a, c)]
    assert np.allclose(gaps[0], gaps[1])
    assert not np.allclose(np.diff(a[0]), np.diff(c[0]))
    assert np.mean(gaps[0]) == pytest.approx(1 / 150.0)
    # strata: every (app, first-axis value) pair equally often, each flat
    # index inside its own stratum
    pairs = []
    for s in (big, big + 1):
        _, app, flat = gen.poisson_stream(s, 10.0, 30.0, 7, 1_244_160, 6)
        pairs.append(sorted(zip(app.tolist(), (flat // 207_360).tolist())))
        counts = np.unique(np.array(pairs[-1]), axis=0, return_counts=True)
        assert len(counts[1]) == 42 and counts[1].max() - counts[1].min() <= 1
    assert pairs[0] == pairs[1]
    with pytest.raises(ValueError):
        gen.poisson_stream(1, 10.0, 1.0, 2, 10, 4)
    assert gen.poisson_stream(-7, 10.0, 1.0, 2, 4)[0].size == 10
    assert gen.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert gen.percentile(range(101), 99) == 99.0


def _trace():
    """Two devices and the host's spans, in ns: window [1000, 2000]."""
    host = ("/host:CPU", [("python", [
        ("window", 1000.0, 1000.0), ("study", 1000.0, 900.0),
        ("engine_batch", 1100.0, 500.0), ("chunk_dispatch", 1200.0, 100.0),
        ("unrelated", 1000.0, 1000.0)])])
    dev0 = ("/device:TPU:0", [
        ("XLA Modules", [("jit__chunk_core(1)", 1200.0, 150.0),
                         ("jit_maximum(3)", 1300.0, 100.0),
                         ("jit_other(2)", 1700.0, 100.0),
                         ("jit__chunk_core(1)", 900.0, 200.0)])])
    dev1 = ("/device:TPU:1", [
        ("XLA Modules", [("jit__chunk_core(1)", 1200.0, 400.0)])])
    other = ("/device:TPU:0 SparseCore",
             [("XLA Modules", [("x", 1000.0, 999.0)])])
    return [host, dev0, dev1, other]


def test_trace_reduction_busy_idle_and_kernel_time():
    red = xplane.reduce(_trace(), "window", ("study", "engine_batch",
                                             "chunk_dispatch"), r"_chunk_core")
    assert red["window_s"] == pytest.approx(1000e-9)
    d0, d1 = red["devices"]
    # busy: [1000,1100] (clipped) + [1200,1400] + [1700,1800]
    assert d0["busy_s"] == pytest.approx(400e-9)
    assert d1["busy_s"] == pytest.approx(400e-9)
    # executions that start inside the window
    assert d0["matched_s"] == pytest.approx(150e-9)
    assert d1["matched_s"] == pytest.approx(400e-9)
    idle = dict()
    for n, s in d0["idle"]:
        idle[n] = idle.get(n, 0.0) + s
    # gaps [1100,1200] and [1400,1700] while engine_batch was open (the
    # midpoint 1550 is before it closed at 1600), [1800,2000] after study
    # closed at 1900: outside any span
    assert idle == pytest.approx({"engine_batch": 400e-9,
                                  "outside any span": 200e-9})
    br = xplane.breakdown(red)
    assert br["device_ops"][0] == ["jit__chunk_core(1)",
                                   pytest.approx(275e-9)]
    assert {n for n, _ in br["idle_gaps"]} <= {
        "engine_batch", "study", "outside any span"}


def test_roofline_work_count_of_a_known_body():
    cfg = dict(spec.load_json(os.path.join(
        BENCH, "configs", "rivec-spacefull.json"))["base"], mvl=64, lanes=4)
    assert reference.body_len("pathfinder", cfg) == 16
    one = reference.work_bytes(["pathfinder"], [cfg], 8, 24)
    assert one == 32 * 16 * 44
    # a knob the timing never reads leaves the cell's work shared
    twin = dict(cfg, vrf_line_bits=1024)
    assert reference.work_bytes(["pathfinder"], [cfg, twin], 8, 24) == one
    other = dict(cfg, lanes=8)
    assert reference.work_bytes(["pathfinder"], [cfg, other], 8, 24) \
        == 2 * one
    # particlefilter's body is the longest: 985 records
    assert reference.body_len("particlefilter", cfg) == 985


def test_bench_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "bench.py"), "--workload",
         "spacefull-sweep", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
