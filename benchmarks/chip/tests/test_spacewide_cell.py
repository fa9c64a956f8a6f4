"""Whole runs of a CPU-sized cell shaped like ``spacewide-sweep-4chip``: the
``rivec-spacewide`` file cut to two apps and 16 points that hold both new
knobs (ring or crossbar, one or two VRF read ports).

On one device it runs in this process, is ``correct`` (the reference and the
program agree on the two knobs no cell set before), and the new
``sweep.sharded_step_share`` reads 0.  With ``chips`` 4 it runs in a
subprocess on four forced host devices: the traced run is ``correct`` and
reads 100.0, and the fault that brings back only the first device's shard
turns ``correct`` false."""
import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import harness, spec
from conftest import BENCH, REPO
from test_chipbench_runs import exchange_left_out

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "spacewide-sweep-4chip"
WIDE_AXES = [["mvl", [8, 64]], ["lanes", [1, 4]],
             ["interconnect", ["ring", "crossbar"]],
             ["vrf_read_ports", [1, 2]]]


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    import jax
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture
def wide(checkout):
    """The checkout with ``tiny-wide`` (1 chip) and ``tiny-wide-4chip``
    added: the new configuration's file cut to the CPU, both cells
    reporting every metric the four-chip cell reports."""
    with open(os.path.join(BENCH, "configs", "rivec-spacewide.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-wide", apps=["jacobi-2d", "pathfinder"],
               axes=WIDE_AXES)
    chip = checkout / "benchmarks" / "chip"
    with open(chip / "configs" / "tiny-wide.json", "w") as f:
        json.dump(cfg, f)
    with open(checkout / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-wide", "source": "https://arxiv.org/abs/2111.01949",
        "file": "benchmarks/chip/configs/tiny-wide.json",
        "reduced": ["axes"], "why": "CPU-sized test cell"})
    names = ("tiny-wide", "tiny-wide-4chip")
    bench["workloads"] += [
        {"name": n, "config": "tiny-wide", "traffic": "exhaustive-studies",
         "chips": c, "why": "test"} for n, c in zip(names, (1, 4))]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] += names
    with open(checkout / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return checkout


def run_cell(root, name, trace=False, seed=2 ** 31 + 4099):
    cell = spec.Cell(name, root=str(root))
    run = harness.Run(cell, seed, 1.0, trace, time.perf_counter())
    return harness.execute(run, CPU, compile_cache=False)


def test_the_configuration_is_spacefull_widened_by_two_knobs():
    wide_cfg = spec.load_json(os.path.join(BENCH, "configs",
                                           "rivec-spacewide.json"))
    full = spec.load_json(os.path.join(BENCH, "configs",
                                       "rivec-spacefull.json"))
    assert wide_cfg["axes"] == full["axes"] + [
        ["interconnect", ["ring", "crossbar"]], ["vrf_read_ports", [1, 2]]]
    assert spec.space_size(wide_cfg) * len(wide_cfg["apps"]) == 43008
    for key in ("apps", "base", "warmup_tiles", "measure_tiles", "limits"):
        assert wide_cfg[key] == full[key], key
    cell = spec.Cell(CELL, root=REPO)
    assert cell.chips == 4 and cell.config == wide_cfg
    assert "sweep.sharded_step_share" in {m["name"] for m in cell.per_layer()}


def test_crossbar_and_two_ports_agree_with_the_reference(wide):
    res = run_cell(wide, "tiny-wide")
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] % (2 * 16) == 0
    assert set(res["metrics"]) == {"cells_per_s", "setup_s"}


def test_one_device_reads_no_sharded_steps(wide):
    """A study that ran on one device reads 0, not nothing: the share is
    the number that shows a fallback."""
    res = run_cell(wide, "tiny-wide", trace=True)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["sweep.sharded_step_share"]["value"] == 0.0
    assert res["metrics"]["sweep.real_step_share"]["value"] > 0


def test_a_program_without_the_counter_gives_nothing(wide, monkeypatch):
    """The parent of the counter: the reader finds nothing to read and
    does not raise."""
    from repro.core import telemetry

    res = run_cell(wide, "tiny-wide")
    assert res["correct"] is True
    totals = telemetry.totals
    monkeypatch.setattr(telemetry, "totals", lambda: {
        **totals(), "counters": {
            k: v for k, v in totals()["counters"].items()
            if not k.endswith("_sharded")}})
    read = spec.Cell("tiny-wide", root=str(wide)).reader(
        "sweep.sharded_step_share")

    class Ctx:
        data = {"studies": [{"cells": 32}]}

    assert read(Ctx()) is None


_FOUR_DEVICES = r"""
import json, sys, time
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import jax
from chipbench import harness, spec
from repro.core import engine as eng
from test_chipbench_runs import exchange_left_out

assert jax.local_device_count() == 4, jax.local_device_count()
CPU = {"platform": "cpu", "kind": "cpu", "count": 4}


def run(trace, seed):
    cell = spec.Cell("tiny-wide-4chip", root=sys.argv[1])
    run = harness.Run(cell, seed, 1.0, trace, time.perf_counter())
    return harness.execute(run, CPU, compile_cache=False)


out = {"traced": run(True, 2 ** 33 + 7)}
orig = eng._dispatch_chunk_batch
eng._dispatch_chunk_batch = exchange_left_out(orig)
out["exchange_left_out"] = run(False, 2 ** 31 + 103)
eng._dispatch_chunk_batch = orig
out["sharded_jits"] = sorted(eng._SHARDED_JITS)
print(json.dumps(out))
"""


@pytest.fixture
def four_devices(wide):
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES, str(wide),
         os.path.join(str(wide), "benchmarks", "chip"),
         os.path.join(BENCH, "tests")],
        cwd=str(wide), env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_devices_sharded_run_is_correct(four_devices):
    """The traced four-device cell is correct and reads every lane-step
    sharded; the first device's shard copied over the others' is caught
    in an untraced run."""
    assert four_devices["sharded_jits"] == [4]
    res = four_devices["traced"]
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] % (2 * 16) == 0
    share = res["metrics"]["sweep.sharded_step_share"]
    assert share == {"value": 100.0, "unit": "%"}
    bad = four_devices["exchange_left_out"]
    assert set(bad["metrics"]) == {"cells_per_s", "setup_s"}
    assert bad["correct"] is False, bad["checks"]
