"""Fixtures of the chip benchmark's CPU tests: a temporary checkout that
holds a copy of the benchmark plus cells small enough for the CPU."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

TINY_AXES = [["mvl", [8, 64]], ["lanes", [1, 4]], ["l2_kb", [256, 1024]]]
SERVE_AXES = [["mvl", [8, 16, 32, 64]], ["lanes", [1, 2, 4, 8]],
              ["l2_kb", [256, 1024]]]


def tiny_config(name, axes=TINY_AXES, apps=("jacobi-2d", "pathfinder")):
    with open(os.path.join(BENCH, "configs", "rivec-spacefull.json")) as f:
        cfg = json.load(f)
    cfg.update(name=name, apps=list(apps), axes=axes)
    return cfg


@pytest.fixture
def checkout(tmp_path):
    """A checkout with the benchmark copied in and two CPU-sized cells,
    ``tiny-sweep`` and ``tiny-serve``, added as new files and entries only:
    the serving cell's metrics have readers but no cell in BENCHMARK.json
    yet, so their entries are added here."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(
                        ".traces", "__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    chip = root / "benchmarks" / "chip"
    for name, axes in (("tiny-sweep", TINY_AXES),
                       ("tiny-serve", SERVE_AXES)):
        with open(chip / "configs" / f"{name}.json", "w") as f:
            json.dump(tiny_config(name, axes), f)
        spec["configs"].append({
            "name": name, "source": "https://arxiv.org/abs/2111.01949",
            "file": f"benchmarks/chip/configs/{name}.json",
            "reduced": ["axes"], "why": "CPU-sized test cell"})
    with open(chip / "traffic" / "tiny-poisson.json", "w") as f:
        json.dump({"driver": "poisson_service", "rate_hz": 60.0,
                   "apps": None, "max_batch": 4, "max_wait_s": 0.05,
                   "max_queue": 16, "overflow": "serialize",
                   "trace_seconds": 1}, f)
    spec["workloads"] += [
        {"name": "tiny-sweep", "config": "tiny-sweep",
         "traffic": "exhaustive-studies", "chips": 1, "why": "test"},
        {"name": "tiny-serve", "config": "tiny-serve",
         "traffic": "tiny-poisson", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "spacefull-sweep" in m.get("workloads", ()):
            m["workloads"].append("tiny-sweep")
    spec["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["tiny-serve"]}
        for n in ("serve_p95_ms", "serve_p50_ms")]
    spec["per_layer"] += [
        {"name": n, "unit": u, "better": b, "source": "host_clock",
         "layer": "service", "moves": "serve_p95_ms",
         "workloads": ["tiny-serve"]}
        for n, u, b in (("serve.cells_per_batch", "cells", "higher"),
                        ("serve.gen_late_ms_p95", "ms", "lower"),
                        ("device_idle_share.serve", "%", "lower"))]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return root
