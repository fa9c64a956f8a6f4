"""Find a cell's configuration, traffic mix and metrics by name."""
from __future__ import annotations

import importlib.util
import itertools
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file of the benchmark (a reader or the generator)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with what it names."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks", "chip")
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.spec["configs"]}
        self.config = load_json(os.path.join(
            root, configs[self.entry["config"]]["file"]))
        self.traffic = load_json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))

    def reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        return [m for m in self.spec["end_to_end"] if self.reports(m)]

    def per_layer(self) -> list:
        """Per-layer metrics read in this cell: those that list it, and
        those without a list whose ``moves`` metric this cell reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric_name: str):
        path = os.path.join(self.bench_dir, "metrics", metric_name + ".py")
        return load_module(path, metric_name).read


# --------------------------------------------------------------------------
# the design space of a configuration file
# --------------------------------------------------------------------------

def space_size(config: dict) -> int:
    n = 1
    for _, choices in config["axes"]:
        n *= len(choices)
    return n


def config_at(config: dict, index: int) -> dict:
    """The knobs of flat point ``index`` (mixed radix, last axis fastest)."""
    if not 0 <= index < space_size(config):
        raise IndexError(index)
    kv = dict(config["base"])
    for name, choices in reversed(config["axes"]):
        index, r = divmod(index, len(choices))
        kv[name] = choices[r]
    return kv


def all_configs(config: dict) -> list:
    """Every point of the space, in enumeration order."""
    names = [n for n, _ in config["axes"]]
    return [{**config["base"], **dict(zip(names, combo))}
            for combo in itertools.product(*(c for _, c in config["axes"]))]
