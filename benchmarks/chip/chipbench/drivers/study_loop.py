"""Closed loop of whole exhaustive studies, back to back.

Each study is ``dse.explore`` over every configuration of the space (in an
order the seed permutes) and every app of the cell, through a fresh
in-memory ``ResultCache``: each cell is simulated or deduplicated in the
run, as in a user's first pass.  The window holds as many whole studies as
fit in ``--seconds``, and at least one: another study starts only if, at
the pace of the last one, it ends in time.  A traced run times one study.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import spec


class Driver:
    def __init__(self, run):
        from repro.core import dse
        from repro.core import engine as eng

        self.run, self.dse = run, dse
        config, traffic = run.cell.config, run.cell.traffic
        self.apps = tuple(traffic.get("apps") or config["apps"])
        self.warmup = config["warmup_tiles"]
        self.measure = config["measure_tiles"]
        self.knobs = spec.all_configs(config)
        self.cfgs = [eng.VectorEngineConfig(**k) for k in self.knobs]
        self.cfg_id = {c: i for i, c in enumerate(self.cfgs)}
        self.app_id = {a: i for i, a in enumerate(self.apps)}
        self.studies = []
        # one whole study warms every shape and memo the window uses
        self._study(self.cfgs)

    def _study(self, cfgs):
        t0 = time.perf_counter()
        with self.run.annotate("study"):
            res = self.dse.explore(cfgs, self.apps,
                                   cache=self.dse.ResultCache(),
                                   warmup=self.warmup, measure=self.measure)
        return res, time.perf_counter() - t0

    def window(self, seconds: float, trace: bool) -> dict:
        rng = np.random.default_rng(self.run.seed % 2 ** 64)
        t_begin = time.perf_counter()
        while True:
            order = rng.permutation(len(self.cfgs))
            res, wall = self._study([self.cfgs[k] for k in order])
            self.studies.append(self._keep(res, wall))
            if trace or time.perf_counter() - t_begin + wall > seconds:
                break
        t_end = time.perf_counter()
        cells = sum(s["cells"] for s in self.studies)
        self.run.log(f"studies in window: {len(self.studies)}, "
                     f"{cells} cells, {t_end - t_begin:.3f} s; study walls "
                     f"{[round(s['wall_s'], 3) for s in self.studies]}")
        return {"cells_per_s": cells / (t_end - t_begin)}

    def _keep(self, res, wall):
        """Numbers of one study, without its record objects."""
        recs = res.records
        phases = {p["phase"]: p["wall_s"] for p in res.stats["phases"]}
        return {
            "wall_s": wall, "cells": len(recs), "phases": phases,
            "simulated": res.stats["simulated"],
            "app": np.asarray([self.app_id[r.app] for r in recs]),
            "cfg": np.asarray([self.cfg_id[r.cfg] for r in recs]),
            "values": np.asarray([(r.steady_ns, r.runtime_ns, r.speedup)
                                  for r in recs], np.float64),
        }

    # ---- what the harness reads after the window ------------------------

    def attempted(self) -> int:
        return len(self.studies) * len(self.apps) * len(self.cfgs)

    def missing(self) -> int:
        want = len(self.apps) * len(self.cfgs)
        return sum(want - len(set(zip(s["app"].tolist(), s["cfg"].tolist())))
                   for s in self.studies)

    def answers(self):
        """``((app index, config index), values)`` of every answered
        cell."""
        keys = [k for s in self.studies
                for k in zip(s["app"].tolist(), s["cfg"].tolist())]
        return keys, np.concatenate([s["values"] for s in self.studies])

    def cell(self, key):
        app, cfg = key
        return self.apps[app], self.knobs[cfg]

    def layer_data(self) -> dict:
        return {"studies": [{k: s[k] for k in ("wall_s", "cells", "phases",
                                               "simulated")}
                            for s in self.studies],
                "apps": self.apps, "knobs": self.knobs,
                "warmup": self.warmup, "measure": self.measure}

    def close(self):
        self.cfgs = self.cfg_id = None
