"""Readings that a cell's limits are set from, on the chip.

    python3 benchmarks/chip/control.py --workload spacefull-sweep \\
        --seeds 1-12 --seconds 1

In one process, for each seed: one short window of the cell's timed path
(one study, or ``--seconds`` of requests), then the numbers that decide
``correct`` twice: for the program's answers against the plain float32
reference (the lower reading), and for the control, the same reference
computed in bfloat16 and put in the program's place (the upper reading).
Prints one JSON line per seed.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

from chipbench import harness, spec  # noqa: E402


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,8")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    device, err = harness.device_info(cell.chips)
    if err:
        print(f"control: {err}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(cell.root, "src"))
    import importlib

    from repro import compile_cache
    from chipbench import compare, reference

    compile_cache.enable()
    mod = importlib.import_module("chipbench.drivers."
                                  + cell.traffic["driver"])
    cfg = cell.config
    limits = dict(cfg["limits"])
    drv = None
    for seed in seeds(args.seeds):
        run = harness.Run(cell, seed, args.seconds, False, T_START)
        if drv is None or not hasattr(drv, "studies"):
            drv = mod.Driver(run)
        drv.run = run
        if hasattr(drv, "studies"):
            drv.studies = []
        drv.window(args.seconds, True)
        keys, values = drv.answers()
        chosen = compare.sample(keys, lambda k: drv.cell(k)[0], seed)
        cells = [drv.cell(k) for k in chosen]
        pos = {k: i for i, k in enumerate(chosen)}
        rows = [i for i, k in enumerate(keys) if k in pos]
        idx = [pos[keys[i]] for i in rows]
        t0 = time.perf_counter()
        ref = reference.answers(cells, cfg["warmup_tiles"],
                                cfg["measure_tiles"])
        t_ref = time.perf_counter() - t0
        refs = [r[idx] for r in ref]
        row = {"seed": seed, "answers": len(rows), "cells": len(cells),
               "reference_s": t_ref,
               "program": {k: v for k, (v, _) in compare.check(
                   values[rows], refs, drv.missing(), limits).items()}}
        low = reference.answers(cells, cfg["warmup_tiles"],
                                cfg["measure_tiles"], dtype="bfloat16")
        row["control"] = {k: v for k, (v, _) in compare.check(
            np.stack([r[idx] for r in low], 1), refs, 0, limits).items()}
        row["device"] = device
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
