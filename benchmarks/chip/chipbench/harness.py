"""One run of one cell: set up, measure a window, read the layers, check the
answers against the plain reference, and print the result line.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device`` and, traced, ``breakdown``; its last key,
``checks``, holds each compared number beside its limit, as do the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
asks for, nothing is run and the exit code is 2.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys
import time

from chipbench import spec

# host spans of the benchmark's own, around the calls into each layer
SPANS = ("study", "submit", "flush", "wait_arrival", "engine_batch",
         "stack", "chunk_dispatch")
WINDOW = "window"
PEAKS = os.path.join(spec.BENCH_DIR, "peaks.json")
# the engine's scan executables (single-device and sharded)
SCAN_MODULES = r"_chunk_core"


class Run:
    """What one run knows: its cell, arguments, clock and spans."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 trace: bool, t_start: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.t_start = trace, t_start
        self.compiles = 0

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int):
    """The devices the run uses, or an error message: a TPU with exactly the
    cell's number of chips, of a kind the peaks table knows."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return None, f"no TPU: JAX found platform {devs[0].platform!r}"
    if len(devs) != chips:
        return None, f"the cell needs {chips} chip(s), JAX found {len(devs)}"
    if devs[0].device_kind not in spec.load_json(PEAKS):
        return None, f"device kind {devs[0].device_kind!r} not in peaks.json"
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}, None


def main(argv, t_start: float) -> int:
    args = parse(argv)
    try:
        cell = spec.Cell(args.workload)
    except (KeyError, OSError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    device, err = device_info(cell.chips)
    if err:
        print(f"bench: {err}; nothing was run", file=sys.stderr)
        return 2
    run = Run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    result = execute(run, device)
    print(json.dumps(result), flush=True)
    return 0


@contextlib.contextmanager
def _count_compiles(run: Run):
    import jax.monitoring as mon

    def on_event(name, *_, **__):
        if name == "/jax/core/compile/backend_compile_duration":
            run.compiles += 1
    mon.register_event_duration_secs_listener(on_event)
    try:
        yield
    finally:
        mon.unregister_event_duration_listener(on_event)


@contextlib.contextmanager
def _engine_spans(run: Run):
    """Traced runs only: spans around the engine batch path's steps."""
    from repro.core import engine as eng
    from repro.core import isa

    saved = []

    def wrap(owner, attr, name):
        fn = getattr(owner, attr)
        saved.append((owner, attr, fn))

        def wrapped(*a, **kw):
            with run.annotate(name):
                return fn(*a, **kw)
        setattr(owner, attr, wrapped)

    if run.trace:
        wrap(eng, "steady_state_time_batch", "engine_batch")
        wrap(isa, "stack_traces", "stack")
        wrap(eng, "_dispatch_chunk_batch", "chunk_dispatch")
    try:
        yield
    finally:
        for owner, attr, fn in saved:
            setattr(owner, attr, fn)


def execute(run: Run, device: dict, compile_cache: bool = True) -> dict:
    """Everything after the device check."""
    import jax

    sys.path.insert(0, os.path.join(run.cell.root, "src"))
    if compile_cache:
        from repro import compile_cache as cc
        cc.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with _count_compiles(run), _engine_spans(run):
        return _execute(run, device)


def _execute(run: Run, device: dict) -> dict:
    import jax

    from repro.core import engine as eng
    from chipbench import compare, reference, xplane

    driver = importlib.import_module(
        "chipbench.drivers." + run.cell.traffic["driver"]).Driver(run)

    trace_dir = os.path.join(run.cell.bench_dir, ".traces", run.cell.name)
    t_window = time.perf_counter()
    setup_s = t_window - run.t_start
    jit0, comp0 = eng.jit_cache_size(), run.compiles
    if run.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    with run.annotate(WINDOW):
        e2e = driver.window(run.seconds, run.trace)
    if run.trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        run.log(f"trace written in {time.perf_counter() - t0:.3f} s")
    run.log(f"compiles inside the window: {run.compiles - comp0} backend "
            f"compiles, {eng.jit_cache_size() - jit0} new engine "
            f"executables")
    device = dict(device, memory_peak_bytes=max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()))
    e2e["setup_s"] = setup_s

    result = {"correct": None, "attempted": driver.attempted(),
              "failed": driver.missing()}
    if run.trace:
        t0 = time.perf_counter()
        red = xplane.reduce(xplane.read(trace_dir, SPANS + (WINDOW,)),
                            WINDOW, SPANS, SCAN_MODULES)
        run.log(f"trace read in {time.perf_counter() - t0:.3f} s")
        for d in red["devices"]:
            idle = 100.0 * (1.0 - d["busy_s"] / d["window_s"])
            run.log(f"device {d['plane']}: busy {d['busy_s']:.6f} s of "
                    f"{d['window_s']:.6f} s, idle {idle:.3f} %; scan "
                    f"executables {d['matched_s']:.6f} s; programs "
                    f"{d['modules'][:12]}")
        devs = red["devices"]
        device["busy_s"] = sum(d["busy_s"] for d in devs) / max(len(devs), 1)
        device["window_s"] = red["window_s"]
        ctx = Layers(driver, red, device["kind"])
        metrics = {}
        for m in run.cell.per_layer():
            v = run.cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = xplane.breakdown(red)
    else:
        metrics = {}
        for m in run.cell.end_to_end():
            if m["name"] not in e2e:
                raise KeyError(f"the {run.cell.traffic['driver']} driver "
                               f"gives no {m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    run.log("end-to-end: " + ", ".join(f"{k} {v!r}" for k, v in e2e.items()))

    # the answers of the window against the plain reference
    keys, values = driver.answers()
    chosen = compare.sample(keys, lambda k: driver.cell(k)[0], run.seed)
    cells = [driver.cell(k) for k in chosen]
    driver.close()
    t_ref = time.perf_counter()
    cfg = run.cell.config
    ref = reference.answers(cells, cfg["warmup_tiles"], cfg["measure_tiles"])
    pos = {k: i for i, k in enumerate(chosen)}
    rows = [i for i, k in enumerate(keys) if k in pos]
    refs = [r[[pos[keys[i]] for i in rows]] for r in ref]
    checks = compare.check(values[rows], refs, result["failed"],
                           cfg["limits"])
    run.log(f"reference: {len(rows)} answers of {len(cells)} distinct "
            f"cells compared, in {time.perf_counter() - t_ref:.3f} s")
    result.update(correct=compare.passed(checks), metrics=metrics,
                  device=device, checks=compare.to_json(checks))
    run.log(f"run: {time.perf_counter() - run.t_start:.3f} s since the "
            f"process started")
    for name, (v, lim) in checks.items():
        run.log(f"check {name}: {v!r} (limit {lim!r})")
    return result


class Layers:
    """What a per-layer metric's reader may read: the driver's own numbers,
    the reduced trace, the chip's peaks and the reference's work count."""

    def __init__(self, driver, red: dict, kind: str):
        from chipbench import reference

        self.data = driver.layer_data()
        self.trace = red
        self.peaks = spec.load_json(PEAKS).get(kind, {})
        self.reference = reference
