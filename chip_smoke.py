"""Bring-up check: the simulator's main path on a TPU, at study size.

One process drives the vector-engine scan (``engine._run_batch_group``) on
the chip through the entry points its users call, and checks every answer:

a. Table-10 sweep — ``suite.sweep_all`` over all 20 app names (480 cells),
   diffed against ``tests/golden_sweep.json`` at its tolerance, the same
   sweep on the host CPU in this process (largest chip-vs-CPU relative
   difference printed), then the 11-anchor scalar scorecard.
b. exact DSE — ``dse.explore`` over ``SPACE_QUICK`` x 10 apps (3,840
   cells) through an in-memory ``ResultCache``; the repeat must be all hits
   with an identical frontier fingerprint.
c. service — ``SimService`` prewarm + the seeded Poisson stream: no
   recompile after prewarm, a repeat pass >= 99 % bitwise cache hits.
d. surrogate — fit on the chip, score ``SPACE_10K``, verify every frontier
   point exactly, bitwise-identical frontiers on repeat.

``--four-chips`` runs only the config axis sharded over four chips and its
single-device reference.  Every result cache is in memory, so nothing on
disk can answer for the chip.  With no TPU the script exits non-zero before
any phase.  The last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage (from the repository root; no environment needed)::

    python chip_smoke.py
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# the chip-vs-CPU reference needs the host backend next to the TPU one
_plats = os.environ.get("JAX_PLATFORMS", "")
if _plats and "cpu" not in _plats.split(","):
    os.environ["JAX_PLATFORMS"] = _plats + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402


def _say(msg: str) -> None:
    print(msg, flush=True)


def phase_sweep() -> None:
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import gen_golden_sweep as golden

    from repro.core import scalar_pipeline, suite

    apps = golden.apps()
    t0 = time.perf_counter()
    chip = suite.sweep_all(apps)
    t_chip = time.perf_counter() - t0
    with open(golden.OUT) as f:
        report = golden.diff_report(golden.to_payload(chip), json.load(f))
    for line in report:
        _say(f"  {line}")
    n_cells = sum(len(g) for g in chip.values())
    _say(f"[a] sweep_all: {len(apps)} apps, {n_cells} cells in "
         f"{t_chip:.2f}s host wall; golden check: {len(report)} problem(s) "
         f"at rtol={golden.RTOL:g}")
    if report or n_cells != 480:
        raise SystemExit("phase a: golden table check failed")

    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        host = suite.sweep_all(apps)
    t_cpu = time.perf_counter() - t0
    worst, where = 0.0, None
    for app in apps:
        for cell, s_chip in chip[app].items():
            s_cpu = host[app][cell]
            rel = abs(s_chip - s_cpu) / abs(s_cpu)
            if rel > worst or where is None:
                worst, where = rel, (app, cell, s_chip, s_cpu)
    n_diff = sum(chip[a][c] != host[a][c] for a in apps for c in chip[a])
    app, (m, l), s_chip, s_cpu = where
    _say(f"[a] chip vs CPU: max rel diff {worst!r} at {app} mvl={m} "
         f"lanes={l} (chip {s_chip!r}, cpu {s_cpu!r}); {n_diff}/{n_cells} "
         f"cells differ at all; CPU sweep {t_cpu:.2f}s host wall")

    if scalar_pipeline.main(["--check"]) != 0:
        raise SystemExit("phase a: anchor scorecard failed")
    _say("[a] anchor scorecard: all 11 anchors in band")


def phase_dse() -> None:
    from repro.core import dse, tracegen

    t0 = time.perf_counter()
    rc = dse.main(["--space", "quick", "--apps",
                   ",".join(sorted(tracegen.APPS)), "--smoke"])
    wall = time.perf_counter() - t0
    if rc != 0:
        raise SystemExit("phase b: DSE repeat check failed")
    _say(f"[b] dse.explore SPACE_QUICK x 10 apps twice: {wall:.2f}s host "
         f"wall incl. compiles; repeat 100% hits, same frontier")


def phase_service() -> None:
    from repro.core import engine as eng
    from repro.serve import sim_service

    jc0 = eng.jit_cache_size()
    if jc0 < 0:
        raise SystemExit("phase c: jit_cache_size() cannot count compiles")
    if sim_service.main(["--smoke"]) != 0:
        raise SystemExit("phase c: service smoke failed")
    _say(f"[c] service: 0 recompiles after prewarm (jit_cache_size() "
         f"{jc0} before prewarm, {eng.jit_cache_size()} after both passes), "
         f"repeat >= 99% bitwise hits")


def phase_surrogate() -> None:
    from repro.core import search

    if search.main(["--smoke"]) != 0:
        raise SystemExit("phase d: surrogate search smoke failed")
    _say("[d] surrogate: every frontier point exact, repeats bitwise")


def phase_four_chips(n_pairs: int = 64) -> None:
    """The config axis sharded over four devices, against the same pairs
    through the single-device executable on device 0."""
    import jax.numpy as jnp

    from repro.configs import vector_engine as vcfg
    from repro.core import dse, engine as eng, isa, tracegen

    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found "
                         f"{len(devices)}")
    apps = sorted(tracegen.APPS)
    cfgs_all = vcfg.SPACE_QUICK.sample(n_pairs, seed=11)
    pairs = [(apps[i % len(apps)], cfgs_all[i]) for i in range(n_pairs)]
    traces = [dse.cell_body(a, c)[0].tile(4) for a, c in pairs]
    cfgs = [c for _, c in pairs]

    t0 = time.perf_counter()
    rows = eng.simulate_batch(traces, cfgs)
    t_sharded = time.perf_counter() - t0
    if 4 not in eng._SHARDED_JITS:
        raise SystemExit("simulate_batch never built the 4-device program")

    length = max(eng.trace_len_bucket(len(t)) for t in traces)
    stacked = isa.stack_traces(traces, length)
    xs_np = [getattr(stacked, f) for f in eng._TRACE_FIELDS]
    params_np = [np.stack(col)
                 for col in zip(*(eng._cfg_params_np(c) for c in cfgs))]

    def scan(dispatch, put):
        carry = tuple(put(np.zeros((n_pairs,) + a.shape, a.dtype))
                      for a in eng._init_carry())
        params = tuple(put(p) for p in params_np)
        for i in range(length // eng.CHUNK):
            xs = tuple(put(a[:, i * eng.CHUNK:(i + 1) * eng.CHUNK])
                       for a in xs_np)
            carry = dispatch(carry, xs, params)
        return {k: np.asarray(v) for k, v in eng._metrics(carry).items()}, \
            carry[9].sharding.device_set

    sharded, spans = scan(
        lambda c, x, p: eng._dispatch_chunk_batch(c, x, p, n_pairs),
        jnp.asarray)
    t0 = time.perf_counter()
    ref, ref_spans = scan(eng._chunk_batch_jit,
                          lambda a: jax.device_put(a, devices[0]))
    t_ref = time.perf_counter() - t0
    worst = 0.0
    for i, row in enumerate(rows):
        for k, v in row.items():
            for got in (v, float(sharded[k][i])):
                want = float(ref[k][i])
                worst = max(worst, abs(got - want) / max(abs(want), 1.0))
    _say(f"[4] simulate_batch: {n_pairs} pairs on {len(devices)} devices in "
         f"{t_sharded:.2f}s host wall; device-0 reference {t_ref:.2f}s")
    _say(f"[4] sharded rows vs device-0 rows: max rel diff {worst!r} "
         f"(limit 1e-5); final carry spans {len(spans)} device(s), "
         f"reference carry {len(ref_spans)}")
    if worst > 1e-5:
        raise SystemExit("four chips: sharded rows disagree with device 0")
    if len(spans) != 4:
        raise SystemExit("four chips: the carry does not span 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the config axis sharded over 4 chips "
                         "and its single-device reference")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache
    cache_dir = compile_cache.enable()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _say(f"chip_smoke: {device}, jax {jax.__version__}, "
         f"compile cache {cache_dir}")

    phases = ((phase_four_chips,) if args.four_chips else
              (phase_sweep, phase_dse, phase_service, phase_surrogate))
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        _say(f"{phase.__name__}: done in {time.perf_counter() - t0:.2f}s "
             f"host wall")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
