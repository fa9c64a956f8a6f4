"""Time the device scan step on a TPU and split one step's time by op.

For each batch bucket, the batched chunk scan (``engine._chunk_batch_jit``)
runs over lanes of RiVEC loop bodies and ``SPACE_FULL`` configs: warmed up,
then timed by the host clock over ``--chunks`` chunks with the carry
threaded through, as a study runs it.  For ``--split-lanes`` the same
chunks then run under the profiler, and the ``XLA Ops`` events of the
device inside the scan program's executions are summed by op (an event is
named by its HLO instruction's text).  The unbatched ``simulate`` program
is timed too.  Every time is per scan step.

    python scripts/scan_op_split.py [--src DIR] [--out FILE.json]

``--src`` imports the engine from another checkout's ``src`` (a parent
commit unpacked beside this one), so one process on the chip measures
either tree.  Needs a TPU: off the chip the trace has no device plane and
the script says so.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time


def _lanes(jax, eng, tracegen, vcfg, n: int):
    """``n`` lanes of ``2 * CHUNK`` records on the device, as two chunks:
    bodies of the seven RiVEC apps tiled, each with a ``SPACE_FULL``
    config, taken in turn."""
    import numpy as np
    cfgs = vcfg.SPACE_FULL.configs()[:n]
    cfgs = (cfgs * (n // len(cfgs) + 1))[:n]
    apps = tracegen.RIVEC_APPS
    length = 2 * eng.CHUNK
    bodies = {}
    rows = []
    for i, c in enumerate(cfgs):
        key = (apps[i % len(apps)], c.mvl)
        if key not in bodies:
            b = tracegen.body_for(key[0], c.mvl, c)
            bodies[key] = b.tile(-(-length // len(b)))
        rows.append(bodies[key])
    chunks = [tuple(np.stack([getattr(t, f)[j * eng.CHUNK:(j + 1) * eng.CHUNK]
                              for t in rows]) for f in eng._TRACE_FIELDS)
              for j in range(2)]
    params = tuple(np.stack(col) for col in
                   zip(*(eng._cfg_params_np(c) for c in cfgs)))
    return jax.device_put(chunks), jax.device_put(params)


def _run(jax, eng, xs, params, chunks: int):
    """``chunks`` chunk dispatches, carry threaded, waited for."""
    import jax.numpy as jnp
    b = xs[0][0].shape[0]
    carry = jax.tree.map(lambda a: jnp.zeros((b,) + a.shape, a.dtype),
                         eng._init_carry())
    for i in range(chunks):
        carry = eng._chunk_batch_jit(carry, xs[i % 2], params)
    jax.block_until_ready(carry)


def _step_us(jax, eng, xs, params, chunks: int, reps: int) -> float:
    _run(jax, eng, xs, params, 2)                      # compile and warm
    walls = []
    for _ in range(reps):
        t = time.perf_counter()
        _run(jax, eng, xs, params, chunks)
        walls.append(time.perf_counter() - t)
    return statistics.median(walls) / (chunks * eng.CHUNK) * 1e6


def _split(jax, eng, xs, params, chunks: int, top: int) -> dict:
    """One step's device time by op, from the ``XLA Ops`` events inside
    the scan program's ``XLA Modules`` executions."""
    _run(jax, eng, xs, params, 2)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            _run(jax, eng, xs, params, chunks)
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        pd = jax.profiler.ProfileData.from_file(files[0])
    dev = [p for p in pd.planes if p.name.startswith("/device:TPU")]
    if not dev:
        raise SystemExit("no TPU device plane in the trace: run on a TPU")
    lines = {ln.name: list(ln.events) for ln in dev[0].lines}
    scans = [(e.start_ns, e.end_ns) for e in lines.get("XLA Modules", [])
             if e.name.startswith("jit__chunk_core")]
    inside = lambda e: any(s <= e.start_ns and e.end_ns <= t
                           for s, t in scans)
    steps = len(scans) * eng.CHUNK
    ops: dict = {}
    for e in lines.get("XLA Ops", []):
        if inside(e):
            # an op event is named by its HLO instruction's text
            ent = ops.setdefault(e.name[:240], {"ns": 0.0, "n": 0})
            ent["ns"] += e.duration_ns
            ent["n"] += 1
    module_ns = sum(t - s for s, t in scans)
    rows = sorted(ops.items(), key=lambda kv: -kv[1]["ns"])
    # the loop op spans the ops of its body: count those, not it
    is_loop = lambda n: n.lstrip("%").startswith("while")
    body = [(n, v) for n, v in rows if not is_loop(n)]
    per_step = lambda ns: ns / steps * 1e-3
    return {
        "executions": len(scans), "steps": steps,
        "module_us_per_step": per_step(module_ns),
        "ops_us_per_step": per_step(sum(v["ns"] for _, v in body)),
        "ops_per_step": sum(v["n"] for _, v in body) / steps,
        "top": [{"op": n, "us_per_step": per_step(v["ns"]),
                 "per_step": v["n"] / steps} for n, v in body[:top]],
        "loops": [{"op": n, "us_per_step": per_step(v["ns"])}
                  for n, v in rows if is_loop(n)],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--src", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "src"))
    ap.add_argument("--buckets", default="256,2048,8192")
    ap.add_argument("--split-lanes", type=int, default=2048)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.src))
    import jax
    from repro.configs import vector_engine as vcfg
    from repro.core import engine as eng, tracegen

    dev = jax.devices()[0]
    res = {"src": os.path.abspath(args.src), "device": dev.device_kind,
           "platform": dev.platform, "step_us": {}}
    for b in (int(x) for x in args.buckets.split(",")):
        xs, params = _lanes(jax, eng, tracegen, vcfg, b)
        res["step_us"][str(b)] = _step_us(jax, eng, xs, params, args.chunks,
                                          args.reps)
        print(f"bucket {b}: {res['step_us'][str(b)]:.3f} us a step",
              flush=True)
        if b == args.split_lanes:
            res["split"] = _split(jax, eng, xs, params, args.chunks, args.top)
    cfg = eng.VectorEngineConfig()
    tr = tracegen.body_for("blackscholes", cfg.mvl, cfg)
    tr = tr.tile(-(-8 * eng.CHUNK // len(tr)))
    eng.simulate(tr, cfg)
    t = time.perf_counter()
    for _ in range(args.reps):
        eng.simulate(tr, cfg)
    res["simulate_step_us"] = ((time.perf_counter() - t) / args.reps
                               / len(tr) * 1e6)
    print(f"simulate: {res['simulate_step_us']:.3f} us a step", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    print(json.dumps({k: v for k, v in res.items() if k != "split"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
