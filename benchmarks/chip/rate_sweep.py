"""Find the highest request rate a serving cell sustains, on the chip.

    python3 benchmarks/chip/rate_sweep.py --workload spacehuge-serve \\
        --seed 1 --seconds 15 --rates 40,60,80,100,120

Runs the cell's traffic at each rate in one process, each for
``--seconds``, and prints per rate the requests answered, the backlog when
the window closed (requests answered later than the window's end plus the
batching wait), the latency percentiles and how late the generator ran.  A
rate is sustained when the backlog is no more than one batch.  The cell's
traffic file then takes 0.8 of the highest sustained rate.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    device, err = harness.device_info(cell.chips)
    if err:
        print(f"rate_sweep: {err}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(cell.root, "src"))
    from repro import compile_cache
    from chipbench.drivers import poisson_service

    compile_cache.enable()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.traffic["rate_hz"] = rate
        run = harness.Run(cell, args.seed + k, args.seconds, False, T_START)
        drv = poisson_service.Driver(run)
        e2e = drv.window(args.seconds, False)
        row = {"rate_hz": rate, "requests": len(drv.t),
               "answered": len(drv.results), "backlog": drv.backlog,
               "batches": drv.counters["batches"],
               "gen_late_p99_ms": drv.gen.percentile(drv.late, 99) * 1e3,
               **e2e, "device": device}
        print(json.dumps(row), flush=True)
        drv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
