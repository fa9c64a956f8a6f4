"""Open-loop Poisson requests to the simulation service, in real time.

Each request is one (app, configuration) cell drawn by the traffic
generator.  The driver sleeps out the schedule, fires the service's batch
timeout at its deadline, and submits each request stamped with its
scheduled time, so a request's latency runs from when it was due to when
its answer was ready.  The service flushes inline, so a flush holds the
submitting thread; how late each submission ran is kept as well.
"""
from __future__ import annotations

import os
import time

import numpy as np

from chipbench import spec


class Driver:
    def __init__(self, run):
        from repro.core import dse, suite
        from repro.core import engine as eng
        from repro.serve import sim_service

        self.run = run
        config, traffic = run.cell.config, run.cell.traffic
        self.apps = tuple(traffic.get("apps") or config["apps"])
        self.warmup = config["warmup_tiles"]
        self.measure = config["measure_tiles"]
        self.gen = spec.load_module(
            os.path.join(run.cell.bench_dir, "traffic", "generator.py"),
            "generator")
        self.svc = sim_service.SimService(
            cache=dse.ResultCache(), max_batch=traffic["max_batch"],
            max_wait_s=traffic["max_wait_s"], max_queue=traffic["max_queue"],
            overflow=traffic["overflow"], warmup=self.warmup,
            measure=self.measure)
        self.svc.prewarm()
        # every body and scalar baseline a request can need
        mvls = dict(config["axes"])["mvl"]
        for app in self.apps:
            for mvl in mvls:
                cfg = eng.VectorEngineConfig(**{**config["base"], "mvl": mvl})
                dse.cell_body(app, cfg)
                suite.scalar_runtime_ns(app, cfg)
        seconds = (min(run.seconds, traffic["trace_seconds"]) if run.trace
                   else run.seconds)
        self.rate = float(traffic["rate_hz"])
        # strata: the values of the space's first axis (the MVL, which sets
        # a body's length and so a request's cost)
        t, app, flat = self.gen.poisson_stream(
            run.seed, self.rate, seconds, len(self.apps),
            spec.space_size(config), len(config["axes"][0][1]))
        self.t, self.app = t, app
        self.knobs = [spec.config_at(config, int(k)) for k in flat]
        self.cfgs = [eng.VectorEngineConfig(**k) for k in self.knobs]
        self.seconds = seconds

    def window(self, seconds: float, trace: bool) -> dict:
        svc, clock, annotate = self.svc, time.perf_counter, self.run.annotate
        s0 = svc.stats()
        n0 = len(svc.completed)
        late = np.zeros(len(self.t))
        t0 = clock()
        for i, (dt, a, cfg) in enumerate(zip(self.t, self.app, self.cfgs)):
            target = t0 + dt
            while True:
                dl = svc.next_deadline()
                nxt = target if dl is None else min(target, dl)
                now = clock()
                if now < nxt:
                    with annotate("wait_arrival"):
                        time.sleep(nxt - now)
                    now = clock()
                if dl is not None and dl <= target and now >= dl:
                    with annotate("flush"):
                        svc.flush(now=now)
                    continue
                break
            late[i] = clock() - target
            with annotate("submit"):
                svc.submit(self.apps[a], cfg, now=target)
        # the last requests wait for their batch's deadline, as any other
        while svc.pending_requests():
            now, dl = clock(), svc.next_deadline()
            if now < dl:
                with annotate("wait_arrival"):
                    time.sleep(dl - now)
            with annotate("flush"):
                svc.flush()
        t_end = clock()
        s1 = svc.stats()
        done = svc.completed[n0:]
        self.results = done
        lat = [r.latency_s for r in done]
        self.counters = {k: s1[k] - s0[k] for k in (
            "requests", "hits", "coalesced", "dispatched", "batches", "shed",
            "serialized", "recompiles")}
        self.late = late
        # requests still unanswered when the window closed, beyond the
        # batching wait that the last ones are due anyway
        close = t0 + self.seconds + svc.max_wait_s
        self.backlog = sum(r.t_done > close for r in done)
        self.run.log(
            f"serve window: {len(self.t)} requests at {self.rate} req/s over "
            f"{self.seconds} s; answered {len(done)}; last answer "
            f"{(t_end - t0 - self.seconds) * 1e3:.1f} ms after the window "
            f"closed; backlog at close {self.backlog}; counters "
            f"{self.counters}")
        self.run.log(
            f"generator lateness: p50 "
            f"{self.gen.percentile(late, 50) * 1e3:.3f} ms, p99 "
            f"{self.gen.percentile(late, 99) * 1e3:.3f} ms, max "
            f"{late.max() * 1e3:.3f} ms")
        self.run.log(f"latency: p50 {self.gen.percentile(lat, 50) * 1e3:.3f}"
                     f" ms, p95 {self.gen.percentile(lat, 95) * 1e3:.3f} ms,"
                     f" p99 {self.gen.percentile(lat, 99) * 1e3:.3f} ms over "
                     f"{len(lat)} requests")
        return {"serve_p50_ms": self.gen.percentile(lat, 50) * 1e3,
                "serve_p95_ms": self.gen.percentile(lat, 95) * 1e3}

    # ---- what the harness reads after the window ------------------------

    def attempted(self) -> int:
        return len(self.t)

    def answers(self):
        """``(request index, values)`` of every request answered for the
        app it asked about."""
        ok = self._answered()
        return ([r.uid for r in ok],
                np.asarray([(r.steady_ns, r.runtime_ns, r.speedup)
                            for r in ok], np.float64).reshape(-1, 3))

    def _answered(self):
        return [r for r in self.results if r.source != "shed"
                and 0 <= r.uid < len(self.t)
                and r.app == self.apps[self.app[r.uid]]]

    def missing(self) -> int:
        return len(self.t) - len({r.uid for r in self._answered()})

    def cell(self, uid: int):
        return self.apps[self.app[uid]], self.knobs[uid]

    def layer_data(self) -> dict:
        return {"counters": self.counters, "gen_late_s": self.late}

    def close(self):
        self.svc = None
