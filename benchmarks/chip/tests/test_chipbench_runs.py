"""Whole runs of CPU-sized cells below the harness's look for a chip: a
sound run is correct, the bfloat16 control is rejected, and each fault of
the timed path that a cell can have turns ``correct`` false."""
import contextlib
import time

import numpy as np
import pytest

from chipbench import compare, harness, reference, spec

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    import jax
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def run_cell(root, name, seconds=1.0, trace=False, seed=2 ** 31 + 77):
    cell = spec.Cell(name, root=str(root))
    run = harness.Run(cell, seed, seconds, trace, time.perf_counter())
    return harness.execute(run, CPU, compile_cache=False)


@pytest.mark.parametrize("name", ["tiny-sweep", "tiny-serve"])
def test_sound_run_is_correct(checkout, name):
    res = run_cell(checkout, name)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {
        "tiny-sweep": {"cells_per_s", "setup_s"},
        "tiny-serve": {"serve_p50_ms", "serve_p95_ms", "setup_s"}}[name]


def test_traced_run_reads_the_layers(checkout):
    res = run_cell(checkout, "tiny-sweep", trace=True)
    assert res["correct"] is True
    assert {"sweep.key_ms_per_kcell", "sweep.derive_ms_per_kcell",
            "sweep.dispatch_ms_per_kcell"} <= set(res["metrics"])
    # no device plane on the CPU: the device readers find nothing to read
    assert "device_idle_share.sweep" not in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])


def test_bfloat16_control_is_rejected():
    cfg = spec.load_json(f"{spec.BENCH_DIR}/configs/rivec-spacefull.json")
    cells = [(app, spec.config_at(cfg, k))
             for app in ("jacobi-2d", "pathfinder", "streamcluster")
             for k in (0, 700, 1535)]
    ref = reference.answers(cells, 8, 24)
    ctl = reference.answers(cells, 8, 24, dtype="bfloat16")
    sound = compare.check(np.stack(ref, 1), ref, 0, cfg["limits"])
    assert compare.passed(sound)
    checks = compare.check(np.stack(ctl, 1), ref, 0, cfg["limits"])
    assert not compare.passed(checks), checks


# --------------------------------------------------------------------------
# faults planted in the timed path
# --------------------------------------------------------------------------

@contextlib.contextmanager
def patched(owner, attr, make):
    orig = getattr(owner, attr)
    setattr(owner, attr, make(orig))
    try:
        yield
    finally:
        setattr(owner, attr, orig)


def state_unchanged(orig):
    return lambda carry, xs, params, bb: carry


def half_batch_mean(orig):
    def f(bodies, cfgs, **kw):
        half = max(len(bodies) // 2, 1)
        out = orig(bodies[:half], cfgs[:half], **kw)
        return out + [float(np.mean(out))] * (len(bodies) - half)
    return f


def exchange_left_out(orig):
    """The sharded config axis with only the first device's shard brought
    back: every quarter of the batch gets the first quarter's state."""
    import jax
    import jax.numpy as jnp

    def f(carry, xs, params, bb):
        out = orig(carry, xs, params, bb)
        q = max(bb // 4, 1)
        return jax.tree.map(
            lambda a: jnp.concatenate([a[:q]] * (bb // q))[:bb], out)
    return f


def answer_altered(orig):
    def f(bodies, cfgs, **kw):
        out = list(orig(bodies, cfgs, **kw))
        out[0] = out[0] * (1.0 + 1e-3)
        return out
    return f


FAULTS = {
    "state_unchanged": ("_dispatch_chunk_batch", state_unchanged),
    "half_batch_mean": ("steady_state_time_batch", half_batch_mean),
    "exchange_left_out": ("_dispatch_chunk_batch", exchange_left_out),
    "answer_altered": ("steady_state_time_batch", answer_altered),
}


# the exchange between chips exists in the sweep's four-chip cell only
CASES = [(f, c) for f in sorted(FAULTS) for c in ("tiny-sweep", "tiny-serve")
         if not (f == "exchange_left_out" and c == "tiny-serve")]


@pytest.mark.parametrize("fault,name", CASES)
def test_fault_in_the_timed_path_is_not_correct(checkout, name, fault):
    from repro.core import engine as eng

    attr, make = FAULTS[fault]
    with patched(eng, attr, make):
        res = run_cell(checkout, name)
    assert res["correct"] is False, res["checks"]
