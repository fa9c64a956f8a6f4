"""Batched-vs-sequential equivalence and padding invariance for the engine.

The batched path shares the sequential scan step (flags are traced, padding
is timing-neutral), so agreement is expected to be bitwise; the asserts allow
1e-5 relative slack for XLA fusion differences, far inside the 1e-3 the
reproduction tolerates.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import isa, tracegen

APPS = sorted(tracegen.APPS)
TABLE10_GRID = [(m, l) for m in (8, 16, 32, 64, 128, 256)
                for l in (1, 2, 4, 8)]


def _close(a, b, tol=1e-5):
    assert abs(a - b) <= tol * max(abs(b), 1.0), (a, b)


def test_batch_matches_sequential_on_table10_grid():
    """Every Table-10 config x every app: simulate_batch == simulate."""
    pairs = [(app, eng.VectorEngineConfig(mvl=m, lanes=l))
             for app in APPS for m, l in TABLE10_GRID]
    traces = [tracegen.body_for(a, c.mvl, c).tile(2) for a, c in pairs]
    cfgs = [c for _, c in pairs]
    batched = eng.simulate_batch(traces, cfgs)
    for (app, cfg), tr, got in zip(pairs, traces, batched):
        want = eng.simulate(tr, cfg)
        for k in want:
            _close(got[k], want[k])


@pytest.mark.parametrize("ooo", [False, True])
@pytest.mark.parametrize("ic", ["ring", "crossbar"])
@pytest.mark.parametrize("l2_kb,mshrs", [(256, 16), (1024, 1)])
def test_batch_matches_sequential_flag_grid(ooo, ic, l2_kb, mshrs):
    """The formerly-static ooo/interconnect flags — and the memory-hierarchy
    knobs the analytic model made live — are traced selects and still
    produce sequential-identical results in a mixed batch."""
    cfgs = [eng.VectorEngineConfig(mvl=m, lanes=l, ooo_issue=ooo,
                                   interconnect=ic, l2_kb=l2_kb, mshrs=mshrs)
            for m, l in ((8, 1), (64, 4), (256, 8))]
    body = tracegen.body_for("jacobi-2d", 64, cfgs[0])
    recs = [isa.vreduce(128, src1=1, dst=2), isa.vslide(128, src1=2, dst=3)]
    tr = body.concat(isa.Trace.from_records(recs)).tile(3)
    for got, cfg in zip(eng.simulate_batch([tr], cfgs), cfgs):
        want = eng.simulate(tr, cfg)
        for k in want:
            _close(got[k], want[k])


def test_batch_broadcasts_and_preserves_order():
    cfg = eng.VectorEngineConfig(mvl=64, lanes=2)
    bodies = [tracegen.body_for(a, 64, cfg).tile(2)
              for a in ("blackscholes", "pathfinder", "streamcluster")]
    got = eng.simulate_batch(bodies, [cfg])
    for tr, row in zip(bodies, got):
        assert row["time"] == eng.simulate(tr, cfg)["time"]


def test_padding_invariance_exact():
    """Appending NOPs never changes any reported metric, bitwise."""
    for app, mvl in (("blackscholes", 64), ("canneal", 16),
                     ("particlefilter", 256)):
        cfg = eng.VectorEngineConfig(mvl=mvl, lanes=4)
        tr = tracegen.body_for(app, mvl, cfg).tile(2)
        base = eng.simulate(tr, cfg)
        for extra in (1, 17, 256):
            padded = eng.simulate(tr.pad_to(len(tr) + extra), cfg)
            assert padded == base, (app, extra)


def test_nop_trace_is_timing_neutral_alone():
    cfg = eng.VectorEngineConfig()
    out = eng.simulate(isa.nop_trace(64), cfg)
    assert out["time"] == 0.0 and out["lane_busy"] == 0.0


def test_pad_to_validates_and_roundtrips():
    tr = isa.Trace.from_records([isa.varith(8), isa.nop()])
    assert len(tr.pad_to(10)) == 10
    assert tr.pad_to(2) is tr
    with pytest.raises(ValueError):
        tr.pad_to(1)
    stacked = isa.stack_traces([tr, tr.pad_to(5)])
    assert stacked.kind.shape == (2, 5)


def test_steady_state_batch_matches_sequential():
    """The fused warmup-checkpoint scan equals the two-simulation recipe."""
    pairs = [("blackscholes", eng.VectorEngineConfig(mvl=64, lanes=4)),
             ("jacobi-2d", eng.VectorEngineConfig(mvl=256, lanes=8,
                                                  ooo_issue=True)),
             ("streamcluster", eng.VectorEngineConfig(mvl=8, lanes=1)),
             ("canneal", eng.VectorEngineConfig(mvl=16, lanes=2,
                                                interconnect="crossbar"))]
    bodies = [tracegen.body_for(a, c.mvl, c) for a, c in pairs]
    cfgs = [c for _, c in pairs]
    got = eng.steady_state_time_batch(bodies, cfgs, warmup=4, measure=8)
    for (app, cfg), body, g in zip(pairs, bodies, got):
        want = eng.steady_state_time(body, cfg, warmup=4, measure=8)
        _close(g, want)


def test_batch_reuses_compiled_executable():
    """Compilation is keyed on (batch bucket, CHUNK): new trace lengths and
    new flag combinations must NOT trigger a recompile."""
    cfg_a = eng.VectorEngineConfig(mvl=64, lanes=4)
    tr = tracegen.body_for("pathfinder", 64, cfg_a).tile(2)
    eng.simulate_batch([tr], [cfg_a, cfg_a])
    before = eng.jit_cache_size()
    longer = tr.tile(3)  # different length, same bucket arithmetic shape
    other = eng.VectorEngineConfig(mvl=128, lanes=8, ooo_issue=True,
                                   interconnect="crossbar")
    eng.simulate_batch([longer], [other, other])
    assert eng.jit_cache_size() == before


def test_empty_batches_return_empty():
    assert eng.simulate_batch([], []) == []
    assert eng.steady_state_time_batch([], []) == []


def test_single_trace_broadcasts_against_many_configs():
    cfg_grid = [eng.VectorEngineConfig(mvl=m, lanes=l)
                for m in (8, 64, 256) for l in (1, 8)]
    tr = tracegen.body_for("swaptions", 64, cfg_grid[0]).tile(2)
    rows = eng.simulate_batch([tr], cfg_grid)
    times = eng.steady_state_time_batch([tracegen.body_for("swaptions", 64,
                                                           cfg_grid[0])],
                                        cfg_grid, warmup=4, measure=8)
    assert len(rows) == len(times) == len(cfg_grid)
    for cfg, row, t in zip(cfg_grid, rows, times):
        want = eng.simulate(tr, cfg)
        for k in want:
            _close(row[k], want[k])
        _close(t, eng.steady_state_time(
            tracegen.body_for("swaptions", 64, cfg_grid[0]), cfg,
            warmup=4, measure=8))


def test_mixed_length_bucket_batch_matches_sequential():
    """Traces landing in different CHUNK buckets run as separate groups but
    must come back in input order, equal to sequential simulate."""
    cfg = eng.VectorEngineConfig(mvl=64, lanes=4)
    short = tracegen.body_for("pathfinder", 64, cfg)          # ~16 instrs
    mid = tracegen.body_for("blackscholes", 64, cfg).tile(4)  # ~1.2k
    long = tracegen.body_for("particlefilter", 64, cfg).tile(3)  # ~2.8k
    traces = [mid, short, long, short.tile(2)]
    buckets = {eng._len_bucket(len(t)) for t in traces}
    assert len(buckets) >= 2          # the premise: a genuinely mixed batch
    rows = eng.simulate_batch(traces, [cfg] * len(traces))
    for tr, row in zip(traces, rows):
        want = eng.simulate(tr, cfg)
        for k in want:
            _close(row[k], want[k])


def _copy(tr):
    """A content-equal Trace that is a distinct object."""
    return isa.Trace(**{k: getattr(tr, k).copy()
                        for k in isa.Trace.__dataclass_fields__})


def _shared_mix():
    """Five lanes (batch bucket 8, so three padding lanes) over two bodies:
    one body object shared by three lanes, one of those a content-equal
    copy, and a second body on two lanes."""
    cfgs = [eng.VectorEngineConfig(mvl=64, lanes=l, ooo_issue=o)
            for l, o in ((1, False), (4, False), (8, True), (2, False),
                         (4, True))]
    a = tracegen.body_for("jacobi-2d", 64, cfgs[0])
    b = tracegen.body_for("pathfinder", 64, cfgs[0])
    return [a, b, a, _copy(a), b], cfgs


def test_lanes_sharing_a_trace_match_sequential_bitwise():
    """Lanes that share a trace object read the same table row; every lane
    still equals its own sequential run bitwise, padding lanes included."""
    bodies, cfgs = _shared_mix()
    tiled = {id(t): t.tile(2) for t in bodies}
    traces = [tiled[id(t)] for t in bodies]
    for tr, cfg, row in zip(traces, cfgs, eng.simulate_batch(traces, cfgs)):
        assert row == eng.simulate(tr, cfg)
    got = eng.steady_state_time_batch(bodies, cfgs, warmup=4, measure=8)
    util = eng.steady_state_time_batch(bodies, cfgs, warmup=4, measure=8,
                                       with_util=True)
    for body, cfg, g, u in zip(bodies, cfgs, got, util):
        assert g == eng.steady_state_time(body, cfg, warmup=4, measure=8)
        assert u == eng.steady_state_time_batch(
            [body], [cfg], warmup=4, measure=8, with_util=True)[0]
        assert u["steady_ns"] == g


def test_fused_trace_is_built_once_per_distinct_body(monkeypatch):
    """``steady_state_time_batch`` tiles each distinct body object twice
    (warm-up and measurement), not each lane."""
    bodies, cfgs = _shared_mix()
    calls = []
    tile = isa.Trace.tile

    def counting_tile(self, n):
        calls.append(id(self))
        return tile(self, n)

    monkeypatch.setattr(isa.Trace, "tile", counting_tile)
    eng.steady_state_time_batch(bodies, cfgs, warmup=4, measure=8)
    assert len(calls) == 2 * len({id(b) for b in bodies})


def test_shared_lanes_add_no_executable():
    """A batch whose lanes share traces runs on the executable of its batch
    bucket, the one an all-distinct batch of that bucket compiled."""
    bodies, cfgs = _shared_mix()
    eng.steady_state_time_batch([_copy(b) for b in bodies], cfgs,
                                warmup=4, measure=8)
    before = eng.jit_cache_size()
    eng.steady_state_time_batch(bodies, cfgs, warmup=4, measure=8)
    eng.simulate_batch([bodies[0].tile(2)] * 3, cfgs[:3])
    assert eng.jit_cache_size() == before


def _host_gather(chunk, rows):
    """Each lane's row of a table chunk taken on the host and put on the
    default device: the per-lane fields the scan read before the gather
    moved onto the device."""
    rows = np.asarray(rows)
    return tuple(jnp.asarray(np.take(np.asarray(a), rows, axis=0))
                 for a in chunk)


def _distinct_mix(n):
    """``n`` lanes, each its own body object: copies of two bodies, taken
    in turn."""
    cfgs = [eng.VectorEngineConfig(mvl=64, lanes=(1, 2, 4, 8)[i % 4],
                                   ooo_issue=bool(i % 3))
            for i in range(n)]
    bodies = [_copy(tracegen.body_for(("jacobi-2d", "pathfinder")[i % 2],
                                      64, cfgs[0])) for i in range(n)]
    return bodies, cfgs


def _row_bucket_mix():
    """Twenty lanes over twelve distinct bodies: a row bucket (16) between
    the least (8) and the batch bucket (32)."""
    bodies, cfgs = _distinct_mix(12)
    return bodies + bodies[:8], cfgs + cfgs[:8]


# mix -> (lanes and configs, (row bucket, batch bucket))
MIXES = {"shared": (_shared_mix, (8, 8)),
         "distinct": (lambda: _distinct_mix(8), (8, 8)),
         "row_bucket": (_row_bucket_mix, (16, 32))}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_device_gather_matches_a_host_gather_bitwise(mix, monkeypatch):
    """Each chunk's lane fields gathered on the device from the table of
    distinct traces give the answers of the same fields gathered on the
    host, bitwise, in both batched entry points."""
    make, buckets = MIXES[mix]
    bodies, cfgs = make()
    rows = len({id(b) for b in bodies})
    assert (eng._pow2_bucket(rows), eng.batch_bucket(len(bodies))) == buckets
    tiled = {id(t): t.tile(2) for t in bodies}
    traces = [tiled[id(t)] for t in bodies]

    def both():
        return (eng.simulate_batch(traces, cfgs),
                eng.steady_state_time_batch(bodies, cfgs, warmup=4,
                                            measure=8, with_util=True))

    on_device = both()
    monkeypatch.setattr(eng, "_ONE_DEVICE",
                        eng._ONE_DEVICE._replace(gather=_host_gather))
    assert both() == on_device


# ---- the step's indexed reads and writes ----------------------------------
# The step reads and writes its rings, register table and FU cost tables by
# one-hot compare-and-select (``engine._read``, ``_write``, ``_lookup``).
# These are the gather and scatter forms they replaced.

def _gather_read(arr, idx):
    """``arr[idx]`` by a gather, 0.0 for an index below 0."""
    return jnp.where(idx >= 0, arr[jnp.maximum(idx, 0)], 0.0)


def _scatter_write(arr, idx, value, write):
    """A scatter of ``value`` at ``idx``, then a select of the whole array."""
    return jnp.where(write & (idx >= 0),
                     arr.at[jnp.maximum(idx, 0)].set(value), arr)


def _gather_lookup(table, idx):
    return jnp.asarray(table)[idx]


STEP_LANES = 64


def _step_lanes(seed=18):
    """``STEP_LANES`` lanes of two chunks of random records: every kind
    (NOPs as padding writes them), every FU class, register fields of -1,
    dependent scalar blocks; configs whose ROB, queue and rename
    capacities run from 1 to ``MAX_RING``."""
    rng = np.random.default_rng(seed)
    shape = (STEP_LANES, 2 * eng.CHUNK)
    kind = rng.integers(isa.SCALAR_BLOCK, isa.NOP + 1, shape)
    nop = kind == isa.NOP
    scalar = kind == isa.SCALAR_BLOCK
    reg = lambda: np.where(nop | (rng.random(shape) < 0.2), -1,
                           rng.integers(0, isa.N_ARCH_REGS, shape))
    fields = dict(
        kind=kind, vl=np.where(scalar | nop, 0, rng.integers(1, 257, shape)),
        fu=np.where(nop, isa.FU_SIMPLE,
                    rng.integers(0, isa.N_FU_CLASSES, shape)),
        n_src=np.where(nop, 0, rng.integers(0, 4, shape)),
        src1=reg(), src2=reg(), dst=reg(),
        mem_pattern=rng.integers(isa.MEM_UNIT, isa.MEM_INDEXED + 1, shape),
        footprint_kb=np.where(nop, 0.0, rng.uniform(1.0, 4096.0, shape)),
        scalar_count=np.where(scalar, rng.integers(0, 40, shape), 0),
        dep_scalar=scalar & (rng.random(shape) < 0.5))
    xs = tuple(np.asarray(fields[f], getattr(isa.nop_trace(1), f).dtype)
               for f in eng._TRACE_FIELDS)
    cfgs = [eng.VectorEngineConfig(
        mvl=256, lanes=int(rng.choice([1, 2, 4, 8])),
        rob_entries=int(rng.choice([1, 8, 40, eng.MAX_RING])),
        queue_entries=int(rng.choice([1, 4, 16, eng.MAX_RING])),
        phys_regs=32 + int(rng.choice([1, 8, 33, eng.MAX_RING])),
        ooo_issue=bool(i % 2), interconnect=("ring", "crossbar")[i // 2 % 2],
        vrf_read_ports=1 + i % 3 // 2, mshrs=int(rng.choice([1, 16])),
        l2_kb=int(rng.choice([256, 1024])), fusion=bool(i % 5 == 0),
        branch_miss_penalty=float(rng.choice([6.0, 12.0])))
        for i in range(STEP_LANES)]
    params = tuple(np.stack(col) for col in
                   zip(*(eng._cfg_params_np(c) for c in cfgs)))
    return xs, params


def _chunked_carry(program, xs, params):
    """Every carry field after both chunks, the carry threaded through."""
    carry = jax.tree.map(lambda a: jnp.zeros((STEP_LANES,) + a.shape, a.dtype),
                         eng._init_carry())
    for i in range(2):
        carry = program(carry, tuple(a[:, i * eng.CHUNK:(i + 1) * eng.CHUNK]
                                     for a in xs), params)
    return carry


@pytest.mark.parametrize("collect", [False, True])
def test_one_hot_step_matches_gather_scatter_bitwise(collect, monkeypatch):
    """The step's one-hot reads and writes give every carry field (and, in
    the collect_stats scan, every accumulator and timeline output) of the
    gather and scatter forms bitwise, rings wrapped many times over."""
    xs, params = _step_lanes()
    if collect:
        run = lambda: jax.jit(jax.vmap(eng._profile_core))(xs, params)
        got = run()
    else:
        got = _chunked_carry(eng._chunk_batch_jit, xs, params)
        for n in (got[2], got[4], got[6], got[8]):  # ROB, rename, AQ, MQ
            assert int(n.min()) >= 2 * eng.MAX_RING
        run = lambda: _chunked_carry(jax.jit(jax.vmap(eng._chunk_core)),
                                     xs, params)
    monkeypatch.setattr(eng, "_read", _gather_read)
    monkeypatch.setattr(eng, "_write", _scatter_write)
    monkeypatch.setattr(eng, "_lookup", _gather_lookup)
    want = run()
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert np.array_equal(np.asarray(g), np.asarray(w)), i


def test_chunk_program_has_no_gather_or_scatter():
    """The batched chunk scan at a study's batch of 2,048 lanes reads and
    writes its indexed state without a gather or a scatter."""
    body = isa.nop_trace(1)
    sds = lambda shape, a: jax.ShapeDtypeStruct(shape, np.asarray(a).dtype)
    carry = tuple(sds((2048,) + a.shape, a) for a in eng._init_carry())
    xs = tuple(sds((2048, eng.CHUNK), getattr(body, f))
               for f in eng._TRACE_FIELDS)
    params = tuple(sds((2048,), p) for p in
                   eng._cfg_params_np(eng.VectorEngineConfig()))
    text = eng._chunk_batch_jit.lower(carry, xs, params).as_text()
    assert "stablehlo.while" in text
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text
