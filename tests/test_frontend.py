"""Jaxpr→vector-IR frontend: lowering unit tests + the cross-validation
contract (derived bodies vs hand-coded tracegen bodies) + the three
frontend-only ML workloads."""
import jax
import jax.numpy as jnp
import jax.scipy.special as jsp
import numpy as np
import pytest
from jax import lax

from repro.core import engine as eng
from repro.core import frontend as fe
from repro.core import isa, tracegen


def _kinds(tr):
    return {isa.KIND_NAMES[k]: int(n)
            for k, n in enumerate(isa.kind_histogram(tr)) if n}


# ---------------------------------------------------------------- lowering

def test_elementwise_fu_classes():
    def fn(a, b):
        x = a + b                  # simple
        y = x * b                  # mul
        z = y / a                  # div
        return jnp.exp(z)          # trans

    tr = fe.lower_trace([fe.KernelBody(fn, 64,
                                       ins=(fe.Stream("a", 8.0),
                                            fe.Stream("b", 8.0)),
                                       outs=(fe.Stream("o", 8.0),))])
    assert _kinds(tr) == {"load": 2, "arith": 4, "store": 1}
    fus = tr.fu[tr.kind == isa.VARITH]
    assert list(fus) == [isa.FU_SIMPLE, isa.FU_MUL, isa.FU_DIV, isa.FU_TRANS]
    assert all(tr.vl[tr.kind != isa.SCALAR_BLOCK] == 64)


def test_roll_lowers_to_slide_and_reduce_to_vreduce():
    def fn(a):
        s = jnp.roll(a, 1)
        return jnp.sum(s + a)

    tr = fe.lower_trace([fe.KernelBody(fn, 32, ins=(fe.Stream("a", 8.0),))])
    assert _kinds(tr) == {"load": 1, "slide": 1, "arith": 1, "reduce": 1}
    assert tr.vl[tr.kind == isa.VREDUCE][0] == 32


def test_bool_reduction_is_mask_to_scalar():
    def fn(a):
        return jnp.any(a > 0.0), jnp.all(a > 1.0)

    tr = fe.lower_trace([fe.KernelBody(fn, 16, ins=(fe.Stream("a", 8.0),))])
    k = _kinds(tr)
    assert k["mask2s"] == 2 and k["arith"] == 2  # two compares, two vfirst/vpopc


def test_cumsum_expands_to_slide_add_ladder():
    tr = fe.lower_trace([fe.KernelBody(lambda a: jnp.cumsum(a), 64,
                                       ins=(fe.Stream("a", 8.0),))])
    k = _kinds(tr)
    assert k["slide"] == 6 and k["arith"] == 6   # ceil(log2(64)) rounds


# How a kernel spells each primitive of the frontend's tables in jnp (lax
# where jnp has no spelling that reaches the primitive).  A JAX release that
# renames a primitive, or wraps it in a new call primitive, fails its case
# here instead of failing every app that happens to use it.
def _i32(x):
    return x.astype(jnp.int32)


PRIM_SPELLING = {
    "add": lambda a, b: a + b,
    "add_any": lambda a, b: jax.vmap(jax.grad(lambda x: x * x + x))(a),
    "sub": lambda a, b: a - b,
    "max": jnp.maximum,
    "min": jnp.minimum,
    "neg": lambda a, b: -a,
    "abs": lambda a, b: jnp.abs(a),
    "and": lambda a, b: (a > 0) & (b > 0),
    "or": lambda a, b: (a > 0) | (b > 0),
    "xor": lambda a, b: (a > 0) ^ (b > 0),
    "not": lambda a, b: ~(a > 0),
    "gt": lambda a, b: a > b,
    "lt": lambda a, b: a < b,
    "ge": lambda a, b: a >= b,
    "le": lambda a, b: a <= b,
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
    "select_n": lambda a, b: jnp.where(a > b, a, b),
    "sign": lambda a, b: jnp.sign(a),
    "floor": lambda a, b: jnp.floor(a),
    "ceil": lambda a, b: jnp.ceil(a),
    "round": lambda a, b: jnp.round(a),
    "clamp": lambda a, b: lax.clamp(0.0, a, 1.0),
    "is_finite": lambda a, b: jnp.isfinite(a),
    "shift_left": lambda a, b: jnp.left_shift(_i32(a), 1),
    "shift_right_logical":
        lambda a, b: jnp.right_shift(a.astype(jnp.uint32), 1),
    "shift_right_arithmetic": lambda a, b: jnp.right_shift(_i32(a), 1),
    "mul": lambda a, b: a * b,
    "integer_pow": lambda a, b: a ** 3,
    "square": lambda a, b: jnp.square(a),
    "div": lambda a, b: a / b,
    "sqrt": lambda a, b: jnp.sqrt(a),
    "rsqrt": lambda a, b: lax.rsqrt(a),
    "rem": lambda a, b: jnp.fmod(a, b),
    "exp": lambda a, b: jnp.exp(a),
    "exp2": lambda a, b: jnp.exp2(a),
    "log": lambda a, b: jnp.log(a),
    "log1p": lambda a, b: jnp.log1p(a),
    "expm1": lambda a, b: jnp.expm1(a),
    "erf": lambda a, b: jsp.erf(a),
    "erfc": lambda a, b: jsp.erfc(a),
    "erf_inv": lambda a, b: jsp.erfinv(a),
    "sin": lambda a, b: jnp.sin(a),
    "cos": lambda a, b: jnp.cos(a),
    "tan": lambda a, b: jnp.tan(a),
    "asin": lambda a, b: jnp.arcsin(a),
    "acos": lambda a, b: jnp.arccos(a),
    "atan": lambda a, b: jnp.arctan(a),
    "atan2": jnp.arctan2,
    "sinh": lambda a, b: jnp.sinh(a),
    "cosh": lambda a, b: jnp.cosh(a),
    "tanh": lambda a, b: jnp.tanh(a),
    "logistic": lambda a, b: jax.nn.sigmoid(a),
    "pow": jnp.power,
    "cbrt": lambda a, b: jnp.cbrt(a),
    "reduce_sum": lambda a, b: jnp.sum(a),
    "reduce_max": lambda a, b: jnp.max(a),
    "reduce_min": lambda a, b: jnp.min(a),
    "reduce_prod": lambda a, b: jnp.prod(a),
    "cumsum": lambda a, b: jnp.cumsum(a),
    "cummax": lambda a, b: lax.cummax(a),
    "cummin": lambda a, b: lax.cummin(a),
    "cumprod": lambda a, b: jnp.cumprod(a),
    "cumlogsumexp": lambda a, b: lax.cumlogsumexp(a),
}


def _primitives(jaxpr, out):
    """Every primitive name in ``jaxpr``, nested call bodies included."""
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


def test_every_table_primitive_has_a_spelling():
    assert set(PRIM_SPELLING) == (set(fe.FU_OF_PRIM) | set(fe.REDUCE_FU)
                                  | set(fe.CUMULATIVE_FU))


@pytest.mark.parametrize("prim", sorted(PRIM_SPELLING))
def test_table_primitive_lowers_on_installed_jax(prim):
    fn = PRIM_SPELLING[prim]
    x = jnp.ones(16, jnp.float32)
    assert prim in _primitives(jax.make_jaxpr(fn)(x, x).jaxpr, set())
    tr = fe.lower_trace([fe.KernelBody(fn, 16, ins=(fe.Stream("a", 8.0),
                                                    fe.Stream("b", 8.0)))])
    if prim in fe.REDUCE_FU:
        kind, fu = isa.VREDUCE, fe.REDUCE_FU[prim]
    else:
        kind = isa.VARITH
        fu = fe.FU_OF_PRIM.get(prim, fe.CUMULATIVE_FU.get(prim))
    assert np.any((tr.kind == kind) & (tr.fu == fu)), prim
    if prim in fe.CUMULATIVE_FU:
        assert np.any(tr.kind == isa.VSLIDE), prim


def test_gather_becomes_indexed_load_with_stream_footprint():
    def fn(x, i):
        idx = jnp.clip(i, 0.0, 7.0).astype(jnp.int32)
        return x[idx]

    tr = fe.lower_trace([fe.KernelBody(fn, 8,
                                       ins=(fe.Stream("table", 3072.0),
                                            fe.Stream("idx", 8.0),))])
    gathers = (tr.kind == isa.VLOAD) & (tr.mem_pattern == isa.MEM_INDEXED)
    assert gathers.sum() == 1
    assert tr.footprint_kb[gathers][0] == np.float32(3072.0)


def test_scalar_eqns_coalesce_and_dep_on_reductions():
    def fn(a):
        m = jnp.sum(a)             # VREDUCE, result handed to scalar core
        c = m * 2.0 + 1.0          # two rank-0 eqns -> one dep SCALAR_BLOCK
        return a + c               # broadcast back into a vector op

    tr = fe.lower_trace([fe.KernelBody(fn, 16, ins=(fe.Stream("a", 8.0),))])
    blocks = tr.kind == isa.SCALAR_BLOCK
    assert blocks.sum() == 1
    assert tr.scalar_count[blocks][0] == 2
    assert tr.dep_scalar[blocks][0]


def test_stream_patterns_and_declared_scalar_work():
    segs = [fe.ScalarWork(12.6, dep_scalar=True),
            fe.KernelBody(lambda a, b: a + b, 8,
                          ins=(fe.Stream("u", 64.0),
                               fe.Stream("s", 64.0, pattern=isa.MEM_STRIDED)),
                          outs=(fe.Stream("o", 64.0),))]
    tr = fe.lower_trace(segs)
    assert tr.scalar_count[0] == 13 and tr.dep_scalar[0]
    loads = tr.mem_pattern[tr.kind == isa.VLOAD]
    assert sorted(loads) == [isa.MEM_UNIT, isa.MEM_STRIDED]


def test_named_values_cross_segments():
    segs = [fe.KernelBody(lambda a: a * a, 8,
                          ins=(fe.Stream("a", 8.0),), outs=("sq",)),
            fe.KernelBody(lambda sq: jnp.sum(sq), 8, ins=("sq",))]
    tr = fe.lower_trace(segs)
    assert _kinds(tr) == {"load": 1, "arith": 1, "reduce": 1}
    # the reduce reads the register the first segment's result lives in
    arith = np.flatnonzero(tr.kind == isa.VARITH)[0]
    red = np.flatnonzero(tr.kind == isa.VREDUCE)[0]
    assert tr.src1[red] == tr.dst[arith]


def test_register_pressure_errors_and_lazy_loads():
    n = fe.N_LOGICAL_REGS + 4
    streams = tuple(fe.Stream(f"s{i}", 8.0) for i in range(n))

    def fold(*xs):
        acc = xs[0]
        for x in xs[1:]:
            acc = acc + x
        return acc

    def hold(*xs):                       # all streams live until the end
        acc = xs[0]
        for x in xs[1:]:
            acc = acc + x
        return tuple(xs)

    outs = tuple(fe.Stream(f"o{i}", 8.0) for i in range(n))
    with pytest.raises(fe.FrontendError, match="register pressure"):
        fe.lower_trace([fe.KernelBody(hold, 8, ins=streams, outs=outs)])
    low = fe.lower([fe.KernelBody(fold, 8, ins=streams, lazy_loads=True)])
    assert low.max_live <= 4
    assert _kinds(low.trace) == {"load": n, "arith": n - 1}


def test_unknown_primitive_is_loud():
    with pytest.raises(fe.FrontendError, match="no vector-IR mapping"):
        fe.lower_trace([fe.KernelBody(
            lambda a: jnp.dot(a.reshape(4, 2), a.reshape(2, 4)), 8,
            ins=(fe.Stream("a", 8.0),))])


def test_unused_blocks_are_still_fetched():
    tr = fe.lower_trace([fe.KernelBody(lambda a, b: a * 2.0, 8,
                                       ins=(fe.Stream("a", 8.0),
                                            fe.Stream("b", 8.0)),
                                       lazy_loads=True)])
    assert _kinds(tr)["load"] == 2       # block-spec semantics: b fetched too


# --------------------------------------- differential: random jax kernels vs
# a reference interpreter over the jaxpr


# The random-kernel op pool: a subset of the supported primitive set whose
# jaxpr spelling is stable (each entry is (callable, jaxpr primitive name)).
_OP_POOL = (
    (lambda a, b: a + b, "add"),
    (lambda a, b: a - b, "sub"),
    (lambda a, b: jnp.maximum(a, b), "max"),
    (lambda a, b: jnp.minimum(a, b), "min"),
    (lambda a, b: a * b, "mul"),
    (lambda a, b: a / b, "div"),
    (lambda a, b: jnp.sqrt(a) + b * 0, "sqrt"),
    (lambda a, b: jnp.exp(a) + b * 0, "exp"),
    (lambda a, b: jnp.tanh(a) + b * 0, "tanh"),
)
_TERMINALS = ("none", "sum", "roll", "cumsum", "any")


def _random_kernel(seed, n_ops=6, n_ins=2):
    """A random elementwise kernel from the supported primitive set: the op
    sequence and operand wiring are drawn *outside* the traced function, so
    the same structure is replayed identically at trace time."""
    rng = np.random.RandomState(seed)
    plan = [(int(rng.randint(len(_OP_POOL))),
             int(rng.randint(n_ins + i)), int(rng.randint(n_ins + i)))
            for i in range(n_ops)]
    terminal = _TERMINALS[rng.randint(len(_TERMINALS))]

    def fn(*ins):
        vals = list(ins)
        for op_i, s1, s2 in plan:
            vals.append(_OP_POOL[op_i][0](vals[s1], vals[s2]))
        out = vals[-1]
        if terminal == "sum":
            return jnp.sum(out)
        if terminal == "roll":
            return jnp.roll(out, 1) + out
        if terminal == "cumsum":
            return jnp.cumsum(out)
        if terminal == "any":
            return jnp.any(out > 0.0)
        return out

    return fn, terminal


def _reference_counts(jaxpr, vl):
    """Independent reference interpreter over a jaxpr: predicts the lowered
    trace's kind/FU/element totals by walking equations directly — no
    walker state, no register allocation, no scalar coalescing — so a
    bookkeeping bug in the lowering pipeline cannot cancel itself out."""
    fu_hist = np.zeros(4, int)
    counts = {"slide": 0, "reduce": 0, "mask": 0, "elems": 0}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in fe.CALL_PRIMS:
            p = eqn.params
            inner = next(p[k] for k in ("jaxpr", "call_jaxpr", "fun_jaxpr")
                         if k in p)
            sub_fu, sub_counts = _reference_counts(
                inner.jaxpr if hasattr(inner, "jaxpr") else inner, vl)
            fu_hist += sub_fu
            for k in counts:
                counts[k] += sub_counts[k]
        elif name in fe.SKIP_PRIMS:
            continue
        elif name in fe.CUMULATIVE_FU:
            rounds = max(1, int(np.ceil(np.log2(max(vl, 2)))))
            counts["slide"] += rounds
            fu_hist[fe.CUMULATIVE_FU[name]] += rounds
            counts["elems"] += 2 * rounds * vl
        elif name in fe.REDUCE_FU:
            counts["reduce"] += 1
            counts["elems"] += vl
        elif name in fe.MASK_PRIMS:
            counts["mask"] += 1
            counts["elems"] += vl
        elif name in fe.SLIDE_PRIMS:
            counts["slide"] += 1
            counts["elems"] += vl
        elif name in fe.FU_OF_PRIM:
            if eqn.outvars[0].aval.shape:
                fu_hist[fe.FU_OF_PRIM[name]] += 1
                counts["elems"] += vl
        else:  # a pool op lowering to an unexpected primitive
            raise AssertionError(f"unmapped primitive {name!r}")
    return fu_hist, counts


@pytest.mark.parametrize("seed", range(12))
def test_differential_random_kernels_match_reference(seed):
    """Random small kernels from the supported primitive set: the full
    lowering pipeline (walker -> register allocator -> TraceBuilder) must
    produce exactly the FU/kind/element/pattern mix the reference
    interpreter reads off the jaxpr."""
    import jax
    vl = int((16, 64)[seed % 2])
    fn, terminal = _random_kernel(seed)
    ins = tuple(fe.Stream(f"s{i}", 64.0) for i in range(2))
    tr = fe.lower_trace([fe.KernelBody(fn, vl, ins=ins)])

    avals = [jax.ShapeDtypeStruct((vl,), jnp.float32) for _ in ins]
    ref_fu, ref = _reference_counts(jax.make_jaxpr(fn)(*avals).jaxpr, vl)

    got_fu = np.bincount(tr.fu[tr.kind == isa.VARITH], minlength=4)
    assert list(got_fu) == list(ref_fu), (terminal, got_fu, ref_fu)
    assert int((tr.kind == isa.VSLIDE).sum()) == ref["slide"]
    assert int((tr.kind == isa.VREDUCE).sum()) == ref["reduce"]
    assert int((tr.kind == isa.VMASK_SCALAR).sum()) == ref["mask"]
    # loads come only from the declared streams; element work matches
    loads = tr.kind == isa.VLOAD
    assert int(loads.sum()) == len(ins)
    assert all(tr.mem_pattern[loads] == isa.MEM_UNIT)
    vec = (tr.kind != isa.SCALAR_BLOCK) & ~loads & (tr.kind != isa.VSTORE)
    assert int(tr.vl[vec].sum()) == ref["elems"], terminal


# ------------------------------------------------- the cross-validation gate

def test_cross_validation_all_rivec_apps():
    """ISSUE acceptance: derived traces match all 7 hand-coded bodies —
    instruction-kind mix exact, steady-state time within 5%."""
    reports = fe.cross_validate_all()
    assert {r.app for r in reports} == set(tracegen.RIVEC_APPS)
    bad = [(r.app, r.time_rel_err) for r in reports if not r.ok]
    assert not bad, bad
    for r in reports:
        assert r.kinds_ok and r.fu_ok and r.pattern_ok
        assert r.elems_ok and r.scalar_ok and r.pressure_ok


# ------------------------------------------------- frontend-only workloads

ML_APPS = ("flash_attention", "decode_attention", "ssd_scan")


def test_ml_workloads_registered_and_lowerable():
    for app in ML_APPS:
        a = tracegen.APPS[app]
        assert a.kernel is not None
        tr = tracegen.body_for(app, 64, eng.VectorEngineConfig(mvl=64, lanes=4))
        kinds = _kinds(tr)
        assert kinds.get("load", 0) > 0 and kinds.get("arith", 0) > 0
        counts = a.counts(64)
        assert counts.vector_ops > 0 and counts.total_vector > 0
        assert 0.99 < sum(a.mix.values()) < 1.01


def test_ml_workload_profiles():
    """The three workloads stress distinct modules: ssd the slide ladder,
    the attention kernels reductions + the scalar round trip."""
    cfg = eng.VectorEngineConfig(mvl=64, lanes=4)
    fa = tracegen.body_for("flash_attention", 64, cfg)
    da = tracegen.body_for("decode_attention", 64, cfg)
    ssd = tracegen.body_for("ssd_scan", 64, cfg)
    assert (ssd.kind == isa.VSLIDE).sum() >= 6          # cumsum ladder
    for tr in (fa, da):
        assert (tr.kind == isa.VREDUCE).sum() > 32      # per-dim dots
        assert tr.dep_scalar.sum() >= 1                 # m/l scalar update
    assert ((da.kind == isa.VLOAD)
            & (da.mem_pattern == isa.MEM_STRIDED)).sum() > 0


def test_ml_workloads_in_full_sweep():
    from repro.core import suite
    table = suite.sweep_all(ML_APPS, mvls=(8, 256), lanes=(1, 8))
    for app in ML_APPS:
        for v in table[app].values():
            assert np.isfinite(v) and v > 0
    # decode is DRAM-bandwidth bound: lanes buy almost nothing
    d = table["decode_attention"]
    assert d[(256, 8)] / d[(256, 1)] < 1.3
    # ssd scales with lanes at large MVL (compute bound)
    s = table["ssd_scan"]
    assert s[(256, 8)] / s[(256, 1)] > 2.0
