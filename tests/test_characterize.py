"""Regression: the count models reproduce the paper's Tables 3-9 and VAO claims."""
import pytest

from repro.core import characterize as ch

DENSE_TOL = 0.011   # <=1.1% on every published cell
CANNEAL_TOL = 0.08  # fitted empirical multipliers; worst cell 7%


@pytest.mark.parametrize("app", list(ch.PAPER_TABLES))
def test_tables_match_paper(app):
    tol = CANNEAL_TOL if app == "canneal" else DENSE_TOL
    for row in ch.compare_to_paper(app):
        for k, v in row.items():
            if k.startswith("err"):
                assert v <= tol, (app, row["mvl"], k, v)


@pytest.mark.parametrize("app,vao", list(ch.PAPER_VAO.items()))
def test_vao_speedups(app, vao):
    got = ch.characterize(app, 8).vao_speedup
    assert abs(got - vao) <= 0.04, (app, got, vao)


def test_blackscholes_pct_vectorization():
    # paper Table 3: 80% / 86% / 87%
    for mvl, pct in [(8, 0.80), (64, 0.86), (256, 0.87)]:
        got = ch.characterize("blackscholes", mvl).pct_vectorization
        assert abs(got - pct) < 0.015, (mvl, got)


def test_swaptions_pct_vectorization():
    # paper Table 9: 81% / 96% / 98%
    for mvl, pct in [(8, 0.81), (64, 0.96), (256, 0.98)]:
        got = ch.characterize("swaptions", mvl).pct_vectorization
        assert abs(got - pct) < 0.015, (mvl, got)


def test_canneal_avg_vl():
    # paper Table 4: average VL 22.25 @64, 65.41 @256
    assert abs(ch.characterize("canneal", 64).avg_vl - 22.25) < 0.7
    assert abs(ch.characterize("canneal", 256).avg_vl - 65.41) < 2.0


@pytest.mark.parametrize("app", sorted(ch.PAPER_TABLES))
def test_pct_vectorization_increases_with_mvl(app):
    a = ch.characterize(app, 8).pct_vectorization
    b = ch.characterize(app, 256).pct_vectorization
    assert b >= a, app


# --------------------------------------------------------------------------
# ISSUE-8 satellite: unit tests for the §4.1.1 closed forms on synthetic
# Counts — the surrogate's trace features build on these three definitions,
# so they get exact (hand-computable) coverage independent of the app models.
# --------------------------------------------------------------------------

def _synth(scalar_code_total=1000.0, scalar_instrs=200.0, vector_mem=30.0,
           vector_arith=60.0, vector_manip=10.0, vector_ops=800.0):
    from repro.core.tracegen import Counts
    return ch.Characterization(
        "synthetic", 64,
        Counts(scalar_code_total=scalar_code_total,
               scalar_instrs=scalar_instrs, vector_mem=vector_mem,
               vector_arith=vector_arith, vector_manip=vector_manip,
               vector_ops=vector_ops))


def test_pct_vectorization_definition():
    # vector_ops / (scalar_instrs + vector_ops) = 800 / 1000
    assert _synth().pct_vectorization == 0.8
    # no vector work at all -> 0
    assert _synth(vector_ops=0.0).pct_vectorization == 0.0


def test_avg_vl_definition():
    # vector_ops / total_vector_instrs = 800 / (30 + 60 + 10)
    assert _synth().avg_vl == 8.0
    # the max(..., 1) guard: a scalar-only characterization divides by 1,
    # not by zero
    c = _synth(vector_mem=0.0, vector_arith=0.0, vector_manip=0.0,
               vector_ops=0.0)
    assert c.avg_vl == 0.0


def test_vao_speedup_definition():
    # scalar_code_total / (scalar_instrs + vector_ops) = 1000 / 1000
    assert _synth().vao_speedup == 1.0
    # halving the vectorized-code instruction count doubles the VAO speedup
    assert _synth(scalar_instrs=100.0, vector_ops=400.0).vao_speedup == 2.0


def test_row_is_consistent_with_properties():
    c = _synth()
    row = c.row()
    assert row["pct_vectorization"] == c.pct_vectorization
    assert row["average_vl"] == c.avg_vl
    assert row["vao_speedup"] == c.vao_speedup
    assert row["total_vector_instructions"] == 100.0
    assert row["total_instructions"] == 300.0


def test_compare_to_paper_smoke_row():
    """compare_to_paper emits one row per golden MVL with every err_* field
    populated and finite — the smoke row the satellite asks for."""
    rows = ch.compare_to_paper("blackscholes")
    assert [r["mvl"] for r in rows] == [8, 64, 256]
    for r in rows:
        assert r["app"] == "blackscholes"
        for k in ("err_total", "err_scalar", "err_mem", "err_arith",
                  "err_ops"):
            assert 0.0 <= r[k] < 0.02, (r["mvl"], k, r[k])
