"""The engine's table of distinct traces in a traced run of the CPU-sized
sweep cell: the gather span and the trace-rows counter are read, and the
counter holds one row per distinct body object of each length group."""
import pytest

from chipbench import spec
from test_program_spans import (  # noqa: F401  (the autouse fixture)
    _no_persistent_cache, distinct_cells, traced_sweep)


def test_trace_rows_are_the_distinct_bodies_per_group(checkout):
    """One table row per distinct body object of each length group: the
    memoised bodies that the study's cells share."""
    from repro.core import engine as eng

    cell, res, counters = traced_sweep(checkout)
    assert res["correct"] is True, res["checks"]
    for name in ("sweep.gather_ms_per_kcell", "sweep.trace_rows_per_kcell"):
        assert res["metrics"][name]["value"] > 0, name
    w, m = cell.config["warmup_tiles"], cell.config["measure_tiles"]
    groups: dict = {}
    for body in distinct_cells(cell):
        fused = eng.trace_len_bucket(w * len(body)) + m * len(body)
        groups.setdefault(eng.trace_len_bucket(fused), set()).add(id(body))
    rows = sum(len(ids) for ids in groups.values())
    assert counters["engine.trace_rows"] == rows
    assert rows < len(distinct_cells(cell))
    cells = spec.space_size(cell.config) * len(cell.config["apps"])
    assert res["metrics"]["sweep.trace_rows_per_kcell"]["value"] \
        == pytest.approx(rows / (cells / 1e3))
