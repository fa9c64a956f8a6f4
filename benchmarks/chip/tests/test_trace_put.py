"""The trace bytes the engine places on the devices in a traced run of the
CPU-sized sweep cell: per length group the lanes' row indices and the
group's table of distinct traces, padded to its row bucket, chunk by chunk;
and nothing read on a program without the counter."""
import pytest

from chipbench import program, spec
from test_program_spans import (  # noqa: F401  (the autouse fixture)
    _no_persistent_cache, distinct_cells, traced_sweep)


def test_trace_put_is_the_table_shapes_per_group(checkout):
    from repro.core import engine as eng
    from repro.core import isa

    cell, res, counters = traced_sweep(checkout)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["sweep.gather_ms_per_kcell"]["value"] > 0
    w, m = cell.config["warmup_tiles"], cell.config["measure_tiles"]
    lanes: dict = {}
    rows: dict = {}
    for body in distinct_cells(cell):
        fused = eng.trace_len_bucket(w * len(body)) + m * len(body)
        length = eng.trace_len_bucket(fused)
        lanes[length] = lanes.get(length, 0) + 1
        rows.setdefault(length, set()).add(id(body))
    nop = isa.nop_trace(1)
    step = sum(getattr(nop, f).itemsize for f in eng._TRACE_FIELDS)
    put = sum(4 * eng.batch_bucket(lanes[n])
              + eng._pow2_bucket(len(rows[n])) * n * step for n in lanes)
    assert counters["engine.trace_bytes_put"] == put
    cells = spec.space_size(cell.config) * len(cell.config["apps"])
    assert res["metrics"]["sweep.trace_put_mb_per_kcell"]["value"] \
        == pytest.approx(put / 1e6 / (cells / 1e3))


def test_a_program_without_the_counter_gives_nothing(checkout, monkeypatch):
    read = spec.Cell("tiny-sweep", root=str(checkout)).reader(
        "sweep.trace_put_mb_per_kcell")
    monkeypatch.setattr(program, "window_counters",
                        lambda ctx: {"engine.trace_rows": 3})

    class Ctx:
        data = {"studies": [{"cells": 32}]}

    assert read(Ctx()) is None
