"""Rows of the engine's tables of distinct traces (one per distinct trace
object of each batch group) per 1,000 cells of the traced studies: the
program's ``engine.trace_rows`` counter."""

from chipbench import program


def read(ctx):
    c = program.window_counters(ctx)
    if not c or "engine.trace_rows" not in c:
        return None
    cells = sum(s["cells"] for s in ctx.data["studies"])
    return c["engine.trace_rows"] / (cells / 1e3)
