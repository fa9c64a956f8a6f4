"""Trace bytes the engine placed on the devices, in MB per 1,000 cells of
the traced studies: the program's ``engine.trace_bytes_put`` counter (each
batch group's lane rows, and each chunk of its table of distinct traces
once per device that holds it).  A program without the counter gives
nothing."""

from chipbench import program


def read(ctx):
    c = program.window_counters(ctx)
    if not c or "engine.trace_bytes_put" not in c:
        return None
    cells = sum(s["cells"] for s in ctx.data["studies"])
    return c["engine.trace_bytes_put"] / 1e6 / (cells / 1e3)
