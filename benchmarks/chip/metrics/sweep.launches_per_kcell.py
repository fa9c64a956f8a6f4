"""Scan program launches (one per 1,024-step chunk of each batch group) per
1,000 cells of the traced studies: the program's ``engine.launches``
counter."""

from chipbench import program


def read(ctx):
    c = program.window_counters(ctx)
    if not c or "engine.launches" not in c:
        return None
    cells = sum(s["cells"] for s in ctx.data["studies"])
    return c["engine.launches"] / (cells / 1e3)
