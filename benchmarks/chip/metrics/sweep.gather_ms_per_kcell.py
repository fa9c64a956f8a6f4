"""Host wall of the program's ``engine.gather`` span (each chunk's per-lane
arrays gathered from the table of distinct traces), in ms per 1,000 cells of
the traced studies."""

from chipbench import program


def read(ctx):
    return program.ms_per_kcell(ctx, "engine.gather")
