"""Host wall of the program's ``engine.build`` span (the fused warm-up + measure trace of every cell: tile, pad and concat), in ms per 1,000 cells of the traced studies."""

from chipbench import program


def read(ctx):
    return program.ms_per_kcell(ctx, "engine.build")
