"""Host wall of the program's ``engine.copy`` span (slicing each 1,024-step chunk of the stacked trace and copying it to the device), in ms per 1,000 cells of the traced studies."""

from chipbench import program


def read(ctx):
    return program.ms_per_kcell(ctx, "engine.copy")
