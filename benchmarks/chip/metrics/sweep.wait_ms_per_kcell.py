"""Host wall of the program's ``engine.wait`` span (waiting on the device for each batch group's final carry), in ms per 1,000 cells of the traced studies."""

from chipbench import program


def read(ctx):
    return program.ms_per_kcell(ctx, "engine.wait")
