"""Host wall of the program's ``engine.readback`` span (reading the metrics and checkpoints back and unpacking each lane), in ms per 1,000 cells of the traced studies."""

from chipbench import program


def read(ctx):
    return program.ms_per_kcell(ctx, "engine.readback")
