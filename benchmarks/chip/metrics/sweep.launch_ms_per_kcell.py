"""Host wall of the program's ``engine.launch`` span (each chunk's scan program launch and the checkpoint reads it queues), in ms per 1,000 cells of the traced studies."""

from chipbench import program


def read(ctx):
    return program.ms_per_kcell(ctx, "engine.launch")
