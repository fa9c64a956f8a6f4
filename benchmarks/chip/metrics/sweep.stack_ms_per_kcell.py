"""Host wall of the program's ``engine.stack`` span (isa.stack_traces, the stacked config parameters and the zeroed carry), in ms per 1,000 cells of the traced studies."""

from chipbench import program


def read(ctx):
    return program.ms_per_kcell(ctx, "engine.stack")
