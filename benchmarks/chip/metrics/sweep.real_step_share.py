"""Share of the device scan's lane-steps that scan a real trace record, in
%: the program's counters over the traced studies, real lane-steps
((warmup + measure) x len(body) per simulated cell) over scanned ones
(batch bucket x padded length per batch group).  The rest is padding: of
the batch to a power of two, and of the length and the warm-up to a chunk
boundary."""

from chipbench import program


def read(ctx):
    c = program.window_counters(ctx)
    if not c or not c.get("engine.lane_steps_scanned"):
        return None
    return 100.0 * c["engine.lane_steps_real"] / c["engine.lane_steps_scanned"]
