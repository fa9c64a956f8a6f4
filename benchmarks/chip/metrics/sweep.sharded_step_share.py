"""Share of the device scan's lane-steps that ran through the program
sharded over the config axis, in %: the program's counters over the traced
studies, ``engine.lane_steps_sharded`` (batch bucket x padded length of
each batch group whose launches went through the sharded program) over
``engine.lane_steps_scanned``.  On a host of several chips it reads 100;
less means part of the study ran on one chip.  A program without the
counter gives nothing."""

from chipbench import program


def read(ctx):
    from repro.core import telemetry

    c = program.window_counters(ctx)
    if not c or not c.get("engine.lane_steps_scanned"):
        return None
    # a study that never sharded moves the counter by 0, which the study's
    # counters leave out: the program's totals say whether it keeps it
    if "engine.lane_steps_sharded" not in telemetry.totals()["counters"]:
        return None
    return (100.0 * c.get("engine.lane_steps_sharded", 0)
            / c["engine.lane_steps_scanned"])
