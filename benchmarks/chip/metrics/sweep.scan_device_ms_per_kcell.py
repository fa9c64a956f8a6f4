"""Device time of the engine's scan executables, in ms per 1,000 cells of
the traced studies: the summed durations of their executions in the trace,
averaged over the devices."""


def read(ctx):
    studies = ctx.data.get("studies")
    devs = ctx.trace["devices"]
    scan_s = sum(d["matched_s"] for d in devs) / max(len(devs), 1)
    if not studies or scan_s <= 0:
        return None
    cells = sum(s["cells"] for s in studies)
    return 1e3 * scan_s / (cells / 1e3)
