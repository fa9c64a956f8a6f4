"""Share of the roofline of the engine's scan, in %: the least time the
chips could take for the studies' work over the scan executables' device
time.  The work is counted from the study's bodies, not from the engine's
padded shapes: every distinct simulated cell reads (warmup + measure) x
len(body) trace records of 44 bytes once.  It is bounded by bytes, so the
peak is the chip's HBM bandwidth."""


def read(ctx):
    studies = ctx.data.get("studies")
    devs = ctx.trace["devices"]
    scan_s = sum(d["matched_s"] for d in devs) / max(len(devs), 1)
    if not studies or scan_s <= 0 or "hbm_bytes_per_s" not in ctx.peaks:
        return None
    work = ctx.reference.work_bytes(ctx.data["apps"], ctx.data["knobs"],
                                    ctx.data["warmup"], ctx.data["measure"])
    bound_s = len(studies) * work / (len(devs) * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / scan_s
