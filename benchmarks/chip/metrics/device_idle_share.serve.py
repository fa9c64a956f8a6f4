"""Share of the traced window in which no operation ran on the device, in
%: one minus the union of the device's operation intervals over the
window, averaged over the devices (each device's own share is printed on
an earlier line of standard error)."""


def read(ctx):
    devs = ctx.trace["devices"]
    if "counters" not in ctx.data or not devs:
        return None
    return 100.0 * sum(1.0 - d["busy_s"] / d["window_s"]
                       for d in devs) / len(devs)
