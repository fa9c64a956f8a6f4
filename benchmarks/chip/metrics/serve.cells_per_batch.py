"""Cells the service simulated per batch over the traced window: its
counters, dispatched / batches."""


def read(ctx):
    c = ctx.data.get("counters")
    if not c or not c["batches"]:
        return None
    return c["dispatched"] / c["batches"]
