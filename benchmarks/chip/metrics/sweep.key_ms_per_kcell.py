"""Host wall of the key phase of dse.explore (bodies, trace and config fingerprints, cache lookups), in ms per 1,000 cells of the traced studies."""


def read(ctx):
    studies = ctx.data.get("studies")
    if not studies:
        return None
    cells = sum(s["cells"] for s in studies)
    return 1e3 * sum(s["phases"]["key"] for s in studies) / (cells / 1e3)
