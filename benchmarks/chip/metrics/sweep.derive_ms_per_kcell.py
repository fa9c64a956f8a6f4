"""Host wall of the derive phase of dse.explore (whole-app runtime, scalar baseline, area per record), in ms per 1,000 cells of the traced studies."""


def read(ctx):
    studies = ctx.data.get("studies")
    if not studies:
        return None
    cells = sum(s["cells"] for s in studies)
    return 1e3 * sum(s["phases"]["derive"] for s in studies) / (cells / 1e3)
