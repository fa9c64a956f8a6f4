"""Host wall of the dispatch phase of dse.explore: the engine batch path, waits on the device included, in ms per 1,000 cells of the traced studies."""


def read(ctx):
    studies = ctx.data.get("studies")
    if not studies:
        return None
    cells = sum(s["cells"] for s in studies)
    return 1e3 * sum(s["phases"]["dispatch"] for s in studies) / (cells / 1e3)
