"""95th percentile, in ms, of how late the load generator submitted each
request after its scheduled time.  The service flushes inline, so this is
how long flushes held the submitting thread."""

import numpy as np


def read(ctx):
    late = ctx.data.get("gen_late_s")
    if late is None or not len(late):
        return None
    return float(np.percentile(np.asarray(late), 95)) * 1e3
