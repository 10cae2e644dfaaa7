"""95th percentile, over every inter-token gap that ends inside the
window (all requests), in milliseconds."""

import numpy as np


def read(ctx):
    g = ctx.nums["gaps"]
    return float(np.percentile(g, 95)) * 1e3 if g else None
