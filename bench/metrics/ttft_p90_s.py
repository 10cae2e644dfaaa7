"""90th percentile, over every request whose first token was stamped
inside the window, of submit to first token."""

import numpy as np


def read(ctx):
    t = ctx.nums["ttft"]
    return float(np.percentile(t, 90)) if t else None
