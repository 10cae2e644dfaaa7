"""Model FLOP utilization of the whole step: the operations the traced
steps' tokens needed, over their time, as a share of the int8 peak (the
STaMP GEMMs are int8).  It bounds what the kernel rooflines claim for
``tokens_per_s``."""

from rooflines import mfu


def read(ctx):
    return mfu(ctx)
