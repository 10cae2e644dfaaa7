"""Device milliseconds inside the step program per step: the trace's
module time over the traced steps."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.steps:
        return None
    ms = ctx.trace.module_s() / ctx.trace.steps * 1e3
    return ms if ms > 0 else None
