"""Host milliseconds per engine step outside the device call: the
engine's plan and post phase timers (``step_phase_s``) over the traced
steps, divided by their number."""


def read(ctx):
    n = ctx.counters["steps"]
    if not n:
        return None
    return (ctx.counters["plan_s"] + ctx.counters["post_s"]) / n * 1e3
