"""Decode spans per engine step: the engine's decode-token counter over
its step counter, in the traced steps."""


def read(ctx):
    n = ctx.counters["steps"]
    return ctx.counters["decode_tokens"] / n if n else None
