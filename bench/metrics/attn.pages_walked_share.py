"""Share of the pages the span tables reserve that the attention layers
read, in the traced steps: the engine's ``attn_pages_walked`` over its
``attn_pages_reserved``, in percent.  A program without these counters
reports nothing."""


def read(ctx):
    reserved = ctx.counters.get("attn_pages_reserved")
    if not reserved:
        return None
    return 100.0 * ctx.counters["attn_pages_walked"] / reserved
