"""Peak share of the KV page pools (hi and lo together) that requests
referenced in any traced step; reclaimable prefix-cache pages count as
free."""


def read(ctx):
    if not ctx.steps or not ctx.pool_pages:
        return None
    return 100.0 * max(s.pages_used for s in ctx.steps) / ctx.pool_pages
