"""Rows per held expert with rows, per MoE layer call, in the traced
steps: the engine's ``moe_rows_held`` over its ``moe_expert_groups``,
the M at which the grouped expert kernel's tiles run.  A program
without these counters reports nothing."""


def read(ctx):
    groups = ctx.counters.get("moe_expert_groups")
    if not groups:
        return None
    return ctx.counters["moe_rows_held"] / groups
