"""Shared arithmetic of the kernel roofline and model-utilization readers:
a kernel's least possible time from its operations and bytes, and the
model operations the served tokens needed."""

from __future__ import annotations

from typing import Optional


def roofline_share(ctx, work, pattern: str, rows_of) -> Optional[float]:
    """Percent of the least time the chip could take for a kernel's calls
    in the traced steps (the larger of operations over the int8 peak and
    bytes over HBM bandwidth) against their device time in the trace, per
    call on average: the mean bound of the calls the steps made over the
    mean device time of the calls the trace holds.  ``work`` is a
    ``bench/work`` module, ``rows_of(step)`` the rows its kernels ran in
    that step (0: not called).  None where the trace holds none of its
    events, or more than the steps made."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    events = ctx.trace.ops(pattern)
    c = ctx.cell.config
    bound, calls = 0.0, 0
    for st in ctx.steps:
        rows = rows_of(st)
        if rows:
            ops, byts = work.step(c, rows)
            bound += max(ops / ctx.peaks["int8_ops_per_s"],
                         byts / ctx.peaks["hbm_bytes_per_s"])
            calls += work.CALLS_PER_LAYER * c["num_hidden_layers"]
    if not events or len(events) > calls:
        return None
    return 100.0 * (bound / calls) / (sum(e.dur for e in events)
                                      / len(events))


def model_ops(c: dict, steps: list) -> float:
    """Operations the model needs for the tokens the steps served: per
    cached position ``2 * linear params`` plus attention over its context
    (``4 * layers * q_dim * context``), per served token ``2 * head
    params``."""
    d, ff, layers = c["hidden_size"], c["intermediate_size"], \
        c["num_hidden_layers"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    linear = layers * (d * (q + 2 * kv) + q * d + 3 * d * ff)
    head = d * c["vocab_size"]
    ops = 0.0
    for st in steps:
        for pos0, pos1, new in st.work:
            n = pos1 - pos0
            ctx_sum = (pos0 + 1 + pos1) * n / 2          # sum of contexts
            ops += n * 2 * linear + 4 * layers * q * ctx_sum \
                + new * 2 * head
    return ops


def mfu(ctx) -> Optional[float]:
    """The whole step's share of the chip's int8 peak: model operations of
    the traced steps over their host-clock span."""
    if ctx.peaks is None or not ctx.steps or ctx.seconds <= 0:
        return None
    return 100.0 * model_ops(ctx.cell.config, ctx.steps) / ctx.seconds \
        / ctx.peaks["int8_ops_per_s"]
