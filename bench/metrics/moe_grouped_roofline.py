"""Roofline share of the grouped expert kernel (``moe_grouped_matmul``)
in the traced steps: the least time the chip could take for the work of
its calls (`bench/work/moe_grouped.py`, from the engine's MoE counters)
over the calls' device time.  The bound is taken over the calls'
summed work, which is at most the sum of their own bounds, so the share
never reads high.  A program without the kernel or its counters reports
nothing."""

import pathlib

import harness

WORK = harness.load_module(pathlib.Path(harness.BENCH, "work",
                                        "moe_grouped.py"))
PATTERN = r"^%moe_grouped_matmul\."


def read(ctx):
    rows = ctx.counters.get("moe_rows_held")
    if ctx.trace is None or ctx.peaks is None or not rows:
        return None
    events = ctx.trace.ops(PATTERN)
    if not events:
        return None
    ops, byts = WORK.work(ctx.cell.config, rows,
                          ctx.counters["moe_expert_groups"])
    bound = max(ops / ctx.peaks["int8_ops_per_s"],
                byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / sum(e.dur for e in events)
