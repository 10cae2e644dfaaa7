"""Roofline share of the fused STaMP prefill kernels (merged QKV,
head-split out-proj, dual gate/up, down-proj) in the traced steps."""

import pathlib

import harness
from rooflines import roofline_share

WORK = harness.load_module(pathlib.Path(harness.BENCH, "work",
                                        "stamp_prefill.py"))
PATTERN = r"^%stamp_quant_(dual_)?matmul\."


def read(ctx):
    chunk = ctx.cell.mix["prefill_chunk"]
    return roofline_share(ctx, WORK, PATTERN,
                          lambda st: st.prefill_chunks * chunk)
