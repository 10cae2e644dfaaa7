"""Roofline share of the single-token integer decode kernel
(``stamp_decode_matmul``) in the traced steps: five calls per layer over
the whole decode slot array."""

import pathlib

import harness
from rooflines import roofline_share

WORK = harness.load_module(pathlib.Path(harness.BENCH, "work",
                                        "stamp_decode.py"))
PATTERN = r"^%stamp_decode_matmul\."


def read(ctx):
    slots = ctx.cell.mix["slots"]
    return roofline_share(ctx, WORK, PATTERN, lambda st: slots)
