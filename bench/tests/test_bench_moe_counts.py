"""The grouped expert kernel's work count (`bench/work/moe_grouped.py`)
against a hand count at Granite-4.0-H-Small's published expert shape,
and the readers of its roofline share and rows per expert, which read
nothing from a program without the MoE counters."""

import json
import pathlib
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness                                                 # noqa: E402

WORK = harness.load_module(BENCH / "work" / "moe_grouped.py")
ROOFLINE = harness.load_module(BENCH / "metrics" / "moe_grouped_roofline.py")
ROWS = harness.load_module(BENCH / "metrics" / "moe.rows_per_expert.py")
CELL = "granite-4.0-h-small-l20-ep4.long_prompt"


def granite():
    return json.loads((BENCH / "configs" /
                       "granite-4.0-h-small-l20-ep4.json").read_text())


def test_grouped_counts_by_hand():
    # one prefill region: 256 tokens × 10 choices, a quarter held, over
    # all 18 held experts
    rows, groups = 640, 18
    d, f = 4096, 768
    ops, byts = WORK.work(granite(), rows, groups)
    assert ops == 2 * rows * (d * f + d * f + f * d)
    codes = 3 * d * f                        # int8 gate, up, down
    params = (f + f + d) * 8                 # f32 scale and zp a channel
    assert byts == groups * (codes + params) \
        + rows * (d + 2 * d)                 # int8 row in, bf16 row out
    # at 36 rows an expert the call is bound by streaming its codes
    assert byts / 819e9 > 3 * ops / 393e12


def ctx(counters, durations):
    events = [types.SimpleNamespace(dur=t) for t in durations]
    trace = types.SimpleNamespace(ops=lambda pattern: events)
    return types.SimpleNamespace(
        counters=counters, trace=trace,
        cell=types.SimpleNamespace(config=granite()),
        peaks=harness.peaks_for("TPU v5 lite"))


def test_roofline_share_reads_summed_work_over_kernel_time():
    c = {"moe_rows_held": 1280.0, "moe_expert_groups": 36.0}
    share = ROOFLINE.read(ctx(c, [4e-4, 5e-4]))
    ops, byts = WORK.work(granite(), 1280, 36)
    bound = max(ops / 393e12, byts / 819e9)
    assert share == pytest.approx(100 * bound / 9e-4)
    assert 0 < share <= 100


@pytest.mark.parametrize("counters,durations", [
    ({}, [1e-3]),                                    # a program without MoE
    ({"moe_rows_held": 0.0, "moe_expert_groups": 0.0}, [1e-3]),
    ({"moe_rows_held": 5.0, "moe_expert_groups": 2.0}, []),   # no kernel
])
def test_roofline_share_reads_nothing_without_its_inputs(counters,
                                                         durations):
    assert ROOFLINE.read(ctx(counters, durations)) is None


def test_rows_per_expert():
    assert ROWS.read(ctx({"moe_rows_held": 660.0,
                          "moe_expert_groups": 30.0}, [])) == 22.0
    assert ROWS.read(ctx({}, [])) is None


def test_new_metrics_are_listed_for_the_granite_cell():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    per_layer = {m["name"] for m in harness.cell(bench, CELL).per_layer}
    assert {"moe_grouped_roofline", "moe.rows_per_expert"} <= per_layer
    nemo = harness.cell(bench, "mistral-nemo-12b-l20.long_prompt")
    assert not {"moe_grouped_roofline", "moe.rows_per_expert"} \
        & {m["name"] for m in nemo.per_layer}
