"""The reduction from a profiler trace to busy time, idle share, kernel
time and idle-gap attribution, on a small hand-made trace."""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import tracereduce as T                                        # noqa: E402


def ev(name, start, dur, text=None):
    return T.Event(name, start, dur, text or name)


RAW = {
    "host": [ev("bench.step", 1.0, 1.0), ev("bench.bookkeeping", 2.0, 0.2),
             ev("bench.step", 2.2, 0.8), ev("python_other", 1.5, 0.1)],
    "devices": [{
        "ops": [ev("fusion.1", 0.5, 0.7),                 # clipped to 1.0
                ev("custom-call.2", 1.3, 0.3,
                   "custom-call.2 long_name=_stamp_kernel"),
                ev("fusion.3", 1.5, 0.2),                 # overlaps .2
                ev("custom-call.4 = bf16[8,128] custom-call(s8[128,128])",
                   2.4, 0.4, "custom-call.4 long_name=_stamp_dual_kernel"),
                ev("fusion.5", 3.5, 0.1)],                # outside
        "modules": [ev("jit_step", 1.0, 0.9), ev("jit_step", 2.3, 0.6)]}],
}


def test_window_busy_and_idle():
    s = T.reduce(RAW)
    assert s.window == (1.0, 3.0)
    assert s.steps == 2
    # busy: [1.0,1.2] + [1.3,1.7] + [2.4,2.8]
    assert s.busy_s == pytest.approx(0.2 + 0.4 + 0.4)
    assert s.window_s == pytest.approx(2.0)
    assert s.module_s() == pytest.approx(0.9 + 0.6)


def test_kernel_time_by_pattern():
    s = T.reduce(RAW)
    k = s.ops(r"stamp_kernel|stamp_dual_kernel")
    assert [e.name.split()[0] for e in k] == ["custom-call.2",
                                              "custom-call.4"]
    assert sum(e.dur for e in k) == pytest.approx(0.7)


def test_gaps_named_by_the_innermost_host_span():
    s = T.reduce(RAW)
    gaps = dict((round(d, 6), n) for n, d in s.gaps())
    assert gaps[round(0.1, 6)] == "bench.step"          # 1.2..1.3
    assert gaps[round(0.7, 6)] == "bench.bookkeeping"   # 1.7..2.4, mid 2.05
    assert gaps[round(0.2, 6)] == "bench.step"          # 2.8..3.0
    b = s.breakdown(top=2)
    assert [n for n, _ in b["idle_gaps"]] == ["bench.bookkeeping",
                                             "bench.step"]
    # named without the operands, longest first
    assert [n for n, _ in b["device_ops"]] == ["custom-call.4",
                                              "custom-call.2"]


def test_union_merges_overlaps():
    assert T.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                            (3, 4)]


def test_no_step_span_is_an_error():
    with pytest.raises(ValueError):
        T.reduce({"host": [], "devices": []})
