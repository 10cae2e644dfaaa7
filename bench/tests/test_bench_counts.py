"""Kernel work counts, peaks and model-operation arithmetic of the
benchmark, against hand counts at the configurations' published shapes."""

import json
import pathlib
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness                                                 # noqa: E402
import rooflines                                               # noqa: E402

PREFILL = harness.load_module(BENCH / "work" / "stamp_prefill.py")
DECODE = harness.load_module(BENCH / "work" / "stamp_decode.py")


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,d,qkv,q,ff,layers", [
    ("deepseek-7b", 4096, 3 * 4096, 4096, 11008, 30),
    ("mistral-nemo-12b-l20", 5120, 4096 + 2 * 1024, 4096, 14336, 20),
])
def test_prefill_counts_by_hand(name, d, qkv, q, ff, layers):
    rows = 256
    ops, byts = PREFILL.step(config(name), rows)
    hand_ops = 2 * rows * (d * qkv + q * d + d * 2 * ff + ff * d) * layers
    hand_bytes = layers * (
        (d * qkv + rows * d * 2 + rows * qkv * 2 + qkv * 8)      # QKV
        + (q * d + rows * q * 2 + rows * d * 2 + d * 8)          # out-proj
        + (2 * d * ff + rows * d * 2 + rows * ff * 2 + 2 * ff * 8)  # gate/up
        + (ff * d + rows * ff * 2 + rows * d * 2 + d * 8))       # down
    assert ops == hand_ops
    assert byts == hand_bytes


@pytest.mark.parametrize("name,d,qkv,q,ff,layers", [
    ("deepseek-7b", 4096, 3 * 4096, 4096, 11008, 30),
    ("mistral-nemo-12b-l20", 5120, 4096 + 2 * 1024, 4096, 14336, 20),
])
def test_decode_counts_by_hand(name, d, qkv, q, ff, layers):
    rows = 12
    ops, byts = DECODE.step(config(name), rows)
    sites = [(d, qkv), (q, d), (d, ff), (d, ff), (ff, d)]
    assert ops == layers * sum(2 * rows * k * n for k, n in sites)
    assert byts == layers * sum(k * n + rows * k * 2 + rows * n * 2 + n * 8
                                for k, n in sites)
    # decode at 12 rows is bound by the int8 weights: bytes over 819 GB/s
    # dwarf ops over 393 TOP/s
    assert byts / 819e9 > 10 * ops / 393e12


def test_peaks_known_and_unknown_device():
    p = harness.peaks_for("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")


def test_model_ops_by_hand():
    c = config("mistral-nemo-12b-l20")
    d, ff, layers, q, kv = 5120, 14336, 20, 4096, 1024
    linear = layers * (d * (q + 2 * kv) + q * d + 3 * d * ff)
    head = d * 131072
    # one 128-token chunk at positions 128..255, then one decode token at
    # position 300 that serves one token
    step = types.SimpleNamespace(work=[(128, 256, 0), (300, 301, 1)])
    ctx_sum = sum(range(129, 257)) + 301
    want = 129 * 2 * linear + 4 * layers * q * ctx_sum + 2 * head
    assert rooflines.model_ops(c, [step]) == pytest.approx(want, rel=1e-12)


def test_roofline_share_per_call():
    c = config("deepseek-7b")
    ev = types.SimpleNamespace(dur=1e-3)
    steps = [types.SimpleNamespace(prefill_chunks=1)] * 2
    trace = types.SimpleNamespace(ops=lambda pattern: [ev] * (2 * 4 * 30))
    ctx = types.SimpleNamespace(trace=trace, steps=steps,
                                cell=types.SimpleNamespace(config=c),
                                peaks=harness.peaks_for("TPU v5 lite"))
    share = rooflines.roofline_share(ctx, PREFILL, "x",
                                     lambda st: 128 * st.prefill_chunks)
    ops, byts = PREFILL.step(c, 128)
    bound = max(ops / 393e12, byts / 819e9)
    assert share == pytest.approx(100 * 2 * bound / (2 * 4 * 30 * 1e-3))
    trace.ops = lambda pattern: [ev] * (2 * 4 * 30 + 1)   # more than made
    assert rooflines.roofline_share(ctx, PREFILL, "x",
                                    lambda st: 128) is None
