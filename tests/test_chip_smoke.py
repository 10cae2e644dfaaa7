"""`chip_smoke.py` rehearsed on the CPU: its set-up and request loop at the
reduced Llama-3-8B widths, in this process with the kernels in interpret
mode, and its refusal to report a result off the TPU."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import types

import jax
import pytest

from repro.configs import get_reduced

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    cfg = get_reduced("llama3-8b")
    engine = smoke.build_engine(cfg, seed=0)
    prompts = smoke.make_prompts(cfg.vocab_size, seed=0)
    done, _ = smoke.serve_requests(engine, prompts)
    return engine, done, prompts


def test_prompts_share_one_prefix(smoke):
    prompts = smoke.make_prompts(1000, seed=0)
    assert [len(p) for p in prompts] == list(smoke.PROMPT_LENS)
    a, b = smoke.SHARED
    n = smoke.SHARED_PREFIX
    assert (prompts[a][:n] == prompts[b][:n]).all()
    assert not (prompts[a][n:] == prompts[b][n:len(prompts[a])]).all()


def test_serving_checks_hold(smoke, served):
    engine, done, prompts = served
    assert engine.serve.stamp.execution == "fused"
    assert engine.ecfg.prefix_caching
    assert smoke.check_serving(engine, done, len(prompts)) == []
    assert engine.stats["recompiles"] >= 1


def test_serving_checks_catch_faults(smoke, served):
    engine, done, prompts = served
    n = len(prompts)
    demoted = types.SimpleNamespace(stats={**engine.stats, "demotions": 1,
                                           "reference_fallback_sites": 2})
    assert smoke.check_serving(demoted, done, n) == [
        "reference_fallback_sites=2", "demotions=1"]
    assert smoke.check_serving(engine, done[:-1], n) == [
        f"{n - 1}/{n} requests finished (statuses {['finished'] * (n - 1)})"]
    uncached = types.SimpleNamespace(stats={**engine.stats,
                                            "prefix_tokens_reused": 0})
    assert smoke.check_serving(uncached, done, n) == [
        f"prefix_tokens_reused=0 (the shared prefix is "
        f"{smoke.SHARED_PREFIX} tokens)"]


def test_kernel_checks_hold(smoke, served):
    engine, _, _ = served
    assert smoke.check_kernels(engine, seed=0) == []


def test_off_grid_check_catches_faults(smoke, monkeypatch):
    bound = smoke.OFF_GRID_RTOL
    monkeypatch.setattr(smoke, "off_grid_errors", lambda *a: {
        "kernel": 2 * bound, "all rows at 8 bits": 0.1,
        "bf16 forward transform": bound / 2})
    assert smoke.check_off_grid(None, None, None, 0) == [
        f"stamp_quant_matmul off-grid rel_err {2 * bound:.3e}",
        f"control 'bf16 forward transform' within the off-grid bound "
        f"({bound / 2:.3e})"]


def test_step_program_calls_no_kernel_off_tpu(served):
    # interpret mode inlines the kernels, so the CPU program holds no
    # Pallas custom call; on the chip the same check must find one
    engine, _, _ = served
    assert "tpu_custom_call" not in engine.step_program().as_text()


def test_refuses_to_report_off_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    with pytest.raises(RuntimeError, match="not a TPU"):
        smoke.result_line(jax.devices())


def test_result_line_names_the_device(smoke):
    class Tpu:
        platform, device_kind = "tpu", "TPU v5 lite"
    line = json.loads(smoke.result_line([Tpu()]))
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
