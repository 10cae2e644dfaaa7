"""Where a serving step's time goes, on the profiler's clock: the paged
engine's ``engine.*`` host spans (one per step phase, the dispatch phase
split into input build, upload, launch, wait and logits fetch), the
public ``step()``, and the model step's named scopes, which tag every op
of the compiled program with the block and STaMP site it belongs to and
change none of its ops."""

import contextlib
import glob
import os
import re

import jax
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.core.stamp import StampConfig                       # noqa: E402
from repro.models import lm                                    # noqa: E402
from repro.models.config import ModelConfig                    # noqa: E402
from repro.serving import kvcache as KV                        # noqa: E402
from repro.serving.engine import (PagedEngineConfig,           # noqa: E402
                                  PagedServingEngine)

CFG = ModelConfig(name="trace-test", family="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=128)
QUANT = KV.KVCacheConfig(quantized=True, num_hi=16)
SERVE = lm.ServeConfig(stamp=StampConfig(num_hi_tokens=8,
                                         execution="fused"), kv=QUANT)
DISPATCH_PARTS = ("build_inputs", "upload", "launch", "wait",
                  "fetch_logits")
SCOPES = ("embed", "attn", "mlp", "head", "stamp.qkv", "stamp.out",
          "stamp.gate_up", "stamp.down", "attn.kv_write")


@pytest.fixture(scope="module")
def params():
    return lm.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(4)
    return [rng.integers(0, CFG.vocab_size, n) for n in (20, 33, 12)]


def engine(params, serve=SERVE):
    return PagedServingEngine(params, CFG, serve, PagedEngineConfig(
        max_slots=3, prefill_chunk=16, max_seq=96, block_size=16,
        max_prefills=2))


def submit(eng, prompts, max_new=5):
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)


def test_step_returns_what_finished(params, prompts):
    """Driving the engine by ``step()`` serves the same tokens as
    ``run()``, and each request comes back once, in the step it ended."""
    a, b = engine(params), engine(params)
    submit(a, prompts)
    want = {r.uid: list(r.out_tokens) for r in a.run()}
    submit(b, prompts)
    got = {}
    while b.sched.has_work():
        for r in b.step():
            assert r.uid not in got and r.status == "finished"
            got[r.uid] = list(r.out_tokens)
    assert got == want
    assert b.step() == []


def test_engine_spans_on_the_profiler_clock(params, prompts, tmp_path):
    """A profiled run carries ``engine.step`` around each step, the three
    phases inside it, and the five dispatch parts inside ``dispatch``;
    each dispatch part also has its own ``step_phase_s`` label, and
    their sum stays within the dispatch phase's."""
    from jax.profiler import ProfileData
    eng = engine(params)
    submit(eng, prompts[:1], max_new=2)
    eng.run()                                 # compile outside the trace
    eng.reset_stats()
    submit(eng, prompts)
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    names = {n for n, _, _ in spans}
    assert {"engine.step", "engine.plan", "engine.dispatch",
            "engine.post"} <= names
    assert {f"engine.{p}" for p in DISPATCH_PARTS} <= names

    def inside(child, parent):
        for n, s, e in spans:
            if n != child:
                continue
            assert any(pn == parent and ps <= s and e <= pe
                       for pn, ps, pe in spans), (child, parent)

    for ph in ("plan", "dispatch", "post"):
        inside(f"engine.{ph}", "engine.step")
    for part in DISPATCH_PARTS:
        inside(f"engine.{part}", "engine.dispatch")
    assert sum(1 for n, _, _ in spans if n == "engine.step") \
        == eng.stats["steps"]

    def total(ph):
        return eng.metrics.histogram("step_phase_s",
                                     labels={"phase": ph}).sum

    parts = sum(total(p) for p in DISPATCH_PARTS)
    assert all(total(p) > 0 for p in DISPATCH_PARTS)
    assert 0 < parts <= total("dispatch")
    # the event ring keeps one slice per top-level phase
    assert {e.phase for e in eng.events if e.kind == "phase"} \
        == {"plan", "dispatch", "post"}


def _op_names(txt: str) -> list:
    return re.findall(r'op_name="([^"]*)"', txt)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["attn.fallback", "attn.kernel"])
def test_step_program_carries_the_named_scopes(params, prompts, kernel):
    """Every scope of the model step tags ops of the compiled unified
    step; the attention core is ``attn.kernel`` on the Pallas path and
    ``attn.fallback`` on the XLA one."""
    import dataclasses
    eng = engine(params, dataclasses.replace(
        SERVE, fused_cache_attention=kernel))
    submit(eng, prompts[:2], max_new=2)
    eng.run()
    paths = [p.split("/") for p in _op_names(eng.step_program().as_text())]
    core = "attn.kernel" if kernel else "attn.fallback"
    for scope in SCOPES + (core,):
        assert any(scope in p for p in paths), scope
    for sub in ("stamp.qkv", "stamp.out", "attn.kv_write", core):
        assert all("attn" in p for p in paths if sub in p), sub
    for sub in ("stamp.gate_up", "stamp.down"):
        assert all("mlp" in p for p in paths if sub in p), sub


def _ops(txt: str) -> str:
    """HLO computations without metadata, debug tables or instruction
    numbering: what the program computes."""
    txt = txt[txt.index("\n%"):]
    txt = re.sub(r',? metadata=\{[^}]*\}', "", txt)
    return re.sub(r'(%[A-Za-z_][A-Za-z0-9_\-]*)(\.[A-Za-z0-9_]+)*', r"\1",
                  txt)


def test_scopes_change_no_op(params, prompts, monkeypatch):
    """The compiled unified step is the same program with the named
    scopes and without them: they are metadata only."""
    def compiled():
        eng = engine(params)
        submit(eng, prompts[:2], max_new=2)
        eng.run()
        return eng.step_program().as_text()

    # an earlier test in this process may have turned JAX's persistent
    # compile cache on (the serving entry points do), and it keys a
    # program without its scope metadata: the second compile would load
    # the first one's text.  The cache settles whether it is in use once,
    # so it is reset around the switch.
    from jax.experimental.compilation_cache import compilation_cache as cc
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        scoped = compiled()
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        plain = compiled()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()
    assert "stamp.qkv" in scoped and "stamp.qkv" not in plain
    assert _ops(scoped) == _ops(plain)
