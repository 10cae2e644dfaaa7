"""Granite-4.0-H (`configs/granite_4_0_h_small.py`) at its `reduced()`
size on the CPU, served through `PagedServingEngine` (the fused STaMP
unified step: prefill in chunks, then decode through the paged KV and
the SSM slots): a Mamba-2 / NoPE-attention hybrid whose every layer holds
a dropless MoE (the chip's share of the experts) beside a shared expert.
Its logits match the benchmark's plain reference
(`bench/reference/granite_hybrid.py`) and, more loosely, the same forward
without any quantizer; with the SSM state carry zeroed at a chunk
boundary they do not.  The engine counts every routed row and drops
none.  The layer-level checks are in `test_granite_layers.py`.
"""

import dataclasses
import functools
import importlib.util
import pathlib
import sys

import jax
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.configs import granite_4_0_h_small as G
from repro.models import layers as L

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SEED = 11
CHUNK = 16
PROMPTS = (45, 21, 60)
NEW = 4


def _load(path: pathlib.Path, name: str):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


REF = _load(BENCH / "reference" / "granite_hybrid.py", "granite_ref_test")
FAMILY = _load(BENCH / "families" / "granite_hybrid.py",
               "granite_family_test")
TINY = __import__("json").loads(
    (BENCH / "tests" / "data" / "tiny-granite.json").read_text())


def test_tiny_configuration_is_the_reduced_preset():
    """The benchmark family builds `reduced()` from the test file's keys,
    so the reference below checks the preset the program serves."""
    got = FAMILY.model_config(TINY)
    want = G.reduced()
    skip = {"name", "source", "head_dim"}        # compared resolved
    for f in dataclasses.fields(want):
        if f.name not in skip:
            assert getattr(got, f.name) == getattr(want, f.name), f.name
    assert got.resolved_head_dim == want.resolved_head_dim
    pro, period, nper = want.layer_plan()
    assert not pro and nper == 1 and len(period) == 10
    assert [s.mixer for s in period] == ["attn" if i == 5 else "mamba"
                                         for i in range(10)]
    assert all(s.ffn == "moe_dense" for s in period)


@functools.lru_cache(maxsize=1)
def _built():
    """The served model's weights and calibrated serving config, built
    once from SEED and shared by every engine below."""
    from repro.launch.serve import build_model
    params, serve, _ = build_model(G.reduced(), SEED)
    return params, serve


def _serve(precision: str = "served", zero_carry: bool = False):
    """Serve three prompts of several chunks each through the fused
    engine; return (prompts, served tokens, logits rows per request,
    engine stats).  ``precision`` "served" keeps the calibrated STaMP
    activations; "weights" serves with no activation quantizer (W4 weights
    and the int8/int4 KV cache only).  ``zero_carry`` starts every
    prefill chunk's SSD from a zero state."""
    from repro.launch.serve import with_execution
    from repro.serving.engine import PagedEngineConfig, PagedServingEngine
    cfg = G.reduced()
    params, serve = _built()
    serve = with_execution(serve, "fused")
    if precision == "weights":
        serve = dataclasses.replace(serve, stamp=None)
    eng = PagedServingEngine(params, cfg, serve, PagedEngineConfig(
        max_slots=2, prefill_chunk=CHUNK, max_seq=128, max_prefills=2,
        block_size=4))
    rows: dict = {}
    orig = PagedServingEngine._next_token

    def record(self, sreq, row):
        rows.setdefault(sreq.uid, []).append(np.array(row, np.float32))
        return orig(self, sreq, row)

    mp = pytest.MonkeyPatch()
    mp.setattr(PagedServingEngine, "_next_token", record)
    if zero_carry:
        ssd = L.ssd_chunked
        mp.setattr(L, "ssd_chunked",
                   lambda *a, init_state=None, **k: ssd(*a, **k))
    try:
        rng = np.random.default_rng(SEED)
        prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
                   for n in PROMPTS]
        uids = [eng.submit(p, max_new_tokens=NEW) for p in prompts]
        done = {r.uid: r for r in eng.run()}
    finally:
        mp.undo()
    served = [np.asarray(done[u].out_tokens) for u in uids]
    return (prompts, served, [np.stack(rows[u]) for u in uids],
            dict(eng.stats))


def _reference(run, precision):
    """The reference's logits at every position the run served, teacher-
    forced on the run's own tokens: (requests, NEW, vocab)."""
    prompts, served = run[0], run[1]
    batch = REF.pack(list(zip(prompts, served)), CHUNK,
                     -(-max(PROMPTS) // CHUNK), NEW - 1)
    with jax.default_matmul_precision("highest"):
        out = REF.logits(TINY, SEED, CHUNK, batch, {"p": precision})["p"]
    return np.asarray(out)[..., :G.reduced().vocab_size]


def _distance(rows, ref) -> float:
    """Mean over served positions of the centred logit vectors' relative
    distance ``|got − want| / |want|``."""
    out = []
    for got, want in zip(rows, ref):
        got = got[:, :want.shape[-1]]
        got = got - got.mean(-1, keepdims=True)
        want = want[:len(got)] - want[:len(got)].mean(-1, keepdims=True)
        out += list(np.linalg.norm(got - want, axis=-1)
                    / np.linalg.norm(want, axis=-1))
    return float(np.mean(out))


def _precision(kind: str):
    p = REF.Precision.of(TINY)
    if kind == "weights":
        p = dataclasses.replace(p, hi_bits=None, lo_bits=None,
                                decode_bits=None, expert_bits=None)
    return p


@pytest.fixture(scope="module")
def served():
    return _serve()


@pytest.fixture(scope="module")
def weights_only():
    return _serve("weights")


# Tolerances on `_distance` (measured at SEED on the CPU):
# * served precision: the program and the reference take the same stated
#   quantizers, and part only where a bfloat16 rounding, the program's
#   int8 re-coding of an int4 weight, or its bf16 conv moves a value across
#   a 4-bit code boundary or a top-k routing tie.  At this width a 4-bit
#   code step is a large share of a row, so such crossings are common: the
#   distance read 0.308, against 0.496 between the reference and the same
#   forward without quantizers.  The tolerance, 0.4, passes the program's
#   reading and fails one as far from the reference as the float model is;
# * against the float model the program may be no further than the
#   reference's own quantizers put it (0.516 against 0.496), with a fifth
#   of room;
# * weights only (no activation quantizer): the paths differ by roundings
#   and the routing ties they tip: 0.060.  The tolerance, 0.09, is 1.5x
#   that; zeroing the SSM state carry at each chunk boundary read 0.123.
SERVED_TOL = 0.4
FLOAT_ROOM = 1.2
WEIGHTS_TOL = 0.09


def test_served_logits_match_the_reference(served):
    p = _precision("served")
    assert _distance(served[2], _reference(served, p)) <= SERVED_TOL
    raw = _reference(served, p.unquantized())
    own = _distance(list(_reference(served, p)), raw)
    assert _distance(served[2], raw) <= FLOAT_ROOM * own


def test_weights_only_logits_match_and_see_the_ssm_carry(weights_only):
    p = _precision("weights")
    assert _distance(weights_only[2], _reference(weights_only, p)) \
        <= WEIGHTS_TOL
    broken = _serve("weights", zero_carry=True)
    assert _distance(broken[2], _reference(broken, p)) > WEIGHTS_TOL


def test_engine_counts_dropless_routing(served):
    """Every token the MoE layers saw routed ``k`` choices, one layer call
    per layer and region; about half land on the 4 of 8 experts held here,
    and none is dropped."""
    cfg = G.reduced()
    prompts, toks, _, st = served
    tokens = sum(map(len, prompts)) + sum(len(t) - 1 for t in toks)
    assert st["moe_rows_routed"] == tokens * cfg.experts_per_token \
        * cfg.num_layers
    assert st["moe_rows_dropped"] == 0
    assert 0.3 < st["moe_rows_held"] / st["moe_rows_routed"] < 0.7
    assert 0 < st["moe_expert_groups"] <= cfg.held_experts \
        * cfg.num_layers * 2 * st["steps"]


