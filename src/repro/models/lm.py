"""Unified LM: dense / MoE / hybrid / SSM / enc-dec, train + prefill + decode.

Layer stacking follows the period plan from ``ModelConfig.layer_plan()``:
periods are `lax.scan`'d (compact HLO at 512-way SPMD), layers inside a
period are unrolled.  Parameters are stored f32 and cast to bf16 at use
(classic mixed precision); serving paths optionally swap the large matmuls
for packed-int4 weights (paper's W4) and always run the mixed-precision
quantized KV cache + STaMP activation fake-quant when enabled.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stamp import StampConfig, stamp_fake_quant
from repro.core.quant import fake_quant
from repro.obs import quantstats as QS
from repro.models import layers as L
from repro.models.config import LayerSpec, ModelConfig, ShapeConfig
from repro.serving import kvcache as KV
from repro.serving import paged_kvcache as PKV
from repro.sharding import ShardingPolicy, constrain

Array = jax.Array
Pytree = Any


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Inference-time quantization configuration (the paper's W4A4KV4)."""

    stamp: Optional[StampConfig] = None          # activation STaMP at prefill
    kv: KV.KVCacheConfig = KV.KVCacheConfig()
    weight_bits: Optional[int] = None            # 4 => packed-int4 weights
    cache_capacity: Optional[int] = None         # reserve room for decode
    fused_cache_attention: bool = False          # Pallas kernel decode path
    # (TPU deployment; on CPU runs in interpret mode — see
    #  kernels/cache_attention.py for the traffic analysis)
    fused_decode_matmul: bool = False            # single-token int8 kernel
    # against prepared weights (kernels/decode_matmul.py) instead of the
    # per-step bf16 dequant of the same buffers
    paged: Optional["PKV.PagedCacheConfig"] = None   # block-paged cache
    # (continuous-batching engine; None = contiguous per-slot cache)
    numerics_guard: bool = False  # serving engines check step outputs for
    # NaN/Inf and quarantine the offending request (engine.py) — the
    # low-precision escape hatch: sub-8-bit activation formats are one
    # outlier away from saturation, and one poisoned request must not
    # take down the batch
    quant_telemetry: bool = False  # per-STaMP-site quant-health stats
    # (clip rate, hi-token coverage, scale range, saturation — see
    # repro/obs/quantstats.py) returned alongside the step outputs as
    # on-device scalar reductions in the SAME program: zero extra device
    # dispatches per step.  Opt-in: adds an element to the returns of
    # prefill / paged_prefill_chunk / paged_unified_step


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _dense_init(key, din, dout, dtype, std=None):
    std = std if std is not None else (1.0 / np.sqrt(din))
    return (jax.random.normal(key, (din, dout), jnp.float32) * std).astype(dtype)


def init_layer_params(key, spec: LayerSpec, cfg: ModelConfig,
                      dtype=jnp.float32) -> dict:
    keys = iter(jax.random.split(key, 24))
    d = cfg.d_model
    p: dict = {}
    if spec.mixer == "attn":
        p["ln1"] = jnp.ones((d,), dtype)
        p["wq"] = _dense_init(next(keys), d, cfg.q_dim, dtype)
        p["wk"] = _dense_init(next(keys), d, cfg.kv_dim, dtype)
        p["wv"] = _dense_init(next(keys), d, cfg.kv_dim, dtype)
        p["wo"] = _dense_init(next(keys), cfg.q_dim, d, dtype)
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((cfg.q_dim,), dtype)
            p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
            p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
        if cfg.encoder_layers:  # decoder layers carry cross-attention
            p["lnx"] = jnp.ones((d,), dtype)
            p["xwq"] = _dense_init(next(keys), d, cfg.q_dim, dtype)
            p["xwk"] = _dense_init(next(keys), d, cfg.kv_dim, dtype)
            p["xwv"] = _dense_init(next(keys), d, cfg.kv_dim, dtype)
            p["xwo"] = _dense_init(next(keys), cfg.q_dim, d, dtype)
    elif spec.mixer == "mamba":
        di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        conv_dim = di + 2 * n
        p["ln1"] = jnp.ones((d,), dtype)
        p["in_proj"] = _dense_init(next(keys), d, 2 * di + 2 * n + h, dtype)
        # Mamba-2's published conv init (PyTorch's Conv1d default): the
        # depthwise fan-in is the width, weight and bias U(±1/sqrt(width))
        bound = 1.0 / np.sqrt(cfg.conv_width)
        p["conv_w"] = jax.random.uniform(
            next(keys), (cfg.conv_width, conv_dim), jnp.float32, -bound,
            bound).astype(dtype)
        if cfg.conv_bias:
            p["conv_b"] = jax.random.uniform(
                jax.random.fold_in(key, 102), (conv_dim,), jnp.float32,
                -bound, bound).astype(dtype)
        p["a_log"], p["dt_bias"] = _ssm_decay_init(key, h)
        p["d_skip"] = jnp.ones((h,), jnp.float32)
        p["ssm_norm"] = jnp.ones((di,), dtype)
        p["out_proj"] = _dense_init(next(keys), di, d, dtype)
    if spec.ffn in ("mlp", "moe_dense"):
        prefix = "d" if spec.ffn == "moe_dense" else ""
        p["ln2"] = jnp.ones((d,), dtype)
        p[f"{prefix}wi_gate"] = _dense_init(next(keys), d, cfg.d_ff, dtype)
        p[f"{prefix}wi_up"] = _dense_init(next(keys), d, cfg.d_ff, dtype)
        p[f"{prefix}wo_mlp"] = _dense_init(next(keys), cfg.d_ff, d, dtype)
    if spec.ffn in ("moe", "moe_dense"):
        e, f = cfg.num_experts, cfg.expert_d_ff
        p["ln2"] = jnp.ones((d,), dtype)
        p["gate_w"] = _dense_init(next(keys), d, e, dtype)
        # each held expert drawn from its GLOBAL index, so a chip's share
        # holds exactly the uncut layer's experts [offset, offset + held)
        held = cfg.expert_offset + jnp.arange(cfg.held_experts)

        def experts(k, shape, std):
            return jax.vmap(lambda i: jax.random.normal(
                jax.random.fold_in(k, i), shape, jnp.float32))(held) * std

        p["we_gate"] = experts(next(keys), (d, f), 1.0 / np.sqrt(d)
                               ).astype(dtype)
        p["we_up"] = experts(next(keys), (d, f), 1.0 / np.sqrt(d)
                             ).astype(dtype)
        p["we_down"] = experts(next(keys), (f, d), 1.0 / np.sqrt(f)
                               ).astype(dtype)
    return p


def _ssm_decay_init(key, h: int) -> tuple[Array, Array]:
    """Mamba-2's published init of the per-head decay: ``A = -exp(a_log)``
    with ``exp(a_log) ~ U[1, 16]``, and ``dt_bias`` the inverse softplus of
    a step ``dt ~ exp(U[log 0.001, log 0.1])`` (floored at 1e-4), so heads
    remember from a few to a few hundred tokens.  Drawn from keys folded
    off the layer key, leaving the other parameters' draws as they were."""
    a = jax.random.uniform(jax.random.fold_in(key, 100), (h,), jnp.float32,
                           1.0, 16.0)
    u = jax.random.uniform(jax.random.fold_in(key, 101), (h,), jnp.float32)
    dt = jnp.maximum(jnp.exp(u * (np.log(0.1) - np.log(0.001))
                             + np.log(0.001)), 1e-4)
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def init_params(key, cfg: ModelConfig, dtype=jnp.float32,
                weight_bits: Optional[int] = None) -> dict:
    """Random-init parameters from ``key``.

    With ``weight_bits`` the tree comes out as served: the float32 init
    cast to bf16 and the large matmul weights packed by
    :func:`quantize_weights_for_serving` — the values of packing a float
    init afterwards (up to float rounding of the scales and ties in the
    codes), but built one scan period at a time, so the float model is
    never whole in memory (an 8B model is 32 GB in f32)."""
    pro, period, nper = cfg.layer_plan()
    k_embed, k_head, k_pro, k_per, k_enc = jax.random.split(key, 5)

    def finish(tree):
        if not weight_bits:
            return tree
        tree = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                            if a.dtype == jnp.float32 else a, tree)
        return quantize_weights_for_serving(tree, weight_bits)

    params: dict = {
        "embed": finish((jax.random.normal(
            k_embed, (cfg.padded_vocab, cfg.d_model), jnp.float32)
            * cfg.embed_init_std).astype(dtype)),
        "final_norm": finish(jnp.ones((cfg.d_model,), dtype)),
    }
    if not cfg.tie_embeddings:
        params["head"] = finish(_dense_init(k_head, cfg.d_model,
                                            cfg.padded_vocab, dtype))
    if pro:
        pro_keys = jax.random.split(k_pro, len(pro))
        params["prologue"] = tuple(
            finish(init_layer_params(k, s, cfg, dtype))
            for k, s in zip(pro_keys, pro))
    per_keys = jax.random.split(k_per, nper)
    # one layer position at a time (a period's layers draw from the split
    # of its period key): only one layer's float tree is ever live
    params["period"] = tuple(
        jax.lax.map(lambda k, j=j, s=s: finish(init_layer_params(
            jax.random.split(k, len(period))[j], s, cfg, dtype)), per_keys)
        for j, s in enumerate(period))
    if cfg.encoder_layers:
        enc_spec = LayerSpec("attn", "mlp")
        enc_cfg = dataclasses.replace(cfg, encoder_layers=0)  # no cross in enc
        enc_keys = jax.random.split(k_enc, cfg.encoder_layers)
        params["encoder"] = {
            "period": jax.lax.map(
                lambda k: finish(
                    (init_layer_params(k, enc_spec, enc_cfg, dtype),)),
                enc_keys),
            "final_norm": finish(jnp.ones((cfg.d_model,), dtype)),
        }
    return params


# ---------------------------------------------------------------------------
# (possibly quantized) linears
# ---------------------------------------------------------------------------


def _linear(x: Array, w, b=None) -> Array:
    """Matmul accepting a plain array, a packed-int4 dict
    ``{"q": (din/2, dout) uint8, "scale": (1, dout), "zp": (1, dout)}`` or a
    fused-path prepared dict ``{"iq", "isw", "izw"}`` (signed int8 codes —
    used directly by decode/no-STaMP call sites that share the serving
    params)."""
    if isinstance(w, dict) and "iq" in w:
        if _FUSED_DECODE_MATMUL and x.ndim >= 2 and x.shape[-2] == 1:
            # decode-shaped call (one token per slot): consume the cached
            # int8 codes directly in the fused kernel instead of
            # re-materializing the bf16 weight every step
            from repro.kernels import ops as kops
            lead = x.shape[:-1]
            y = kops.stamp_decode_matmul(
                x.reshape(-1, x.shape[-1]), w["iq"], w["isw"], w["izw"],
                b, out_dtype=x.dtype)
            return y.reshape(*lead, y.shape[-1])
        # target-dtype arithmetic for the same reason as _dequant_packed:
        # the dequant intermediate is what FSDP all-gathers, and the signed
        # codes / zero points are integers in [-128, 127] — exact in bf16
        # (prepare_linear anchors the quant range at zero to guarantee it)
        wd = ((w["iq"].astype(x.dtype) - w["izw"].astype(x.dtype)) *
              w["isw"].astype(x.dtype))
    elif isinstance(w, dict):
        wd = _dequant_packed(w, x.dtype)
    else:
        wd = w.astype(x.dtype)
    y = x @ wd
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def _use_fused(stamp: Optional[StampConfig], w) -> bool:
    """Dispatch to the fused integer kernel only when the serving params hold
    prepared int8 buffers for this site *and* STaMP is active in fused mode
    (prefill; decode passes stamp=None and takes the dequant `_linear`)."""
    return (stamp is not None and stamp.enabled
            and stamp.execution == "fused"
            and isinstance(w, dict) and "iq" in w)


def _dequant_packed(w: dict, dtype) -> Array:
    # arithmetic entirely in the target dtype: an f32 dequant intermediate
    # becomes the tensor GSPMD all-gathers for FSDP-sharded weights (2×
    # the bytes of bf16, 8× the packed bytes); zp ≤ 15 and int4 codes are
    # exact in bf16 (§Perf decode iter 4).
    q = KV.unpack_nibbles(jnp.swapaxes(w["q"], -1, -2)).astype(dtype)
    q = jnp.swapaxes(q, -1, -2)                              # (din, dout)
    return (q - w["zp"].astype(dtype)) * w["scale"].astype(dtype)


def quantize_weights_for_serving(params: Pytree, bits: int = 4) -> Pytree:
    """Pack the large matmul weights to int4 (nibbles along d_in).  Norms,
    biases, embeddings and small SSM params stay bf16/f32."""
    big = ("wq", "wk", "wv", "wo", "xwq", "xwk", "xwv", "xwo",
           "wi_gate", "wi_up", "wo_mlp", "dwi_gate", "dwi_up", "dwo_mlp",
           "we_gate", "we_up", "we_down", "in_proj", "out_proj")

    def visit(tree):
        if isinstance(tree, dict):
            return {k: (pack_weight(v, bits) if k in big else visit(v))
                    for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(visit(t) for t in tree)
        return tree

    return visit(params)


# Per-site fused-wiring table: every prefill-path STaMP linear and how the
# fused integer kernel consumes its prepared int8 buffers.
#
#   single — one `stamp_quant_matmul` call (the attention out-proj feeds
#            the raw (b, s, nh, hd) attention output; the head merge fuses
#            with the kernel's in-VMEM quantize);
#   pair   — the SwiGLU gate/up pair shares ONE transform+quantize through
#            the dual-output kernel (`stamp_quant_dual_matmul`, silu·mul
#            epilogue);
#   merged — wq/wk/wv concatenate into one "wqkv" buffer at prepare time so
#            prefill issues a single kernel call over the full QKV width.
#
#   grouped — the stacked (E, din, dout) expert buffers prepare in one
#            `prepare_linear` pass (per-output-channel scales per expert)
#            and feed `stamp_quant_ragged_matmul`, which walks each held
#            expert's ragged row group with the tile table
#            scalar-prefetched.
#
# Cross-attention projections (xw*) stay un-prepared: the paper applies no
# sequence transform at pooled-conditioning sites (Table 4).
FUSED_SITES = {
    "wo": "single",              # attention out-proj (head-merge fused)
    "wo_mlp": "single", "dwo_mlp": "single",
    "in_proj": "single", "out_proj": "single",   # mamba projections
    "wi_gate": "pair", "wi_up": "pair",
    "dwi_gate": "pair", "dwi_up": "pair",
    "we_gate": "grouped", "we_up": "grouped", "we_down": "grouped",
}
_QKV = ("wq", "wk", "wv")
_QKV_BIAS = ("bq", "bk", "bv")
_PAIRS = (("wi_gate", "wi_up"), ("dwi_gate", "dwi_up"))


def fused_site_matrix(cfg: ModelConfig, stamp: Optional[StampConfig],
                      feature_rot=None) -> dict:
    """Eligibility audit: every STaMP site this architecture instantiates,
    mapped to ``fused`` or ``reference`` with structured reason codes.

    The per-config half of ``repro.analysis.contracts`` (and the serve-time
    init log): config-level ineligibility comes from
    `repro.core.stamp.fused_ineligibility`, site-level structural
    ineligibility (MoE expert einsums, cross-attention, the encoder) is
    stated here explicitly instead of falling through an implicit branch.
    Cells: ``{"status", "kernel", "wiring", "layers", "reasons"}`` keyed by
    the telemetry site label (``qkv``/``wo``/``gate_up``/``wo_mlp``/
    ``moe``/``in_proj``/``out_proj``/``cross_attn``/``encoder``).
    """
    from repro.core.stamp import fused_ineligibility
    base = (("stamp_disabled",) if stamp is None
            else fused_ineligibility(stamp, feature_rot))
    pro, period, nper = cfg.layer_plan()
    specs = pro + period * nper
    matrix: dict = {}

    def add(site, kernel, wiring, site_reasons=()):
        reasons = tuple(site_reasons) + (() if site_reasons else base)
        cell = matrix.setdefault(site, {
            "status": "fused" if not reasons else "reference",
            "kernel": kernel if not reasons else None,
            "wiring": wiring,
            "layers": 0,
            "reasons": list(reasons),
        })
        cell["layers"] += 1

    for spec in specs:
        if spec.mixer == "attn":
            add("qkv", "stamp_quant_matmul", "merged_wqkv")
            add("wo", "stamp_quant_matmul", "single_head_merge")
        elif spec.mixer == "mamba":
            add("in_proj", "stamp_quant_matmul", "single")
            add("out_proj", "stamp_quant_matmul", "single")
        if spec.ffn in ("mlp", "moe_dense"):
            add("gate_up", "stamp_quant_dual_matmul", "pair")
            add("wo_mlp", "stamp_quant_matmul", "single")
        if spec.ffn in ("moe", "moe_dense"):
            # dropless routing: ragged per-expert row groups of the held
            # experts through the grouped kernel, prefill and decode alike
            add("moe", "stamp_quant_ragged_matmul", "ragged_dispatch")
    if cfg.encoder_layers:
        # pooled-conditioning sites carry no sequence transform (Table 4)
        for _ in range(len(specs)):
            add("cross_attn", None, "reference_xattn",
                site_reasons=("site_cross_attn_no_seq_transform",))
        for _ in range(cfg.encoder_layers):
            add("encoder", None, "reference_encoder",
                site_reasons=("site_encoder_unstamped",))
    return matrix


def prepare_fused_weights(params: Pytree, stamp: StampConfig) -> Pytree:
    """Hoist the fused sites' weights into cached int8 buffers
    ``{"iq", "isw", "izw"}`` (per-output-channel scales, signed codes);
    self-attention wq/wk/wv merge into one ``"wqkv"`` entry and their biases
    into ``"bqkv"`` (concatenated **once here**, not per forward call), and
    each gate/up pair stacks into one `prepare_linear` call.

    Runs once at engine/benchmark setup; stacked ``(nper, din, dout)`` period
    weights prepare one period at a time (`lax.map`) into stacks that slice
    cleanly under `lax.scan` — per-output-channel scales make that identical
    to one whole-stack pass, and only one period's f32 dequant is ever
    live.  Packed int4 dicts from :func:`quantize_weights_for_serving` are
    dequantized first and re-coded at ``stamp.fused_weight_bits``.  No-op
    when the config cannot run the fused kernel.
    """
    from repro.core.stamp import fused_eligible, prepare_linear
    if not fused_eligible(stamp):
        return params

    def raw(w):
        return _dequant_packed(w, jnp.float32) if isinstance(w, dict) \
            else w.astype(jnp.float32)

    def prep(w):
        p = prepare_linear(raw(w), bits=stamp.fused_weight_bits)
        return {"iq": p.qw, "isw": p.sw, "izw": p.zw}

    def prep_pair(wg, wu):
        # stacked (2, din, dout) prepare: per-output-channel scales make it
        # identical to two separate prepares, in one pass over the pair
        p = prepare_linear(jnp.stack([raw(wg), raw(wu)]),
                           bits=stamp.fused_weight_bits)
        return ({"iq": p.qw[0], "isw": p.sw[0], "izw": p.zw[0]},
                {"iq": p.qw[1], "isw": p.sw[1], "izw": p.zw[1]})

    def visit(tree):
        if isinstance(tree, dict):
            items = dict(tree)
            out = {}
            if all(k in items for k in _QKV) and "wqkv" not in items:
                # per-output-channel scales make prepare(concat) identical
                # to concat(prepare): quantize the merged buffer directly
                raws = [raw(items.pop(k)) for k in _QKV]
                out["wqkv"] = prep(jnp.concatenate(raws, axis=-1))
                if all(k in items for k in _QKV_BIAS):
                    out["bqkv"] = jnp.concatenate(
                        [items.pop(k) for k in _QKV_BIAS], axis=-1)
            for kg, ku in _PAIRS:
                if kg in items and ku in items and \
                        not (isinstance(items[kg], dict)
                             and "iq" in items[kg]):
                    out[kg], out[ku] = prep_pair(items.pop(kg),
                                                 items.pop(ku))
            for k, v in items.items():
                if k == "encoder":
                    # the encoder never runs STaMP (stamp=None in
                    # _encoder_forward): quantizing it is pure precision loss
                    out[k] = v
                elif k == "period":
                    # one layer position at a time: one layer's f32
                    # dequant is live, not a whole period's
                    out[k] = tuple(jax.lax.map(visit, layer) for layer in v)
                elif k in FUSED_SITES and \
                        not (isinstance(v, dict) and "iq" in v):
                    out[k] = prep(v)
                else:
                    out[k] = visit(v)
            return out
        if isinstance(tree, tuple):
            return tuple(visit(t) for t in tree)
        return tree

    return visit(params)


def pack_weight(w: Array, bits: int = 4) -> dict:
    """(…, din, dout) → packed dict; per-output-channel asymmetric scales."""
    n = float(2**bits - 1)
    wf = w.astype(jnp.float32)
    mn = jnp.min(wf, axis=-2, keepdims=True)
    mx = jnp.max(wf, axis=-2, keepdims=True)
    scale = jnp.maximum((mx - mn) / n, 1e-8)
    zp = jnp.round(-mn / scale)
    q = jnp.clip(jnp.round(wf / scale) + zp, 0.0, n)
    qt = jnp.swapaxes(q, -1, -2)                             # (dout, din)
    packed = KV.pack_nibbles(qt)
    return {"q": jnp.swapaxes(packed, -1, -2), "scale": scale, "zp": zp}


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


_FUSED_CACHE_ATTENTION = False
_FUSED_DECODE_MATMUL = False


def kw_fused(kv_cfg) -> bool:
    return _FUSED_CACHE_ATTENTION


def paged_kernel(pcfg, cfg: ModelConfig, forced: bool) -> bool:
    """Whether paged attention over this pool takes the Pallas kernel
    (kernels/paged_attention.py), decided at trace time: on a TPU for
    every quantized pool it compiles for; elsewhere only when ``forced``
    (``ServeConfig.fused_cache_attention``: interpret mode, for tests)."""
    from repro.kernels.ops import default_interpret
    from repro.kernels.paged_attention import compiles_for
    if not pcfg.quant.quantized:
        return False
    if forced:
        return True
    return not default_interpret() and compiles_for(
        pcfg.block_size, cfg.num_kv_heads, cfg.resolved_head_dim)


def set_fused_cache_attention(enabled: bool) -> None:
    """Route decode attention through the Pallas packed-cache kernel
    (kernels/cache_attention.py for the contiguous layout; the paged one
    takes kernels/paged_attention.py on a TPU anyway, see `paged_kernel`).
    Module-level switch so the functional layer code stays
    signature-stable; the serving engine sets it from
    ``ServeConfig.fused_cache_attention``."""
    global _FUSED_CACHE_ATTENTION
    _FUSED_CACHE_ATTENTION = enabled


def set_fused_decode_matmul(enabled: bool) -> None:
    """Route decode-shaped linears over prepared int8 weights through
    `kernels/decode_matmul.stamp_decode_matmul` (no per-step bf16 weight
    re-materialization).  Set from ``ServeConfig.fused_decode_matmul`` at
    each decode entry point and reset to False by every prefill/train/eval
    entry (`model_hidden`, `paged_prefill_chunk`): the `_linear` dispatch
    keys only on the token dimension being 1, so a stale True from an
    earlier decode would silently skip the STaMP transform on any later
    length-1-sequence forward."""
    global _FUSED_DECODE_MATMUL
    _FUSED_DECODE_MATMUL = enabled


def _collect_telemetry(serve: ServeConfig) -> bool:
    """Static (Python-level) gate for quant telemetry: only meaningful
    when a STaMP config is actually quantizing.  Being static, default
    configs see the exact historical return arities."""
    return (serve.quant_telemetry and serve.stamp is not None
            and serve.stamp.enabled)


def _residual(x: Array, y: Array, cfg: ModelConfig) -> Array:
    """``x + m·y`` with the model's residual multiplier (Granite's 0.22);
    at 1 exactly ``x + y``."""
    m = cfg.residual_multiplier
    return x + y if m == 1.0 else x + y * m


# MoE counts (`layers.MOE_COUNTS`) the dropless layers record while a
# stack traces: `run_stack` sums them over its layers (through the scan
# as outputs) and leaves the total here for the entry point to return.
_MOE_TALLY: list = []


def _drain_tally() -> Array:
    """Sum and clear the (4,) int32 MoE counts recorded at this trace
    level (zeros where none were)."""
    out = jnp.zeros((len(L.MOE_COUNTS),), jnp.int32)
    for c in _MOE_TALLY:
        out = out + c
    _MOE_TALLY.clear()
    return out


def _maybe_stamp(x: Array, stamp: Optional[StampConfig],
                 site: Optional[str] = None) -> Array:
    if stamp is None or not stamp.enabled:
        return x
    return stamp_fake_quant(x, stamp, site=site)


def _split_heads(x: Array, nh: int, hd: int) -> Array:
    return x.reshape(*x.shape[:-1], nh, hd)


def _merge_heads(x: Array) -> Array:
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])


def _attn_qkv(p: dict, h: Array, cfg: ModelConfig,
              stamp: Optional[StampConfig]) -> tuple[Array, Array, Array]:
    """QKV projections off the normed input (shared by the prefill, decode
    and unified paths so their dispatch rules cannot diverge), under the
    named scope ``stamp.qkv``."""
    with jax.named_scope("stamp.qkv"):
        if "wqkv" in p:
            # merged prepared int8 QKV (prepare_fused_weights): the merged
            # "bqkv" bias was concatenated there too — once at prepare time,
            # not per layer call
            bqkv = p.get("bqkv")
            if bqkv is None and p.get("bq") is not None:
                # legacy prepared tree (merged weight, per-site bias leaves):
                # fall back to the per-call concat rather than dropping biases
                bqkv = jnp.concatenate([p["bq"], p["bk"], p["bv"]], axis=-1)
            if _use_fused(stamp, p["wqkv"]):
                # ONE kernel call: the sequence transform + quantize of h runs
                # once (kernel scratch), amortized over the full QKV width
                qkv = L.stamp_fused_linear(h, p["wqkv"], bqkv, stamp,
                                           site="qkv")
            else:
                # decode / reference execution against the same int8 buffers
                qkv = _linear(_maybe_stamp(h, stamp, site="qkv"),
                              p["wqkv"], bqkv)
            q, k, v = jnp.split(
                qkv, [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
            return q, k, v
        h = _maybe_stamp(h, stamp, site="qkv")
        return (_linear(h, p["wq"], p.get("bq")),
                _linear(h, p["wk"], p.get("bk")),
                _linear(h, p["wv"], p.get("bv")))


def _attn_out(p: dict, attn: Array, x: Array,
              stamp: Optional[StampConfig], cfg: ModelConfig) -> Array:
    """Out-projection (named scope ``stamp.out``) + residual (shared
    across paths)."""
    with jax.named_scope("stamp.out"):
        if _use_fused(stamp, p["wo"]):
            # fused out-proj: the raw head-split attention output goes
            # straight into the kernel — its stamped quantize fuses with the
            # head-merge reshape, so no merged (b, s, nh·hd) activation
            # round-trips HBM
            y = L.stamp_fused_linear(attn, p["wo"], None, stamp,
                                     merge_heads=True, site="wo")
        else:
            y = _linear(_maybe_stamp(_merge_heads(attn), stamp, site="wo"),
                        p["wo"])
    return _residual(x, y, cfg)


def attn_block(
    p: dict, x: Array, cfg: ModelConfig, *,
    mode: str, positions: Array, policy: Optional[ShardingPolicy],
    stamp: Optional[StampConfig], kv_cfg: KV.KVCacheConfig,
    cache_entry: Optional[dict] = None, pos_scalar: Optional[Array] = None,
    enc_out: Optional[Array] = None, causal: bool = True,
    cache_capacity: Optional[int] = None, paged: Optional[dict] = None,
) -> tuple[Array, Optional[dict]]:
    hd, nh, kvh = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    scale = cfg.attention_multiplier
    h = L.rms_norm(x, p["ln1"].astype(x.dtype), cfg.norm_eps)
    q, k, v = _attn_qkv(p, h, cfg, stamp)
    q = apply_rope_heads(q, positions, cfg, nh, hd)
    k = apply_rope_heads(k, positions, cfg, kvh, hd)
    v = _split_heads(v, kvh, hd)

    new_entry: Optional[dict] = None
    if mode == "decode" and paged is not None:
        # continuous batching: per-slot write through the block tables,
        # attention over the mapped pages only
        assert cache_entry is not None
        pcfg = paged["cfg"]
        with jax.named_scope("attn.kv_write"):
            new_entry = PKV.write_tokens(cache_entry, k, v, paged["pages"],
                                         paged["offsets"], paged["is_hi"],
                                         pcfg)
        length = paged["lengths"]
        if paged_kernel(pcfg, cfg, _FUSED_CACHE_ATTENTION):
            from repro.kernels.paged_attention import paged_decode_attention
            with jax.named_scope("attn.kernel"):
                attn = paged_decode_attention(new_entry, q, length,
                                              paged["hi_table"],
                                              paged["lo_table"],
                                              pcfg.block_size, scale=scale)
        else:
            with jax.named_scope("attn.fallback"):
                segs = PKV.gather_segments(new_entry, paged["hi_table"],
                                           paged["lo_table"], pcfg, x.dtype)
                attn = L.decode_attention_segments(q, segs, length=length,
                                                   scale=scale)
    elif mode == "decode":
        assert cache_entry is not None
        new_entry = KV.write_token(cache_entry, k, v, pos_scalar, kv_cfg)
        length = jnp.asarray(pos_scalar).reshape(-1) + 1
        if kv_cfg.quantized and kw_fused(kv_cfg):
            from repro.kernels.cache_attention import cache_decode_attention
            attn = cache_decode_attention(new_entry, q, length, scale=scale)
        elif kv_cfg.quantized:
            (k_hi, v_hi), (k_lo, v_lo) = KV.dequantize_segments(
                new_entry, kv_cfg, x.dtype)
            if policy is not None:
                spec = policy.decode_kv_spec(k_lo.shape[0])
                k_lo = policy.constraint(k_lo, spec)
                v_lo = policy.constraint(v_lo, spec)
            hi_len = k_hi.shape[1]
            attn = L.decode_attention_segments(
                q, [(k_hi, v_hi, 0), (k_lo, v_lo, hi_len)], length=length,
                scale=scale)
        else:
            kf, vf = KV.dequantize_full(new_entry, kv_cfg, x.dtype)
            if policy is not None:
                spec = policy.decode_kv_spec(kf.shape[0])
                kf = policy.constraint(kf, spec)
                vf = policy.constraint(vf, spec)
            attn = L.decode_attention(q, kf, vf, length=length, scale=scale)
    elif mode == "prefill" and paged is not None:
        # chunked prefill into the paged cache: write this chunk's K/V
        # through the block table, attend to the cached prefix + the raw
        # chunk.  The first chunk has no prefix (start = 0) and the same
        # call reduces to pure causal self-attention over the chunk.
        assert cache_entry is not None
        pcfg = paged["cfg"]
        new_entry = PKV.write_chunk(cache_entry, k, v, paged["pages"],
                                    paged["offsets"], paged["is_hi"], pcfg)
        # first and continuation chunks share the chunked call (start = 0
        # masks the cached segments exactly — see chunked_prefill_attention)
        # so the two-call and unified engines run row-identical math
        segs = PKV.gather_segments(new_entry, paged["hi_table"],
                                   paged["lo_table"], pcfg, x.dtype)
        attn = L.chunked_prefill_attention(q, segs, k, v, paged["start"],
                                           scale=scale)
    else:
        attn = L.flash_attention(q, k, v, causal=causal, scale=scale)
        if mode == "prefill":
            new_entry = KV.quantize_full(k, v, kv_cfg, capacity=cache_capacity)
    x = _attn_out(p, attn, x, stamp, cfg)

    if enc_out is not None and "xwq" in p:   # cross-attention (enc-dec)
        hx = L.rms_norm(x, p["lnx"].astype(x.dtype), cfg.norm_eps)
        qx = _split_heads(_linear(hx, p["xwq"]), nh, hd)
        if mode == "decode" and cache_entry is not None and "xk" in cache_entry:
            kx = cache_entry["xk"].astype(x.dtype)
            vx = cache_entry["xv"].astype(x.dtype)
            ax = L.decode_attention(qx, kx, vx, scale=scale)
        else:
            kx = _split_heads(_linear(enc_out, p["xwk"]), kvh, hd)
            vx = _split_heads(_linear(enc_out, p["xwv"]), kvh, hd)
            ax = L.flash_attention(qx, kx, vx, causal=False, scale=scale)
            if mode == "prefill":
                new_entry = dict(new_entry or {})
                new_entry["xk"] = kx.astype(jnp.bfloat16)
                new_entry["xv"] = vx.astype(jnp.bfloat16)
        ox = _merge_heads(ax)
        # paper Fig. 5 / Table 4: no sequence transform on cross-attn to_out
        # (pooled conditioning breaks the Toeplitz structure) — per-token
        # quant only.
        if stamp is not None and stamp.enabled:
            ox = fake_quant(ox, stamp.lo_bits, axis=-1)
        x = x + _linear(ox, p["xwo"])
        if mode == "decode" and cache_entry is not None and "xk" in cache_entry:
            new_entry = dict(new_entry or {})
            new_entry["xk"] = cache_entry["xk"]
            new_entry["xv"] = cache_entry["xv"]
    return x, new_entry


def apply_rope_heads(flat: Array, positions: Array, cfg: ModelConfig,
                     nh: int, hd: int) -> Array:
    """Split heads and rotate them; a NoPE model (``use_rope=False``)
    only splits."""
    if not cfg.use_rope:
        return _split_heads(flat, nh, hd)
    return L.apply_rope(_split_heads(flat, nh, hd), positions, cfg.rope_theta)


def attn_block_unified(
    p: dict, x: tuple, cfg: ModelConfig, *,
    stamp: Optional[StampConfig], kv_cfg: KV.KVCacheConfig,
    cache_entry: dict, paged: dict,
) -> tuple[tuple, dict]:
    """One attention block of the **unified ragged step**: the prefill
    chunk rows ``(n_pf, C, d)`` and the decode slots ``(S, 1, d)`` run in
    one program — QKV per region (prefill under STaMP, decode transform
    free, exactly the two-call dispatch), ONE combined K/V scatter over the
    flattened token stream, then attention per span: decode spans over
    their mapped pages, prefill spans causally within the chunk against
    their own block-table prefix.  The XLA fallback runs ONE
    `chunked_prefill_attention` call for all chunk rows: a first row's
    ``pf_start = 0`` masks its cached segments to an exactly-zero merge
    contribution, so no separate flash variant (and no evaluate-both-and-
    ``jnp.where`` select) is needed — first/continuation chunks share one
    compiled program and each row's math is bit-identical to the two-call
    engine's chunk call (the parity contract).  On a TPU (`paged_kernel`)
    `paged_ragged_attention` computes the same result, its Pallas kernel
    reading only each span's own int4 pages.
    """
    x_pf, x_dec = x
    hd, nh, kvh = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    pcfg = paged["cfg"]
    n_pf, c_len = x_pf.shape[:2]
    s_slots = x_dec.shape[0]

    h_pf = L.rms_norm(x_pf, p["ln1"].astype(x_pf.dtype), cfg.norm_eps)
    h_dec = L.rms_norm(x_dec, p["ln1"].astype(x_dec.dtype), cfg.norm_eps)
    q_pf, k_pf, v_pf = _attn_qkv(p, h_pf, cfg, stamp)
    q_dec, k_dec, v_dec = _attn_qkv(p, h_dec, cfg, None)
    pos_pf = paged["pf_positions"]                     # (n_pf, C)
    pos_dec = paged["dec_positions"][:, None]          # (S, 1)
    q_pf = apply_rope_heads(q_pf, pos_pf, cfg, nh, hd)
    k_pf = apply_rope_heads(k_pf, pos_pf, cfg, kvh, hd)
    v_pf = _split_heads(v_pf, kvh, hd)
    q_dec = apply_rope_heads(q_dec, pos_dec, cfg, nh, hd)
    k_dec = apply_rope_heads(k_dec, pos_dec, cfg, kvh, hd)
    v_dec = _split_heads(v_dec, kvh, hd)

    # ONE scatter covers every token this step writes: all chunk tokens in
    # span order, then one token per decode slot (pads/inactive slots are
    # routed to the null page by the host-built index arrays)
    k_flat = jnp.concatenate([k_pf.reshape(n_pf * c_len, kvh, hd),
                              k_dec.reshape(s_slots, kvh, hd)], axis=0)
    v_flat = jnp.concatenate([v_pf.reshape(n_pf * c_len, kvh, hd),
                              v_dec.reshape(s_slots, kvh, hd)], axis=0)
    with jax.named_scope("attn.kv_write"):
        new_entry = PKV.write_ragged(cache_entry, k_flat, v_flat,
                                     paged["pages"], paged["offsets"],
                                     paged["is_hi"], pcfg)

    scale = cfg.attention_multiplier
    if paged_kernel(pcfg, cfg, _FUSED_CACHE_ATTENTION):
        from repro.kernels.paged_attention import paged_ragged_attention
        with jax.named_scope("attn.kernel"):
            attn_pf, attn_dec = paged_ragged_attention(
                new_entry, q_pf, q_dec, k_pf, v_pf, paged["span_cached"],
                paged["span_ht"], paged["span_lt"], pcfg.block_size,
                scale=scale)
    else:
        with jax.named_scope("attn.fallback"):
            segs_dec = PKV.gather_segments(new_entry, paged["dec_ht"],
                                           paged["dec_lt"], pcfg,
                                           x_dec.dtype)
            attn_dec = L.decode_attention_segments(
                q_dec, segs_dec, length=paged["dec_lengths"], scale=scale)
            # chunk rows: ONE branch covers first and continuation chunks.
            # A first row's empty cached prefix (pf_start = 0) masks every
            # segment and the online-softmax merge correction underflows
            # to exactly zero, so the single chunked call IS the no-prefix
            # result for those rows.  (The previous fallback evaluated BOTH
            # variants and jnp.where-selected per row — paying the flash
            # O(C²) scores on top of the segment attention for every chunk
            # row, every step.)
            segs_pf = PKV.gather_segments(new_entry, paged["pf_ht"],
                                          paged["pf_lt"], pcfg, x_pf.dtype)
            attn_pf = L.chunked_prefill_attention(q_pf, segs_pf, k_pf,
                                                  v_pf, paged["pf_start"],
                                                  scale=scale)

    return (_attn_out(p, attn_pf, x_pf, stamp, cfg),
            _attn_out(p, attn_dec, x_dec, None, cfg)), new_entry


def _mamba_in(p: dict, x: Array, cfg: ModelConfig,
              stamp: Optional[StampConfig]) -> tuple[Array, Array, Array]:
    """Norm + in-projection + split (shared by the prefill, decode and
    unified paths so their dispatch rules cannot diverge)."""
    di, n = cfg.d_inner, cfg.ssm_state
    h = L.rms_norm(x, p["ln1"].astype(x.dtype), cfg.norm_eps)
    with jax.named_scope("stamp.in_proj"):
        if _use_fused(stamp, p["in_proj"]):
            # single-output fused kernel on the pre-mixer projection
            proj = L.stamp_fused_linear(h, p["in_proj"], None, stamp,
                                        site="in_proj")
        else:
            proj = _linear(_maybe_stamp(h, stamp, site="in_proj"),
                           p["in_proj"])
    z, xbc, dt_raw = jnp.split(proj, [di, 2 * di + 2 * n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    return z, xbc, dt


def _mamba_out(p: dict, yh: Array, z: Array, x: Array, cfg: ModelConfig,
               stamp: Optional[StampConfig], decode: bool) -> Array:
    """Gate + norm + out-projection + residual (shared across paths)."""
    y = yh.reshape(*yh.shape[:-2], cfg.d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = L.rms_norm(y, p["ssm_norm"].astype(x.dtype), cfg.norm_eps)
    # decode always passes stamp=None, so _use_fused is False there — the
    # same contract that keeps the in_proj dispatch above off the
    # sequence-transform kernel during decode
    with jax.named_scope("stamp.out_proj"):
        if _use_fused(stamp, p["out_proj"]):
            y = L.stamp_fused_linear(y, p["out_proj"], None, stamp,
                                     site="out_proj")
        else:
            y = _maybe_stamp(y, stamp, site="out_proj") if not decode else y
            y = _linear(y, p["out_proj"])
    return _residual(x, y, cfg)


def _mamba_step(p: dict, xbc: Array, dt: Array, state: Array,
                conv_cache: Array, cfg: ModelConfig, dtype
                ) -> tuple[Array, Array, Array]:
    """One-token recurrence: ``xbc`` (b, 1, conv_dim), ``dt`` (b, 1, h),
    ``state`` (b, h, p, n) f32, ``conv_cache`` (b, width-1, conv_dim).
    Returns (yh (b, 1, h, p) f32, new_state, new_conv)."""
    di, n, nh, pd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    with jax.named_scope("ssm.conv"):
        xp = jnp.concatenate([conv_cache.astype(dtype), xbc], axis=1)
        w = p["conv_w"].astype(dtype)
        y = sum(xp[:, i:i + 1] * w[i][None, None] for i in range(w.shape[0]))
        if "conv_b" in p:
            y = y + p["conv_b"].astype(dtype)
        xbc_c = jax.nn.silu(y)
        new_conv = xp[:, 1:]
    with jax.named_scope("ssm.step"):
        x_ssm, b_mat, c_mat = jnp.split(xbc_c, [di, di + n], axis=-1)
        xh = x_ssm.reshape(*x_ssm.shape[:-1], nh, pd)
        a = -jnp.exp(p["a_log"])
        da = jnp.exp(dt[:, 0] * a[None])                      # (b, h)
        upd = jnp.einsum("bhp,bn,bh->bhpn", xh[:, 0].astype(jnp.float32),
                         b_mat[:, 0].astype(jnp.float32), dt[:, 0])
        state = state * da[..., None, None] + upd
        yh = jnp.einsum("bn,bhpn->bhp", c_mat[:, 0].astype(jnp.float32),
                        state)
        yh = yh[:, None] + p["d_skip"][None, None, :, None] * \
            xh.astype(jnp.float32)
    return yh, state, new_conv


def _mamba_masked_step(p: dict, xbc: Array, dt: Array, state_all: Array,
                       conv_all: Array, act: Array, cfg: ModelConfig, dtype
                       ) -> tuple[Array, Array, Array]:
    """Masked one-token recurrence over the slot-dense pool: compute the
    update for every real slot row (``state_all``/``conv_all`` carry the
    extra null-slot row, excluded here), then keep inactive rows' state
    bit-for-bit — a slot with no RUNNING request (its token is a null pad)
    must not advance the recurrence with garbage.  Shared by the two-call
    decode step and the unified step's decode region so the parity tests
    compare one implementation with itself."""
    s_slots = act.shape[0]
    state, conv_cache = state_all[:s_slots], conv_all[:s_slots]
    yh, state_new, conv_new = _mamba_step(p, xbc, dt, state, conv_cache,
                                          cfg, dtype)
    state_new = jnp.where(act[:, None, None, None], state_new, state)
    conv_new = jnp.where(act[:, None, None], conv_new,
                         conv_cache.astype(dtype))
    return yh, state_new, conv_new


def _mamba_scan(p: dict, xbc: Array, dt: Array, cfg: ModelConfig, *,
                conv_cache: Optional[Array], init_state: Optional[Array],
                lengths: Optional[Array], dtype
                ) -> tuple[Array, Array, Array]:
    """Multi-token conv + SSD over a (possibly right-padded) span, stateful
    across calls: ``conv_cache`` / ``init_state`` carry the recurrence in
    from the previous chunk, ``lengths`` (b,) marks each row's valid token
    count.  Masking ``dt`` to zero past the valid length makes the SSD
    recurrence a *no-op* there (decay ``exp(0·a) = 1``, update weight 0),
    so the returned ``state`` is exactly the state after the last valid
    token — pad tokens never advance the recurrence (full rows multiply
    ``dt`` by 1.0: bit-identical to the unmasked path).  ``conv_tail`` is
    likewise sliced at the valid boundary.  Outputs past a row's length are
    garbage the caller discards."""
    di, n, nh, pd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    if lengths is not None:
        mask = jnp.arange(xbc.shape[1])[None, :] < lengths[:, None]
        dt = dt * mask[..., None].astype(dt.dtype)
    with jax.named_scope("ssm.conv"):
        xbc_c, conv_tail = L.causal_conv1d(xbc, p["conv_w"].astype(dtype),
                                           cache=conv_cache, lengths=lengths,
                                           bias=p.get("conv_b"))
    with jax.named_scope("ssm.scan"):
        x_ssm, b_mat, c_mat = jnp.split(xbc_c, [di, di + n], axis=-1)
        xh = x_ssm.reshape(*x_ssm.shape[:-1], nh, pd)
        yh, state = L.ssd_chunked(xh, dt, p["a_log"], b_mat, c_mat,
                                  init_state=init_state)
        yh = yh.astype(jnp.float32) + p["d_skip"][None, None, :, None] * \
            xh.astype(jnp.float32)
    return yh, state, conv_tail


def mamba_block(
    p: dict, x: Array, cfg: ModelConfig, *,
    mode: str, policy: Optional[ShardingPolicy],
    stamp: Optional[StampConfig],
    cache_entry: Optional[dict] = None, paged: Optional[dict] = None,
    seq_lengths: Optional[Array] = None,
) -> tuple[Array, Optional[dict]]:
    z, xbc, dt = _mamba_in(p, x, cfg, stamp)

    new_entry: Optional[dict] = None
    if mode == "decode" and paged is not None:
        # continuous batching: the cache entry is the slot-dense pool
        # (num_slots + 1 rows; the last is the null slot)
        assert cache_entry is not None
        state_all, conv_all = cache_entry["state"], cache_entry["conv"]
        yh, state_new, conv_new = _mamba_masked_step(
            p, xbc, dt, state_all, conv_all, paged["dec_active"], cfg,
            x.dtype)
        s_slots = x.shape[0]
        with jax.named_scope("ssm.state_write"):
            new_entry = {
                "state": state_all.at[:s_slots].set(state_new),
                "conv": conv_all.at[:s_slots].set(
                    conv_new.astype(conv_all.dtype)),
            }
    elif mode == "decode":
        assert cache_entry is not None
        yh, state, new_conv = _mamba_step(p, xbc, dt, cache_entry["state"],
                                          cache_entry["conv"], cfg, x.dtype)
        new_entry = {"state": state,
                     "conv": new_conv.astype(cache_entry["conv"].dtype)}
    elif mode == "prefill" and paged is not None:
        # chunked prefill into the slot pool: the scan is *stateful* across
        # chunk boundaries — conv tail + SSM state of the previous chunk
        # come from this request's slot row, the chunk's final state goes
        # back to it (two-call parity path; the unified step runs the same
        # math in `mamba_block_unified`).
        assert cache_entry is not None
        state_all, conv_all = cache_entry["state"], cache_entry["conv"]
        slot, valid = paged["slot"], paged["valid"]
        if paged["first"]:           # static in the two-call pair
            conv0 = jnp.zeros((1,) + conv_all.shape[1:], x.dtype)
            state0 = jnp.zeros((1,) + state_all.shape[1:], jnp.float32)
        else:
            conv0 = conv_all[slot][None].astype(x.dtype)
            state0 = state_all[slot][None]
        yh, state_f, conv_tail = _mamba_scan(
            p, xbc, dt, cfg, conv_cache=conv0, init_state=state0,
            lengths=jnp.reshape(valid, (1,)), dtype=x.dtype)
        with jax.named_scope("ssm.state_write"):
            new_entry = {
                "state": state_all.at[slot].set(state_f[0]),
                "conv": conv_all.at[slot].set(
                    conv_tail[0].astype(conv_all.dtype)),
            }
    else:
        yh, state, conv_tail = _mamba_scan(
            p, xbc, dt, cfg, conv_cache=None, init_state=None,
            lengths=seq_lengths, dtype=x.dtype)
        if mode == "prefill":
            new_entry = {"state": state, "conv": conv_tail.astype(jnp.bfloat16)}
    return _mamba_out(p, yh, z, x, cfg, stamp, decode=mode == "decode"), \
        new_entry


def mamba_block_unified(
    p: dict, x: tuple, cfg: ModelConfig, *,
    stamp: Optional[StampConfig], cache_entry: dict, paged: dict,
) -> tuple[tuple, dict]:
    """One Mamba block of the **unified ragged step** over the slot-dense
    state pool: the prefill chunk rows ``(n_pf, C, d)`` run the stateful
    chunked scan (per span — conv tail + SSM state gathered from each
    span's slot row, first chunks start from zeros via the traced
    ``pf_first`` mask, ``dt`` masked past the valid length so pads never
    advance the recurrence) and the decode slots ``(S, 1, d)`` advance the
    one-token recurrence with inactive slots masked — in one program, with
    ONE write per state array: the masked decode update covers the slot
    array, then the chunk rows scatter their final state at their own slot
    (a request is either prefilling or running, never both, so the writes
    are disjoint; unused chunk rows scatter to the null slot — row ``S`` —
    exactly as masked K/V writes route to the null page)."""
    x_pf, x_dec = x
    state_all, conv_all = cache_entry["state"], cache_entry["conv"]
    s_slots = x_dec.shape[0]

    # ---- prefill region: STaMP path, stateful per-span scan ----
    z_pf, xbc_pf, dt_pf = _mamba_in(p, x_pf, cfg, stamp)
    pf_slots = paged["pf_slots"]                   # (n_pf,), dummies -> S
    first = paged["pf_first"]
    conv0 = jnp.where(first[:, None, None], 0.0,
                      conv_all[pf_slots].astype(x_pf.dtype)
                      ).astype(x_pf.dtype)
    state0 = jnp.where(first[:, None, None, None], 0.0, state_all[pf_slots])
    yh_pf, state_f, conv_tail = _mamba_scan(
        p, xbc_pf, dt_pf, cfg, conv_cache=conv0, init_state=state0,
        lengths=paged["pf_valid"], dtype=x_pf.dtype)

    # ---- decode region: transform-free one-token recurrence, masked ----
    z_dec, xbc_dec, dt_dec = _mamba_in(p, x_dec, cfg, None)
    yh_dec, state_new, conv_new = _mamba_masked_step(
        p, xbc_dec, dt_dec, state_all, conv_all, paged["dec_active"], cfg,
        x_dec.dtype)

    with jax.named_scope("ssm.state_write"):
        st = state_all.at[:s_slots].set(state_new)
        st = st.at[pf_slots].set(state_f)
        cv = conv_all.at[:s_slots].set(conv_new.astype(conv_all.dtype))
        cv = cv.at[pf_slots].set(conv_tail.astype(conv_all.dtype))
    new_entry = {"state": st, "conv": cv}

    return (_mamba_out(p, yh_pf, z_pf, x_pf, cfg, stamp, decode=False),
            _mamba_out(p, yh_dec, z_dec, x_dec, cfg, None, decode=True)), \
        new_entry


def ffn_block(p: dict, x: Array, spec: LayerSpec, cfg: ModelConfig, *,
              stamp: Optional[StampConfig],
              valid: Optional[Array] = None, train: bool = False) -> Array:
    """The layer's FFN on the normed input, added to the residual.
    An MoE layer routes dropless when serving and with GShard capacity
    (`L.moe_ffn`, static expert buckets) when ``train``.  ``valid``
    (broadcastable to ``x``'s token axes) marks the real tokens: the
    dropless MoE routes only those (pads and idle slots take no expert
    rows)."""
    if spec.ffn == "none":
        return x
    h = L.rms_norm(x, p["ln2"].astype(x.dtype), cfg.norm_eps)
    # h stays raw here: the fused gate/up pair quantizes it inside the dual
    # kernel; only reference-path consumers see the stamped round trip
    # (computed once, shared between the MoE branch and un-fused gate/up)
    hq = None
    out = jnp.zeros_like(x)
    if spec.ffn in ("moe", "moe_dense"):
        gate_w = (p["gate_w"] if not isinstance(p["gate_w"], dict)
                  else _dequant_packed(p["gate_w"], jnp.float32))
        # the fused and reference experts see the SAME stamped round trip
        # (routing on it keeps the chosen experts bit-identical)
        hq = _maybe_stamp(h, stamp, site="moe")
        if train:
            out = out + L.moe_ffn(
                hq, gate_w, *(_expert_w(p[k], x.dtype)
                              for k in ("we_gate", "we_up", "we_down")),
                cfg.experts_per_token, cfg.capacity_factor,
                group_size=cfg.moe_group_size, offset=cfg.expert_offset)
        else:
            out = out + _moe_dropless(p, hq, gate_w, cfg, valid)
    if spec.ffn in ("mlp", "moe_dense"):
        prefix = "d" if spec.ffn == "moe_dense" else ""
        wg, wu = p[f"{prefix}wi_gate"], p[f"{prefix}wi_up"]
        with jax.named_scope("stamp.gate_up"):
            if _use_fused(stamp, wg) and _use_fused(stamp, wu):
                # ONE dual-output kernel call: the shared input's transform
                # + quantize runs once (VMEM scratch) and drives both
                # GEMMs, silu·mul epilogue included
                g = L.stamp_fused_dual_linear(h, wg, wu, stamp,
                                              site="gate_up")
            else:
                hq = (_maybe_stamp(h, stamp, site="gate_up")
                      if hq is None else hq)
                g = jax.nn.silu(_linear(hq, wg)) * _linear(hq, wu)
        with jax.named_scope("stamp.down"):
            if _use_fused(stamp, p[f"{prefix}wo_mlp"]):
                y = L.stamp_fused_linear(g, p[f"{prefix}wo_mlp"], None,
                                         stamp, site="wo_mlp")
            else:
                y = _linear(_maybe_stamp(g, stamp, site="wo_mlp"),
                            p[f"{prefix}wo_mlp"])
        out = out + y
    return _residual(x, out, cfg)


def _moe_dropless(p: dict, hq: Array, gate_w: Array, cfg: ModelConfig,
                  valid: Optional[Array]) -> Array:
    """The dropless MoE over this chip's experts on the (stamped) normed
    tokens ``hq`` (..., d): prepared int8 experts through the grouped
    kernel (`layers.moe_ffn_dropless_fused`) in prefill and decode alike,
    float experts through the dense loop.  Records the layer's counts."""
    lead, d = hq.shape[:-1], hq.shape[-1]
    xt = hq.reshape(-1, d)
    ok = jnp.ones(lead, bool) if valid is None else \
        jnp.broadcast_to(valid, lead)
    ws = [p["we_gate"], p["we_up"], p["we_down"]]
    if all(isinstance(w, dict) and "iq" in w for w in ws):
        y, counts = L.moe_ffn_dropless_fused(
            xt, gate_w, *ws, cfg.experts_per_token, ok.reshape(-1),
            offset=cfg.expert_offset)
    else:
        y, counts = L.moe_ffn_dropless(
            xt, gate_w, *(_expert_w(w, hq.dtype) for w in ws),
            cfg.experts_per_token, ok.reshape(-1), offset=cfg.expert_offset)
    _MOE_TALLY.append(counts)
    return y.reshape(hq.shape).astype(hq.dtype)


def _expert_w(w, dtype):
    if isinstance(w, dict) and "iq" in w:
        # prepared stacked (E, din, dout) int8 codes (decode / no-STaMP
        # call sites share the serving params): exact bf16 dequant — codes
        # and zero points are integers in [-128, 127]
        return ((w["iq"].astype(dtype) - w["izw"].astype(dtype))
                * w["isw"].astype(dtype))
    if isinstance(w, dict):
        return _dequant_packed(w, dtype)
    return w.astype(dtype)


def apply_block(spec: LayerSpec, p: dict, x: Array, cfg: ModelConfig, **kw
                ) -> tuple[Array, Optional[dict]]:
    stamp = kw.get("stamp")
    if kw["mode"] == "unified":
        # unified ragged step: x is the (prefill_rows, decode_slots) pair;
        # prefill keeps the STaMP path, decode the transform-free one —
        # per region, inside one program.  Attention mixes through the
        # paged pools, Mamba through the slot-dense state pool.
        if spec.mixer == "attn":
            with jax.named_scope("attn"):
                x, entry = attn_block_unified(p, x, cfg, stamp=stamp,
                                              kv_cfg=kw["kv_cfg"],
                                              cache_entry=kw["cache_entry"],
                                              paged=kw["paged"])
        elif spec.mixer == "mamba":
            with jax.named_scope("mamba"):
                x, entry = mamba_block_unified(p, x, cfg, stamp=stamp,
                                               cache_entry=kw["cache_entry"],
                                               paged=kw["paged"])
        else:
            entry = None
        paged = kw["paged"]
        c_len = x[0].shape[1]
        with jax.named_scope("mlp"):
            x_pf = ffn_block(p, x[0], spec, cfg, stamp=stamp,
                             valid=jnp.arange(c_len)[None, :]
                             < paged["pf_valid"][:, None])
            x_dec = ffn_block(p, x[1], spec, cfg, stamp=None,
                              valid=paged["dec_active"][:, None])
        return (x_pf, x_dec), entry
    if spec.mixer == "attn":
        with jax.named_scope("attn"):
            x, entry = attn_block(p, x, cfg, mode=kw["mode"],
                                  positions=kw["positions"],
                                  policy=kw.get("policy"),
                                  stamp=stamp, kv_cfg=kw["kv_cfg"],
                                  cache_entry=kw.get("cache_entry"),
                                  pos_scalar=kw.get("pos_scalar"),
                                  enc_out=kw.get("enc_out"),
                                  causal=kw.get("causal", True),
                                  cache_capacity=kw.get("cache_capacity"),
                                  paged=kw.get("paged"))
    elif spec.mixer == "mamba":
        with jax.named_scope("mamba"):
            x, entry = mamba_block(p, x, cfg, mode=kw["mode"],
                                   policy=kw.get("policy"), stamp=stamp,
                                   cache_entry=kw.get("cache_entry"),
                                   paged=kw.get("paged"),
                                   seq_lengths=kw.get("seq_lengths"))
    else:
        entry = None
    with jax.named_scope("mlp"):
        x = ffn_block(p, x, spec, cfg, stamp=stamp,
                      valid=_valid_tokens(x, kw),
                      train=kw["mode"] == "train")
    return x, entry


def _valid_tokens(x: Array, kw: dict) -> Optional[Array]:
    """Real-token mask of a (b, s, d) block input outside the unified
    step: active decode slots, a prefill chunk's valid prefix, or each
    row's ``seq_lengths``; None where every token is real."""
    paged, mode = kw.get("paged"), kw["mode"]
    pos = jnp.arange(x.shape[1])[None, :]
    if paged is not None and mode == "decode":
        return paged["dec_active"][:, None]
    if paged is not None and mode == "prefill":
        return pos < paged["valid"]
    if kw.get("seq_lengths") is not None:
        return pos < kw["seq_lengths"][:, None]
    return None


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------


def run_stack(
    params: dict, x: Array, cfg: ModelConfig, *,
    mode: str, positions: Array, policy: Optional[ShardingPolicy],
    stamp: Optional[StampConfig] = None,
    kv_cfg: KV.KVCacheConfig = KV.KVCacheConfig(quantized=False),
    cache: Optional[dict] = None, pos_scalar: Optional[Array] = None,
    enc_out: Optional[Array] = None, causal: bool = True, remat: bool = True,
    cache_capacity: Optional[int] = None, paged: Optional[dict] = None,
    seq_lengths: Optional[Array] = None,
) -> tuple[Array, Optional[dict]]:
    """Run prologue (unrolled) + periods (scanned).  Returns (x, cache).

    ``seq_lengths`` (b,) marks per-row valid prompt lengths for
    right-padded prefill: attention is pad-safe by construction (causal
    mask + per-slot logit reads), but the Mamba recurrence is sequential —
    without the mask, pad tokens after a short prompt would keep advancing
    the SSM state the decode steps then continue from."""
    pro, period, nper = cfg.layer_plan()
    kw = dict(mode=mode, positions=positions, policy=policy, stamp=stamp,
              kv_cfg=kv_cfg, pos_scalar=pos_scalar, enc_out=enc_out,
              causal=causal, cache_capacity=cache_capacity, paged=paged,
              seq_lengths=seq_lengths)

    _MOE_TALLY.clear()
    new_pro_cache = {}
    for i, spec in enumerate(pro):
        entry = None if cache is None else cache.get(f"pro{i}")
        x, ne = apply_block(spec, params["prologue"][i], x, cfg,
                            cache_entry=entry, **kw)
        if ne is not None:
            new_pro_cache[f"pro{i}"] = ne

    stateful = [j for j, s in enumerate(period) if s.mixer in ("attn", "mamba")]
    cache_per = None
    if cache is not None:
        cache_per = {str(j): cache[str(j)] for j in stateful
                     if str(j) in cache}

    if mode == "decode" and cache_per is not None and False:
        # DISABLED (§Perf decode iter 6): carrying the cache and updating at
        # a dynamic layer index forces XLA to COPY the full stacked buffers
        # every layer (read-before-write kills aliasing) — 4×0.67 GB/layer
        # measured.  The xs/ys path below only moves per-layer slices, and
        # with one-hot token writes it no longer triggers GSPMD gathers.
        def body(carry, p_slice):
            xc, cache_c, idx = carry
            cache_next = dict(cache_c)
            for j, spec in enumerate(period):
                entry = None
                if str(j) in cache_c:
                    entry = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, idx, 0, keepdims=False), cache_c[str(j)])
                xc, ne = apply_block(spec, p_slice[j], xc, cfg,
                                     cache_entry=entry, **kw)
                if ne is not None:
                    cache_next[str(j)] = jax.tree.map(
                        lambda full, upd: jax.lax.dynamic_update_index_in_dim(
                            full, upd, idx, 0), cache_next[str(j)], ne)
            xc = constrain(xc, policy, lambda pol: pol.acts())
            return (xc, cache_next, idx + 1), ()

        (x, cache_out, _), _ = jax.lax.scan(
            body, (x, cache_per, jnp.zeros((), jnp.int32)),
            params["period"])
        new_cache = dict(cache_out)
        new_cache.update(new_pro_cache)
        return x, new_cache

    # quant telemetry: records made by the prologue layers above live at
    # the outer trace level — drain them NOW so the scan body (traced
    # next) cannot capture them as closure constants and stack them
    # nper×.  The body drains its own records and returns them as extra
    # scan outputs; absorb() reduces the stacked period axis back out.
    pro_telem = QS.drain()
    pro_tally = _drain_tally()

    def body(xc, xs):
        p_slice, c_slice = xs
        new_entries = {}
        for j, spec in enumerate(period):
            entry = None if c_slice is None else c_slice.get(str(j))
            xc, ne = apply_block(spec, p_slice[j], xc, cfg,
                                 cache_entry=entry, **kw)
            if ne is not None:
                new_entries[str(j)] = ne
        xc = constrain(xc, policy, lambda pol: pol.acts())
        return xc, (new_entries, QS.drain(), _drain_tally())

    if mode == "train" and remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    xs = (params["period"], cache_per)
    x, (period_cache, period_telem, period_tally) = jax.lax.scan(body, x, xs)
    QS.absorb(period_telem)
    QS.merge_flat(pro_telem)
    _MOE_TALLY.append(pro_tally + period_tally.sum(0))
    new_cache = None
    if mode in ("prefill", "decode", "unified"):
        new_cache = dict(period_cache)
        new_cache.update(new_pro_cache)
    return x, new_cache


# ---------------------------------------------------------------------------
# losses / steps
# ---------------------------------------------------------------------------


def chunked_xent(x: Array, head, labels: Array, chunk: int = 512,
                 logits_scaling: float = 1.0) -> Array:
    """Cross-entropy without materializing (b, s, vocab): scan over sequence
    chunks (each chunk's logits live only inside the scan body).  Labels < 0
    are ignored (VLM patch positions)."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nch = s // chunk
    xs = x.reshape(b, nch, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(b, nch, chunk).swapaxes(0, 1)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def body(tot, inp):
        xc, lc = inp
        logits = _scale_logits(_linear(xc, head), logits_scaling)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        valid = (lc >= 0).astype(jnp.float32)
        loss = jnp.sum((logz - gold) * valid)
        return (tot[0] + loss, tot[1] + jnp.sum(valid)), ()

    (loss, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, ls))
    return loss / jnp.maximum(cnt, 1.0)


def _embed(params, tokens: Array, dtype=jnp.bfloat16,
           scale: float = 1.0) -> Array:
    """Embedding rows in ``dtype``, times the model's embedding
    multiplier (Granite's 12) where it is not 1."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    return x if scale == 1.0 else x * scale


def _head_weight(params):
    if "head" in params:
        return params["head"]
    return params["embed"].T


def _scale_logits(logits: Array, scaling: float) -> Array:
    """Float32 logits divided by the model's logits scaling (Granite's
    16) where it is not 1."""
    logits = logits.astype(jnp.float32)
    return logits if scaling == 1.0 else logits / scaling


def _head_logits(x: Array, params, cfg: ModelConfig) -> Array:
    """Float32 logits of normed hidden rows ``x``."""
    return _scale_logits(_linear(x, _head_weight(params)),
                         cfg.logits_scaling)


def _encoder_forward(params, frames: Array, cfg: ModelConfig,
                     policy, mode: str) -> Array:
    enc = params["encoder"]
    enc_cfg = dataclasses.replace(cfg, encoder_layers=0)
    pos = jnp.arange(frames.shape[1])[None, :]
    x = frames

    def body(xc, p_slice):
        xc, _ = apply_block(LayerSpec("attn", "mlp"), p_slice[0], xc, enc_cfg,
                            mode="train", positions=pos, policy=policy,
                            stamp=None,
                            kv_cfg=KV.KVCacheConfig(quantized=False),
                            causal=False)
        xc = constrain(xc, policy, lambda pol: pol.acts())
        return xc, ()

    if mode == "train":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, enc["period"])
    return L.rms_norm(x, enc["final_norm"].astype(x.dtype), cfg.norm_eps)


def model_hidden(params, batch: dict, cfg: ModelConfig, *,
                 mode: str, policy, stamp=None,
                 kv_cfg=KV.KVCacheConfig(quantized=False),
                 remat: bool = True,
                 cache_capacity: Optional[int] = None,
                 seq_lengths: Optional[Array] = None
                 ) -> tuple[Array, Optional[dict], Array]:
    """Shared train/prefill forward.  Returns (hidden, cache, labels).
    ``mode``: "train" (capacity-routed MoE, remat), "prefill" (fills the
    cache), or "forward" — the serving layers over whole sequences with
    no cache (calibration)."""
    # non-decode entry: clear the process-global decode-matmul flag so a
    # previous fused decode can't divert a length-1 forward off the STaMP
    # transform path (see set_fused_decode_matmul)
    set_fused_decode_matmul(False)
    compute_dtype = jnp.bfloat16
    labels = batch.get("labels")
    enc_out = None
    if cfg.frontend == "frames" or cfg.encoder_layers:
        enc_out = _encoder_forward(params, batch["frames"].astype(compute_dtype),
                                   cfg, policy, mode)
        x = _embed(params, batch["tokens"], compute_dtype,
                   cfg.embedding_multiplier)
    elif cfg.frontend == "patch":
        tok = _embed(params, batch["tokens"], compute_dtype,
                     cfg.embedding_multiplier)
        x = jnp.concatenate([batch["patches"].astype(compute_dtype), tok],
                            axis=1)
    else:
        x = _embed(params, batch["tokens"], compute_dtype,
                   cfg.embedding_multiplier)
    x = constrain(x, policy, lambda pol: pol.acts())
    positions = jnp.arange(x.shape[1])[None, :]
    x, cache = run_stack(params, x, cfg, mode=mode, positions=positions,
                         policy=policy, stamp=stamp, kv_cfg=kv_cfg,
                         enc_out=enc_out, remat=remat,
                         cache_capacity=cache_capacity,
                         seq_lengths=seq_lengths)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    return x, cache, labels


def train_loss(params, batch: dict, cfg: ModelConfig,
               policy: Optional[ShardingPolicy] = None,
               remat: bool = True) -> Array:
    x, _, labels = model_hidden(params, batch, cfg, mode="train",
                                policy=policy, remat=remat)
    return chunked_xent(x, _head_weight(params), labels,
                        logits_scaling=cfg.logits_scaling)


def prefill(params, batch: dict, cfg: ModelConfig,
            serve: ServeConfig, policy: Optional[ShardingPolicy] = None,
            last_pos: Optional[Array] = None) -> tuple[Array, dict]:
    """Full-sequence forward with STaMP activation quantization, producing
    next-token logits and the mixed-precision quantized KV cache.

    ``last_pos`` (b,) selects each row's logit position — right-padded
    batches read the logits at their true last prompt token instead of the
    final (pad) column.  Default: the last position for every row.  When
    given, it also masks the Mamba recurrence past each row's length
    (``seq_lengths = last_pos + 1``): attention never sees pad tokens
    (causal), but an SSM state *would* keep absorbing them — decode must
    continue from the state at the true last token.
    """
    seq_lengths = None if last_pos is None else \
        jnp.asarray(last_pos, jnp.int32) + 1
    collect = _collect_telemetry(serve)
    if collect:
        QS.begin()
    try:
        x, cache, _ = model_hidden(params, batch, cfg, mode="prefill",
                                   policy=policy, stamp=serve.stamp,
                                   kv_cfg=serve.kv, remat=False,
                                   cache_capacity=serve.cache_capacity,
                                   seq_lengths=seq_lengths)
    finally:
        telem = QS.end() if collect else None
    if last_pos is None:
        x_last = x[:, -1:]
    else:
        x_last = jnp.take_along_axis(x, last_pos[:, None, None], axis=1)
    logits = _head_logits(x_last, params, cfg)[:, 0]
    if collect:
        return logits, cache, telem
    return logits, cache


def decode_step(params, cache: dict, tokens: Array, pos: Array,
                cfg: ModelConfig, serve: ServeConfig,
                policy: Optional[ShardingPolicy] = None
                ) -> tuple[Array, dict]:
    """One-token decode against the quantized cache.  ``tokens``: (b,) int32;
    ``pos``: scalar int32 current length (lockstep batch) or (b,) int32
    per-slot lengths (continuous batching / right-padded prompts)."""
    set_fused_cache_attention(serve.fused_cache_attention)
    set_fused_decode_matmul(serve.fused_decode_matmul)
    compute_dtype = jnp.bfloat16
    x = _embed(params, tokens[:, None], compute_dtype,
               cfg.embedding_multiplier)
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] if pos.ndim == 1 else \
        jnp.full((1, 1), pos, jnp.int32)
    x, new_cache = run_stack(params, x, cfg, mode="decode",
                             positions=positions, policy=policy,
                             stamp=None, kv_cfg=serve.kv, cache=cache,
                             pos_scalar=pos)
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    return _head_logits(x[:, 0], params, cfg), new_cache


def init_cache(cfg: ModelConfig, batch: int, seq: int,
               serve: ServeConfig) -> dict:
    """Zero-initialized decode cache for every stateful layer position."""
    pro, period, nper = cfg.layer_plan()
    hd, kvh = cfg.resolved_head_dim, cfg.num_kv_heads
    cache: dict = {}

    def attn_entry(periods):
        entry = KV.init_layer_cache(periods, batch, seq, kvh, hd, serve.kv)
        if cfg.encoder_layers:
            s_enc = max(seq // cfg.frame_ratio, 1)
            entry["xk"] = jnp.zeros((periods, batch, s_enc, kvh, hd),
                                    jnp.bfloat16)
            entry["xv"] = jnp.zeros((periods, batch, s_enc, kvh, hd),
                                    jnp.bfloat16)
        return entry

    def ssm_entry(periods):
        di, n, nh, pd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
        return {
            "state": jnp.zeros((periods, batch, nh, pd, n), jnp.float32),
            "conv": jnp.zeros((periods, batch, cfg.conv_width - 1,
                               di + 2 * n), jnp.bfloat16),
        }

    for j, spec in enumerate(period):
        if spec.mixer == "attn":
            cache[str(j)] = attn_entry(nper)
        elif spec.mixer == "mamba":
            cache[str(j)] = ssm_entry(nper)
    for i, spec in enumerate(pro):
        if spec.mixer == "attn":
            cache[f"pro{i}"] = jax.tree.map(lambda a: a[0], attn_entry(1))
        elif spec.mixer == "mamba":
            cache[f"pro{i}"] = jax.tree.map(lambda a: a[0], ssm_entry(1))
    return cache


# ---------------------------------------------------------------------------
# continuous batching (paged cache) entry points
# ---------------------------------------------------------------------------


def init_paged_cache(cfg: ModelConfig, pcfg: "PKV.PagedCacheConfig",
                     num_slots: Optional[int] = None) -> dict:
    """Zero cache state for every stateful layer position: page pools for
    attention (block ids shared across layer positions — one allocation
    covers the whole stack, so each position gets its own pool arrays but
    the same geometry) and, for hybrid / pure-SSM stacks, slot-dense
    per-slot conv + SSM state (``num_slots`` = the engine's decode slot
    count; row ``num_slots`` is the null slot — see
    `PKV.init_ssm_slots`)."""
    pro, period, nper = cfg.layer_plan()
    hd, kvh = cfg.resolved_head_dim, cfg.num_kv_heads
    if cfg.encoder_layers:
        raise NotImplementedError(
            "paged serving does not cover encoder-decoder stacks: the "
            "cross-attention K/V is computed once from the encoder output "
            "and held dense per request — serve these through "
            "BucketedEngine (--engine bucketed)")
    specs = list(period) + list(pro)
    if any(s.mixer == "mamba" for s in specs) and num_slots is None:
        raise ValueError(
            "hybrid/SSM stacks hold slot-dense SSM state: init_paged_cache "
            "needs num_slots (the engine's max_slots) to size the per-slot "
            "state pool")
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state

    def ssm_pool(periods):
        return PKV.init_ssm_slots(periods, num_slots, cfg.conv_width,
                                  conv_dim, cfg.ssm_heads,
                                  cfg.ssm_head_dim, cfg.ssm_state)

    cache: dict = {}
    for j, spec in enumerate(period):
        if spec.mixer == "attn":
            cache[str(j)] = PKV.init_pools(nper, kvh, hd, pcfg)
        elif spec.mixer == "mamba":
            cache[str(j)] = ssm_pool(nper)
    for i, spec in enumerate(pro):
        if spec.mixer == "attn":
            cache[f"pro{i}"] = jax.tree.map(
                lambda a: a[0], PKV.init_pools(1, kvh, hd, pcfg))
        elif spec.mixer == "mamba":
            cache[f"pro{i}"] = jax.tree.map(lambda a: a[0], ssm_pool(1))
    return cache


def paged_prefill_chunk(params, pools: dict, tokens: Array, start: Array,
                        hi_table: Array, lo_table: Array, pages: Array,
                        offsets: Array, is_hi: Array, last_index: Array,
                        cfg: ModelConfig, serve: ServeConfig,
                        first: bool, slot: Optional[Array] = None,
                        policy: Optional[ShardingPolicy] = None
                        ) -> tuple:
    """One prefill chunk of one request into the paged cache.  Returns
    ``(logits (1, V), new_pools[, telemetry], moe_counts)``; the last is
    the (4,) int32 MoE counts (`layers.MOE_COUNTS`) summed over the
    layers, zeros for a model without experts.

    **Two-call parity path**: the unified engine runs prefill and decode
    through one `paged_unified_step` program; this entry (and
    `paged_decode_step`) is kept as the PR-3 step pair —
    ``PagedEngineConfig(step_mode="two_call")`` — so the parity tests can
    pin the unified step bit-for-bit against it.

    ``tokens``: (1, C) right-padded chunk; ``start``: scalar int32 tokens
    already cached — *however* they got there: earlier chunks of this
    request, a preemption swap-in, or a prefix-cache hit (the scheduler
    admits with ``pos = matched`` and the first chunk simply starts at an
    arbitrary ``start > 0``; the chunked attention reads the cached
    segment through the block table and masks ``kpos >= start``, so no
    extra plumbing exists for the prefix case);
    ``pages/offsets/is_hi``: (C,) host-computed write
    targets (pad tokens routed to the null page); ``last_index``: scalar
    chunk-local index of the prompt's final token (its logits are the
    request's first-token distribution — only meaningful on the last
    chunk); ``first``: static — Mamba layers key their chunk-state
    initialization on it (attention needs no branch: ``start = 0`` makes
    the chunked call pure causal self-attention); ``slot``: scalar int32
    decode-slot index of the request — Mamba layers carry their conv/SSM
    state across chunk boundaries through that row of the slot-dense state
    pool (required for hybrid/SSM stacks, ignored by attention-only ones).

    STaMP's sequence transform is applied per chunk (the transform window
    is the chunk, not the whole prompt): identical to the bucketed engine
    when the prompt fits one chunk, a documented approximation beyond that.
    """
    set_fused_cache_attention(serve.fused_cache_attention)
    # prefill must run the STaMP transform even at chunk width 1 — never
    # the (transform-free) decode matmul
    set_fused_decode_matmul(False)
    compute_dtype = jnp.bfloat16
    x = _embed(params, tokens, compute_dtype, cfg.embedding_multiplier)
    x = constrain(x, policy, lambda pol: pol.acts())
    c = tokens.shape[1]
    positions = (start + jnp.arange(c))[None, :]
    paged = {"cfg": serve.paged, "hi_table": hi_table, "lo_table": lo_table,
             "pages": pages, "offsets": offsets, "is_hi": is_hi,
             "start": start, "first": first,
             # slot-dense SSM state routing (hybrid stacks): the chunk's
             # valid token count is last_index + 1 on every chunk (final
             # chunks end at the prompt's last token by construction)
             "slot": slot, "valid": last_index + 1}
    collect = _collect_telemetry(serve)
    if collect:
        QS.begin()
    try:
        x, new_pools = run_stack(params, x, cfg, mode="prefill",
                                 positions=positions, policy=policy,
                                 stamp=serve.stamp, kv_cfg=serve.kv,
                                 cache=pools, paged=paged, remat=False)
    finally:
        telem = QS.end() if collect else None
    tally = _drain_tally()
    x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
    x_last = jnp.take_along_axis(x, last_index[None, None, None], axis=1)
    out = (_head_logits(x_last, params, cfg)[:, 0], new_pools)
    if collect:
        out += (telem,)
    return out + (tally,)


def paged_unified_step(params, pools: dict, pf_tokens: Array,
                       pf_start: Array, pf_length: Array, pf_first: Array,
                       pf_last_index: Array, pf_slots: Array,
                       dec_tokens: Array, dec_positions: Array,
                       dec_active: Array, hi_table: Array,
                       lo_table: Array, pages: Array, offsets: Array,
                       is_hi: Array, cfg: ModelConfig, serve: ServeConfig,
                       policy: Optional[ShardingPolicy] = None
                       ) -> tuple:
    """ONE device program per engine step: every planned prefill chunk and
    the whole decode slot array run as a single ragged batch.

    The flattened token stream is ``n_pf`` chunk spans of ``C`` tokens
    (right-padded rows of ``pf_tokens``) followed by one 1-token span per
    decode slot; the scheduler's per-span ``(query_start, query_len)``
    metadata arrives here as the span-ordered arrays below.  Inside the
    program the prefill region is built **span-major** — ``(n_pf, C, d)``,
    one batch row per span — so every sequence-axis op (the STaMP
    transform above all) applies per span and never across the flattened
    batch: the segment rule `repro.core.stamp.fold_segments` defines,
    satisfied here by construction rather than by a runtime fold (the
    ``seg_len`` stamp APIs serve callers that do hold a flattened
    carrier).  The decode region keeps the two-call path's exact
    ``(S, 1, d)`` shapes.

    ``pf_tokens``: (n_pf, C) int32 right-padded chunks (n_pf may be 0 —
    the all-decode fast case delegates to the `paged_decode_step` graph,
    single-token integer matmuls included);
    ``pf_start``: (n_pf,) tokens already cached per chunk row;
    ``pf_length``: (n_pf,) materialized length after this chunk
    (= start + valid tokens);
    ``pf_first``: (n_pf,) bool — consumed by the Mamba chunk-state
    initialization (attention needs no per-row branch: ``pf_start = 0``
    already reduces a no-prefix row to causal self-attention);
    ``pf_last_index``: (n_pf,) chunk-local index whose logits are the
    request's next-token distribution (meaningful on final chunks);
    ``pf_slots``: (n_pf,) decode-slot index per chunk row — Mamba layers
    carry conv/SSM state across chunk boundaries through that row of the
    slot-dense state pool (unused dummy rows point at the null slot, index
    ``S``);
    ``dec_tokens / dec_positions``: (S,) as in `paged_decode_step`;
    ``dec_active``: (S,) bool — True where a RUNNING request occupies the
    slot; where False the slot's (null) token must leave the per-slot
    conv/SSM state untouched (attention needs no mask: its null-page
    writes are never read);
    ``hi_table / lo_table``: (n_pf + S, ·) span-ordered block tables —
    chunk spans first (each row is that request's own table), then the
    slot array;
    ``pages / offsets / is_hi``: (n_pf·C + S,) write targets for the
    flattened token stream (pads and inactive slots → null page).

    Returns ``(pf_logits (n_pf, V), dec_logits (S, V), new_pools[,
    telemetry], moe_counts)``, the counts as in `paged_prefill_chunk`.
    """
    n_pf, c_len = pf_tokens.shape
    collect = _collect_telemetry(serve)
    if n_pf == 0:
        # all-decode fast case: decode runs transform-free (stamp=None),
        # so there is nothing to record — but the return arity must match
        # the collecting branch
        dec_logits, new_pools, tally = paged_decode_step(
            params, pools, dec_tokens, dec_positions, hi_table, lo_table,
            pages, offsets, is_hi, cfg, serve, dec_active, policy)
        pf_logits = jnp.zeros((0, dec_logits.shape[-1]), jnp.float32)
        out = (pf_logits, dec_logits, new_pools)
        if collect:
            out += ({},)
        return out + (tally,)
    assert policy is None, "unified step is single-device for now"
    set_fused_cache_attention(serve.fused_cache_attention)
    # both regions live in ONE trace, so the decode-matmul dispatch relies
    # on `_linear`'s token-dim shape guard: the (S, 1, d) decode
    # sub-tensors may take the single-token integer kernel, the (n_pf, C,
    # d) chunk rows never match it.  C == 1 would alias the two — keep the
    # transform path in that corner.
    set_fused_decode_matmul(serve.fused_decode_matmul and c_len > 1)
    compute_dtype = jnp.bfloat16
    # span-major from the start: embedding is per-token, so the (n_pf, C,
    # d) per-span view of the flattened batch is built directly
    with jax.named_scope("embed"):
        x_pf = _embed(params, pf_tokens, compute_dtype,
                      cfg.embedding_multiplier)
        x_dec = _embed(params, dec_tokens[:, None], compute_dtype,
                       cfg.embedding_multiplier)
    pos_pf = pf_start[:, None] + jnp.arange(c_len)[None, :]
    paged = {"cfg": serve.paged,
             "span_ht": hi_table, "span_lt": lo_table,
             # positions each span reads through its pages: a chunk row
             # its cached prefix, a decode slot its own token too
             "span_cached": jnp.concatenate([pf_start, dec_positions + 1]),
             "pf_ht": hi_table[:n_pf], "pf_lt": lo_table[:n_pf],
             "dec_ht": hi_table[n_pf:], "dec_lt": lo_table[n_pf:],
             "pf_positions": pos_pf, "pf_start": pf_start,
             "pf_first": pf_first, "dec_positions": dec_positions,
             "dec_lengths": dec_positions + 1,
             "pages": pages, "offsets": offsets, "is_hi": is_hi,
             # slot-dense SSM state routing (hybrid stacks)
             "pf_slots": pf_slots, "pf_valid": pf_length - pf_start,
             "dec_active": dec_active}
    if collect:
        QS.begin()
    try:
        x, new_pools = run_stack(params, (x_pf, x_dec), cfg,
                                 mode="unified", positions=None,
                                 policy=policy, stamp=serve.stamp,
                                 kv_cfg=serve.kv, cache=pools,
                                 paged=paged, remat=False)
    finally:
        telem = QS.end() if collect else None
    tally = _drain_tally()
    x_pf, x_dec = x
    with jax.named_scope("head"):
        x_pf = L.rms_norm(x_pf, params["final_norm"].astype(x_pf.dtype),
                          cfg.norm_eps)
        x_last = jnp.take_along_axis(x_pf, pf_last_index[:, None, None],
                                     axis=1)
        pf_logits = _head_logits(x_last, params, cfg)[:, 0]
        x_dec = L.rms_norm(x_dec, params["final_norm"].astype(x_dec.dtype),
                           cfg.norm_eps)
        dec_logits = _head_logits(x_dec[:, 0], params, cfg)
    out = (pf_logits, dec_logits, new_pools)
    if collect:
        out += (telem,)
    return out + (tally,)


def paged_decode_step(params, pools: dict, tokens: Array, positions: Array,
                      hi_table: Array, lo_table: Array, pages: Array,
                      offsets: Array, is_hi: Array,
                      cfg: ModelConfig, serve: ServeConfig,
                      active: Optional[Array] = None,
                      policy: Optional[ShardingPolicy] = None
                      ) -> tuple[Array, dict, Array]:
    """One decode step for the whole slot array against the paged cache.
    Returns ``(logits (S, V), new_pools, moe_counts)``, the counts as in
    `paged_prefill_chunk`.

    **Two-call parity path** (see `paged_prefill_chunk`) — and the graph
    the unified step delegates to for its all-decode fast case (n_pf = 0),
    single-token integer matmuls (`kernels/decode_matmul.py`) included.

    ``tokens``: (S,) int32 last token per slot; ``positions``: (S,) int32
    per-slot lengths (the incoming token's position); ``pages/offsets/
    is_hi``: (S,) write targets (inactive slots routed to the null page).
    Requests join and leave the slot array between steps — shapes stay
    static, inactivity is expressed entirely through the host-built index
    arrays and the per-slot lengths — except for Mamba layers, whose
    recurrence has no null page to hide behind: ``active`` (S,) bool masks
    the per-slot conv/SSM state update so an inactive slot's state is
    left untouched rather than advanced with a garbage token (defaults to
    all-active for the attention-only callers that predate it).
    """
    set_fused_cache_attention(serve.fused_cache_attention)
    set_fused_decode_matmul(serve.fused_decode_matmul)
    compute_dtype = jnp.bfloat16
    with jax.named_scope("embed"):
        x = _embed(params, tokens[:, None], compute_dtype,
                   cfg.embedding_multiplier)
    if active is None:
        active = jnp.ones(tokens.shape, bool)
    paged = {"cfg": serve.paged, "hi_table": hi_table, "lo_table": lo_table,
             "pages": pages, "offsets": offsets, "is_hi": is_hi,
             "lengths": positions + 1, "dec_active": active}
    x, new_pools = run_stack(params, x, cfg, mode="decode",
                             positions=positions[:, None], policy=policy,
                             stamp=None, kv_cfg=serve.kv, cache=pools,
                             pos_scalar=positions, paged=paged)
    tally = _drain_tally()
    with jax.named_scope("head"):
        x = L.rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps)
        logits = _head_logits(x[:, 0], params, cfg)
    return logits, new_pools, tally
