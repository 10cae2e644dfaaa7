"""STaMP: the sequence-transformed, mixed-precision linear layer (Fig. 2a).

The algorithm for ``y = act_quant(X) @ W + β`` under STaMP:

    1.  ``T = L · X``                      (sequence transform, §3)
    2.  ``T = T · R``                      (optional feature transform;
                                            ``R⁻¹`` is pre-folded into W)
    3.  ``Tq = Q(T)``                       (mixed-precision fake quant,
                                            first ``num_hi`` tokens hi-bit)
    4.  ``Y = Tq · W'``                     (W' = R⁻¹ W, possibly int)
    5.  ``y = L⁻¹ · Y + 1βᵀ``               (inverse transform then bias —
                                            Eq. 7 commutation)

``L`` is never materialized: DWT/DCT/WHT are applied as fast operators.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import quant as Q
from repro.core import transforms as T
from repro.obs import quantstats as QS

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class StampConfig:
    """Configuration for STaMP activation quantization.

    Defaults reproduce the paper's headline setting: Haar DWT, 3 levels,
    64 tokens at 8 bits, rest at 4 bits (avg 4.0625–4.125), first-token
    exception on for LLMs (§B.2).
    """

    seq_transform: str = "dwt"       # none|dwt|dwt2d|dct|wht|klt
    levels: Optional[int] = None     # None = auto: log2(seq / num_hi), so the
                                     # low-pass band aligns with the hi-bit
                                     # token budget (total cost stays O(s·d))
    num_hi_tokens: int = 64
    hi_bits: int = 8
    lo_bits: int = 4
    skip_first_token: bool = True    # attention-sink exception (§B.2)
    granularity: str = "token"       # token | block
    block_size: int = 64
    hw: Optional[tuple[int, int]] = None   # (H, W) grid for dwt2d
    enabled: bool = True
    execution: str = "reference"     # reference | fused (Pallas integer path)
    fused_weight_bits: int = 8       # weight codes for on-the-fly prepare

    def bits_vector(self, seq_len: int) -> Array:
        return Q.mixed_precision_bits(seq_len, self.num_hi_tokens,
                                      self.hi_bits, self.lo_bits)

    def resolved_levels(self, seq_len: int) -> int:
        if self.levels is not None:
            return self.levels
        import math
        ratio = max(seq_len / max(self.num_hi_tokens, 1), 2)
        return max(1, int(math.ceil(math.log2(ratio))))

    def average_bits(self, seq_len: int) -> float:
        return Q.average_bits(self.bits_vector(seq_len))


# ---------------------------------------------------------------------------
# segment-aware application (the unified ragged serving step)
# ---------------------------------------------------------------------------
#
# The unified prefill+decode step flattens several requests' tokens into one
# batch.  STaMP's sequence transform is defined per *sequence span* — mixing
# tokens of different requests through the DWT/WHT butterflies would be
# numerically meaningless — so every sequence-axis op on the flattened batch
# must first fold the span structure back into the batch axis.  With the
# uniform span padding the scheduler produces (each prefill chunk padded to
# the same ``seg_len``), that fold is a pure reshape: the transform then
# runs independently per span exactly as it does for a lone chunk, and the
# fused kernels see spans as batch grid rows (their transform+quantize
# scratch is per grid row already, so no kernel change is needed beyond the
# fold).  Decode spans are single tokens — their "transform" is the
# identity, which is why the decode path applies no sequence transform.


def fold_segments(x: Array, seg_len: int) -> Array:
    """View a flattened ``(b, n·seg_len, …)`` ragged batch as
    ``(b·n, seg_len, …)`` so sequence-axis ops (the STaMP transform above
    all) apply per span and never across the flattened batch."""
    b, t = x.shape[0], x.shape[1]
    if t % seg_len:
        raise ValueError(f"flattened length {t} is not a whole number of "
                         f"{seg_len}-token segments")
    return x.reshape(b * (t // seg_len), seg_len, *x.shape[2:])


def unfold_segments(y: Array, batch: int) -> Array:
    """Inverse of :func:`fold_segments`: ``(b·n, seg_len, …)`` back to the
    flattened ``(b, n·seg_len, …)`` layout."""
    bn, seg_len = y.shape[0], y.shape[1]
    return y.reshape(batch, (bn // batch) * seg_len, *y.shape[2:])


def apply_seq_transform(x: Array, cfg: StampConfig, axis: int = -2,
                        basis: Optional[Array] = None) -> Array:
    if not cfg.enabled or cfg.seq_transform == "none":
        return x
    return T.sequence_transform(
        x, cfg.seq_transform, axis=axis,
        levels=cfg.resolved_levels(x.shape[axis]),
        skip_first=cfg.skip_first_token, hw=cfg.hw, basis=basis)


def invert_seq_transform(y: Array, cfg: StampConfig, axis: int = -2,
                         basis: Optional[Array] = None) -> Array:
    if not cfg.enabled or cfg.seq_transform == "none":
        return y
    return T.inverse_sequence_transform(
        y, cfg.seq_transform, axis=axis,
        levels=cfg.resolved_levels(y.shape[axis]),
        skip_first=cfg.skip_first_token, hw=cfg.hw, basis=basis)


def stamp_fake_quant(x: Array, cfg: StampConfig, axis: int = -2,
                     basis: Optional[Array] = None,
                     seg_len: Optional[int] = None,
                     site: Optional[str] = None) -> Array:
    """Full STaMP round trip on an activation: ``L⁻¹ Q(L X)`` — used when a
    consumer needs the activation back in the original domain (e.g. KV-cache
    values feeding non-linear attention math).

    ``seg_len`` marks ``x`` as a flattened ragged batch of uniform
    ``seg_len``-token spans along axis 1: the round trip applies per span
    (see :func:`fold_segments`), identical to running each span alone."""
    if not cfg.enabled:
        return x
    if seg_len is not None and seg_len != x.shape[1]:
        if axis not in (-2, x.ndim - 2):
            raise ValueError("segments fold along axis 1")
        return unfold_segments(
            stamp_fake_quant(fold_segments(x, seg_len), cfg, axis=-2,
                             basis=basis, site=site), x.shape[0])
    # f32 transform + quant statistics: bf16 butterflies perturb the min/max
    # scales enough to flip 4-bit codes, which would make the reference and
    # fused paths (kernel computes in f32) diverge beyond quant tolerance.
    tx = apply_seq_transform(x.astype(jnp.float32), cfg, axis=axis,
                             basis=basis)
    bits = cfg.bits_vector(tx.shape[axis])
    if axis in (-2, x.ndim - 2):     # telemetry assumes (..., s, d) layout
        QS.record(site, tx, bits, cfg.hi_bits)
    if cfg.granularity == "block":
        # per-(token, block) scales — bits stays per-token
        tq = _blockwise_mixed(tx, bits, cfg.block_size)
    else:
        tq = Q.fake_quant(tx, bits, axis=-1)
    return invert_seq_transform(tq, cfg, axis=axis,
                                basis=basis).astype(x.dtype)


def _blockwise_mixed(tx: Array, bits: Array, block_size: int) -> Array:
    *lead, s, d = tx.shape
    if d % block_size:
        return Q.fake_quant(tx, bits, axis=-1)
    xb = tx.reshape(*lead, s, d // block_size, block_size)
    bitsb = bits[:, None]  # per-token bits broadcast over feature blocks
    n = 2.0 ** bitsb - 1.0
    mn = jnp.min(xb, axis=-1, keepdims=True)
    mx = jnp.max(xb, axis=-1, keepdims=True)
    scale = jnp.maximum((mx - mn) / n[..., None], 1e-8)
    zp = jnp.round(-mn / scale)
    q = jnp.clip(jnp.round(xb / scale) + zp, 0.0, n[..., None])
    deq = ((q - zp) * scale).astype(tx.dtype)
    return deq.reshape(*lead, s, d)


# ---------------------------------------------------------------------------
# fused (integer) execution path
# ---------------------------------------------------------------------------


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("qw", "sw", "zw", "bias", "layer"),
                   meta_fields=())
@dataclasses.dataclass(frozen=True)
class PreparedLinear:
    """Deployment weight buffers for the fused path: signed-int8 codes plus
    per-output-channel affine params, quantized **once** at preparation time
    instead of re-materializing bf16 weights on every call.  With a
    ``layer`` index the buffers are the layer scan's stacks and the kernels
    read that layer in place."""

    qw: Array               # (din, dout) int8, codes shifted by -2^(b-1)
    sw: Array               # (1, dout) f32 scale
    zw: Array               # (1, dout) f32 zero point (same shift applied)
    bias: Optional[Array]   # (dout,) or None
    layer: Optional[Array] = None   # () int32 index into (L, ·, ·) stacks

    def dequant(self, dtype=jnp.bfloat16) -> Array:
        qw, sw, zw = self.qw, self.sw, self.zw
        if self.layer is not None:
            qw, sw, zw = (jax.lax.dynamic_index_in_dim(a, self.layer, 0,
                                                       keepdims=False)
                          for a in (qw, sw, zw))
        return ((qw.astype(jnp.float32) - zw) * sw).astype(dtype)


def prepare_linear(
    w: Optional[Array] = None,
    b: Optional[Array] = None,
    w_quant: Optional[Q.QuantizedWeight] = None,
    bits: int = 8,
) -> PreparedLinear:
    """Build the fused path's cached weight buffers.

    From ``w_quant`` the existing integer codes are reused bit-exactly
    (shifted into signed storage, zero point shifted identically); from a
    raw ``w`` a per-output-channel asymmetric min-max quantization at
    ``bits`` is applied.  ``axis=-2`` reduction, so stacked ``(layers, din,
    dout)`` weights prepare in one call — the gate/up pair of a SwiGLU MLP
    stacks to ``(2, din, dout)`` and prepares as one call too (per-channel
    scales make the stacked prepare identical to two separate ones); see
    `repro.models.lm.prepare_fused_weights`.
    """
    if w_quant is not None:
        if w_quant.bits > 8:
            raise ValueError("fused path stores weight codes in int8")
        shift = 1 << (w_quant.bits - 1)
        qw = (w_quant.q.astype(jnp.int32) - shift).astype(jnp.int8)
        return PreparedLinear(qw=qw, sw=w_quant.scale.astype(jnp.float32),
                              zw=(w_quant.zero_point - shift).astype(jnp.float32),
                              bias=b)
    if bits > 8:
        raise ValueError("fused path stores weight codes in int8")
    n = float(2**bits - 1)
    shift = float(1 << (bits - 1))
    wf = w.astype(jnp.float32)
    # anchor the range at zero: guarantees zp ∈ [0, n], so the signed-shifted
    # zero point stays a bf16-exact small integer (the decode-path dequant in
    # models/lm.py relies on this; an unanchored one-sided channel would
    # push zp to ±range/step and round in bf16)
    mn = jnp.minimum(jnp.min(wf, axis=-2, keepdims=True), 0.0)
    mx = jnp.maximum(jnp.max(wf, axis=-2, keepdims=True), 0.0)
    sw = jnp.maximum((mx - mn) / n, 1e-8)
    zp = jnp.round(-mn / sw)
    qw = (jnp.clip(jnp.round(wf / sw) + zp, 0.0, n) - shift).astype(jnp.int8)
    return PreparedLinear(qw=qw, sw=sw, zw=zp - shift, bias=b)


def token_quantize(x: Array, bits: int = 8
                   ) -> tuple[Array, Array, Array]:
    """Per-token asymmetric min-max quantize in the **token domain** — the
    grouped MoE path's dispatch-buffer format.  The STaMP round trip
    (transform + mixed-precision quantize + inverse) has already shaped
    ``x``; this re-codes each token once, *before* dispatch, so a top-k
    routed token is quantized a single time however many expert buckets it
    lands in and the dispatch gather moves int8 codes instead of bf16
    activations.  Returns signed int8 codes plus ``(..., 1)`` f32 scale
    and identically shifted zero point (the `_int_gemm` convention)."""
    n = float(2 ** bits - 1)
    shift = float(1 << (bits - 1))
    xf = x.astype(jnp.float32)
    mn = jnp.min(xf, axis=-1, keepdims=True)
    mx = jnp.max(xf, axis=-1, keepdims=True)
    s = jnp.maximum((mx - mn) / n, 1e-8)
    z = jnp.round(-mn / s)
    q = (jnp.clip(jnp.round(xf / s) + z, 0.0, n) - shift).astype(jnp.int8)
    return q, s, z - shift


def fused_ineligibility(cfg: StampConfig,
                        feature_rot: Optional[Array] = None
                        ) -> tuple:
    """Why this config canNOT run the fused Pallas kernel, as a tuple of
    structured reason codes (empty == fused-eligible).  The codes are the
    machine-readable half of the eligibility audit
    (``repro.analysis.contracts``): the ROADMAP's "silently fall back"
    configs (dense bases, per-block scales, activation rotations, bit
    widths beyond int8 storage) each map to a stable code here instead of
    an implicit branch fall in :func:`stamp_linear`."""
    from repro.kernels.stamp_matmul import FUSABLE_TRANSFORMS
    reasons = []
    if not cfg.enabled:
        reasons.append("stamp_disabled")
    if cfg.execution != "fused":
        reasons.append("execution_reference")
    if cfg.granularity != "token":
        # per-block scale plumbing has no kernel treatment yet (ROADMAP)
        reasons.append(f"granularity_{cfg.granularity}")
    if cfg.seq_transform not in FUSABLE_TRANSFORMS:
        # dense O(s²) bases / latent-grid reads don't tile
        reasons.append(f"transform_not_fusable:{cfg.seq_transform}")
    if max(cfg.hi_bits, cfg.lo_bits, cfg.fused_weight_bits) > 8:
        # activation AND weight codes live in int8 storage
        reasons.append("bits_exceed_int8")
    if feature_rot is not None:
        reasons.append("feature_rotation")
    return tuple(reasons)


def fused_eligible(cfg: StampConfig, feature_rot: Optional[Array] = None
                   ) -> bool:
    """Whether this config can run the fused Pallas kernel; anything else
    stays on the reference path — see :func:`fused_ineligibility` for the
    structured per-reason breakdown."""
    return not fused_ineligibility(cfg, feature_rot)


def _fused_linear(x: Array, prep: PreparedLinear, cfg: StampConfig,
                  merge_heads: bool = False) -> Array:
    from repro.kernels import ops as kops
    if merge_heads:
        # raw head-split attention output: keep the (nh, hd) axes intact
        # down to the kernel, which merges them on the in-VMEM tile
        *lead, s, nh, hd = x.shape
        xk = x.reshape(-1, s, nh, hd)
    else:
        *lead, s, d = x.shape
        xk = x.reshape(-1, s, d)
    y = kops.stamp_quant_matmul(
        xk, prep.qw, prep.sw, prep.zw, prep.bias, layer=prep.layer,
        transform=cfg.seq_transform, levels=cfg.resolved_levels(s),
        skip_first=cfg.skip_first_token, num_hi=cfg.num_hi_tokens,
        hi_bits=cfg.hi_bits, lo_bits=cfg.lo_bits, out_dtype=x.dtype)
    return y.reshape(*lead, s, y.shape[-1])


def stamp_linear(
    x: Array,
    w: Optional[Array],
    b: Optional[Array],
    cfg: StampConfig,
    *,
    w_quant: Optional[Q.QuantizedWeight] = None,
    basis: Optional[Array] = None,
    feature_rot: Optional[Array] = None,
    prepared: Optional[PreparedLinear] = None,
    merge_heads: bool = False,
    seg_len: Optional[int] = None,
    site: Optional[str] = None,
) -> Array:
    """STaMP linear layer (Fig. 2a).

    ``feature_rot`` is the feature-transform matrix R applied to the
    activation; callers must pre-fold ``R⁻¹`` into ``w`` (QuaRot-style).
    ``w_quant`` replaces ``w`` with its dequantized int approximation
    (W4 path).  The bias is added *after* the inverse sequence transform,
    which is exact per Eq. 7.

    With ``cfg.execution == "fused"`` (and a fusable transform/granularity)
    the whole chain runs in one Pallas kernel on integer weights: pass
    ``prepared`` (see :func:`prepare_linear`) to reuse cached int8 buffers
    across calls; otherwise they are prepared on the fly from ``w_quant``'s
    codes or ``w``.

    ``merge_heads`` marks ``x`` as the raw head-split attention output
    ``(..., s, nh, hd)`` (out-proj site): the fused kernel merges the head
    axes on its in-VMEM tile, the fallback paths merge up front.

    ``seg_len`` marks ``x`` as a flattened ragged batch of uniform
    ``seg_len``-token spans (the unified serving step): the sequence
    transform and its inverse apply per span — spans fold into the batch
    axis, so the fused kernel sees them as independent grid rows and the
    reference path as independent batch rows.
    """
    if seg_len is not None and x.ndim >= 3 and seg_len != x.shape[1]:
        y = stamp_linear(fold_segments(x, seg_len), w, b, cfg,
                         w_quant=w_quant, basis=basis,
                         feature_rot=feature_rot, prepared=prepared,
                         merge_heads=merge_heads, site=site)
        return unfold_segments(y, x.shape[0])
    if fused_eligible(cfg, feature_rot) and \
            (w_quant is None or w_quant.bits <= 8):
        _record_fused(x, cfg, site, merge_heads=merge_heads)
        prep = prepared
        if prep is None:
            prep = prepare_linear(w, b, w_quant=w_quant,
                                  bits=cfg.fused_weight_bits)
        elif b is not None:
            # explicit bias wins over the prepared one (matches the
            # reference fallback below)
            prep = dataclasses.replace(prep, bias=b)
        return _fused_linear(x, prep, cfg, merge_heads=merge_heads)
    if merge_heads:
        x = x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])

    if w is None and w_quant is None and prepared is not None:
        # reference fallback for a caller that only holds prepared buffers
        w = prepared.dequant(x.dtype)
        b = prepared.bias if b is None else b

    if not cfg.enabled:
        wmat = w_quant.dequant(x.dtype) if w_quant is not None else w
        y = x @ wmat
        return y + b if b is not None else y

    tq = _reference_quantize(x, cfg, basis=basis, feature_rot=feature_rot,
                             site=site)
    wmat = w_quant.dequant(x.dtype) if w_quant is not None else w
    y = tq.astype(x.dtype) @ wmat
    y = invert_seq_transform(y, cfg, basis=basis)
    if b is not None:
        y = y + b
    return y


def _reference_quantize(x: Array, cfg: StampConfig,
                        basis: Optional[Array] = None,
                        feature_rot: Optional[Array] = None,
                        site: Optional[str] = None) -> Array:
    """Reference-path transformed + fake-quantized activation (shared by
    the single and dual linears, so their quantization semantics can't
    diverge)."""
    tx = apply_seq_transform(x.astype(jnp.float32), cfg, basis=basis)
    if feature_rot is not None:
        tx = tx @ feature_rot.astype(tx.dtype)
    bits = cfg.bits_vector(tx.shape[-2])
    QS.record(site, tx, bits, cfg.hi_bits)
    if cfg.granularity == "block":
        return _blockwise_mixed(tx, bits, cfg.block_size)
    return Q.fake_quant(tx, bits, axis=-1)


def _record_fused(x: Array, cfg: StampConfig, site: Optional[str],
                  merge_heads: bool = False) -> None:
    """Quant-health telemetry for the fused path: the kernel fuses
    transform→quantize→GEMM into one program, so the transform and the
    per-token scale statistics are recomputed HERE with plain jnp ops —
    extra FLOPs inside the same traced program, never an extra device
    dispatch (the no-op case costs nothing: collection is off at trace
    time unless the entry point opened a scope)."""
    if not QS.active() or site is None or not cfg.enabled:
        return
    xm = x.reshape(*x.shape[:-2], -1) if merge_heads else x
    tx = apply_seq_transform(xm.astype(jnp.float32), cfg)
    QS.record(site, tx, cfg.bits_vector(tx.shape[-2]), cfg.hi_bits)


def stamp_dual_linear(
    x: Array,
    w_gate: Optional[Array],
    w_up: Optional[Array],
    cfg: StampConfig,
    *,
    b_gate: Optional[Array] = None,
    b_up: Optional[Array] = None,
    basis: Optional[Array] = None,
    prepared_gate: Optional[PreparedLinear] = None,
    prepared_up: Optional[PreparedLinear] = None,
    epilogue: str = "silu_mul",
    seg_len: Optional[int] = None,
    site: Optional[str] = None,
):
    """STaMP gate/up pair sharing ONE transform+quantize of ``x``.

    The fused path issues a single dual-output kernel call
    (`kernels.stamp_matmul.stamp_quant_dual_matmul_pallas`): the sequence
    transform and mixed-precision quantize of the shared MLP input run once
    into VMEM scratch and drive both integer GEMMs.  The reference path
    shares the transformed/fake-quantized activation across two plain
    matmuls — mathematically the same single quantization (``L⁻¹`` commutes
    with the right-multiplication), just unfused.

    ``epilogue="silu_mul"`` returns ``silu(gate)·up`` (the SwiGLU front
    half, combined in the original token domain); ``"none"`` the tuple.
    ``seg_len``: flattened uniform-span ragged batch, transformed per span
    (see :func:`stamp_linear`).
    """
    if epilogue not in ("silu_mul", "none"):
        raise ValueError(f"unknown epilogue {epilogue!r}")
    if seg_len is not None and seg_len != x.shape[1]:
        y = stamp_dual_linear(fold_segments(x, seg_len), w_gate, w_up, cfg,
                              b_gate=b_gate, b_up=b_up, basis=basis,
                              prepared_gate=prepared_gate,
                              prepared_up=prepared_up, epilogue=epilogue,
                              site=site)
        if epilogue == "silu_mul":
            return unfold_segments(y, x.shape[0])
        return tuple(unfold_segments(o, x.shape[0]) for o in y)
    if fused_eligible(cfg):
        _record_fused(x, cfg, site)
        prep_g = prepared_gate if prepared_gate is not None else \
            prepare_linear(w_gate, b_gate, bits=cfg.fused_weight_bits)
        prep_u = prepared_up if prepared_up is not None else \
            prepare_linear(w_up, b_up, bits=cfg.fused_weight_bits)
        from repro.kernels import ops as kops
        *lead, s, d = x.shape
        y = kops.stamp_quant_dual_matmul(
            x.reshape(-1, s, d),
            prep_g.qw, prep_g.sw, prep_g.zw,
            prep_u.qw, prep_u.sw, prep_u.zw,
            prep_g.bias if b_gate is None else b_gate,
            prep_u.bias if b_up is None else b_up, layer=prep_g.layer,
            transform=cfg.seq_transform, levels=cfg.resolved_levels(s),
            skip_first=cfg.skip_first_token, num_hi=cfg.num_hi_tokens,
            hi_bits=cfg.hi_bits, lo_bits=cfg.lo_bits, epilogue=epilogue,
            out_dtype=x.dtype)
        if epilogue == "silu_mul":
            return y.reshape(*lead, s, y.shape[-1])
        return tuple(o.reshape(*lead, s, o.shape[-1]) for o in y)

    def resolve(w, prep, b):
        if w is None and prep is not None:
            w = prep.dequant(x.dtype)
            b = prep.bias if b is None else b
        return w, b

    w_gate, b_gate = resolve(w_gate, prepared_gate, b_gate)
    w_up, b_up = resolve(w_up, prepared_up, b_up)

    if not cfg.enabled:
        g = x @ w_gate
        u = x @ w_up
    else:
        # one shared reference-path quantization, two matmuls
        tq = _reference_quantize(x, cfg, basis=basis,
                                 site=site).astype(x.dtype)
        g = invert_seq_transform(tq @ w_gate, cfg, basis=basis)
        u = invert_seq_transform(tq @ w_up, cfg, basis=basis)
    if b_gate is not None:
        g = g + b_gate
    if b_up is not None:
        u = u + b_up
    if epilogue == "silu_mul":
        return jax.nn.silu(g) * u
    return g, u
