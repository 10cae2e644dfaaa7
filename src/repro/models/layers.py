"""Model building blocks, written for GSPMD-friendly lowering.

Design constraints (CPU-only container, 512-way dry-run compiles):

* memory-bounded attention: double-scan flash-style accumulation so a 32k
  prefill never materializes an (s × s) score tensor;
* GShard-style capacity-based MoE dispatch (einsum form — partitions cleanly
  with experts on the 'model' mesh axis);
* chunked Mamba2 / SSD with a `lax.scan` over chunks (state-passing);
* every op keeps the feature/flattened-head dims divisible by the TP axis —
  head-count itself may not divide the mesh (MiniCPM: 36 heads), which GSPMD
  handles via the flat projections.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.obs import quantstats as QS

Array = jax.Array


# ---------------------------------------------------------------------------
# norms & rope
# ---------------------------------------------------------------------------


def rms_norm(x: Array, gamma: Array, eps: float = 1e-5) -> Array:
    # f32 statistics.  (A bf16-square variant with f32 reduction dtype was
    # tried to stop XLA hoisting the x→f32 convert out of the remat'd
    # backward loop — it *increased* per-device HBM traffic 15–43% on the
    # dry run, so the explicit cast stays; see EXPERIMENTS.md §Perf iter 1.)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * gamma


def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                            / head_dim)).astype(np.float32)


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., s, h, hd); positions: broadcastable (..., s)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., s, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# fused STaMP linear (integer deployment path)
# ---------------------------------------------------------------------------


def stamp_fused_linear(x: Array, w: dict, b: Optional[Array],
                       stamp_cfg, merge_heads: bool = False,
                       site: Optional[str] = None) -> Array:
    """Run one STaMP linear through the fused Pallas integer kernel.

    ``w`` is a prepared-weight dict ``{"iq": (din, dout) int8, "isw": (1,
    dout), "izw": (1, dout)}`` built by `repro.models.lm.prepare_fused_weights`
    — the int8 buffers are reused across calls (no per-call dequant).  The
    kernel applies the sequence transform, mixed-precision quantization,
    integer GEMM and inverse transform in one VMEM residency, so the
    activation never materializes an intermediate in HBM.

    ``merge_heads=True`` marks ``x`` as the raw head-split ``(b, s, nh,
    hd)`` attention output (out-proj site): the head-merge reshape fuses
    with the kernel's in-VMEM quantize instead of materializing a merged
    activation first.
    """
    from repro.core.stamp import PreparedLinear, stamp_linear
    prep = PreparedLinear(qw=w["iq"], sw=w["isw"], zw=w["izw"], bias=b)
    return stamp_linear(x, None, None, stamp_cfg, prepared=prep,
                        merge_heads=merge_heads, site=site)


def stamp_fused_dual_linear(x: Array, w_gate: dict, w_up: dict,
                            stamp_cfg, site: Optional[str] = None) -> Array:
    """SwiGLU front half ``silu(x·Wg)·(x·Wu)`` through the dual-output
    fused kernel: the sequence transform + mixed-precision quantize of the
    shared input run ONCE (VMEM scratch) and drive both integer GEMMs; the
    silu·mul epilogue combines the pair in-VMEM, so the whole gate/up stage
    costs one HBM read of ``x`` and one write of the product."""
    from repro.core.stamp import PreparedLinear, stamp_dual_linear
    pg = PreparedLinear(qw=w_gate["iq"], sw=w_gate["isw"],
                        zw=w_gate["izw"], bias=None)
    pu = PreparedLinear(qw=w_up["iq"], sw=w_up["isw"],
                        zw=w_up["izw"], bias=None)
    return stamp_dual_linear(x, None, None, stamp_cfg,
                             prepared_gate=pg, prepared_up=pu,
                             epilogue="silu_mul", site=site)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _score_scale(scale: Optional[float], hd: int) -> float:
    """The attention score scale: the model's own (Granite's
    ``attention_multiplier``), else ``1/sqrt(head_dim)``."""
    return 1.0 / np.sqrt(hd) if scale is None else scale


@dataclasses.dataclass(frozen=True)
class AttnChunks:
    q: int = 2048
    kv: int = 2048


def flash_attention(
    q: Array,                # (b, sq, h, hd)
    k: Array,                # (b, skv, kv, hd)
    v: Array,
    causal: bool = True,
    chunks: AttnChunks = AttnChunks(),
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> Array:
    """Memory-bounded attention: outer scan over query chunks, inner scan
    over KV chunks with running (max, sum, acc) — the standard online-softmax
    recurrence.  GQA query heads are *grouped* against their KV head
    (no materialized KV repeat).  Causal masking is applied per
    (q-chunk, kv-chunk) pair; fully-masked pairs still lower (XLA cannot
    skip data-dependent work in a scan) — the wasted half of causal FLOPs is
    accounted for in the roofline's MODEL_FLOPS/HLO ratio.  ``scale``
    multiplies the scores (None: ``1/sqrt(hd)``).
    """
    b, sq, h, hd = q.shape
    skv, g = k.shape[1], k.shape[2]
    rep = h // g

    cq = min(chunks.q, sq)
    ckv = min(chunks.kv, skv)
    nq, nkv = sq // cq, skv // ckv
    assert sq % cq == 0 and skv % ckv == 0, (sq, cq, skv, ckv)

    scale = _score_scale(scale, hd)
    # (nq, b, g, rep, cq, hd) / (nkv, b, g, ckv, hd)
    qc = q.reshape(b, nq, cq, g, rep, hd).transpose(1, 0, 3, 4, 2, 5)
    kc = k.reshape(b, nkv, ckv, g, hd).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nkv, ckv, g, hd).transpose(1, 0, 3, 2, 4)

    q_pos_base = jnp.arange(cq)
    k_pos_base = jnp.arange(ckv)

    def q_body(_, qi_and_chunk):
        qi, qck = qi_and_chunk
        # NOTE (§Perf arctic iter 4, REVERTED): casting operands to bf16
        # with preferred_element_type=f32 left arctic's f32 collectives
        # untouched and cost prefill an extra score-sized bf16
        # materialization of `p` per KV block (−15 % on every prefill
        # cell).  f32 operands restored.
        qck32 = qck.astype(jnp.float32) * scale

        @functools.partial(jax.checkpoint,
                           policy=jax.checkpoint_policies.nothing_saveable)
        def kv_body(carry, kv_in):
            m, l, acc = carry
            ki, kck, vck = kv_in
            s = jnp.einsum("bgrqd,bgkd->bgrqk", qck32,
                           kck.astype(jnp.float32))
            if causal:
                qpos = q_offset + qi * cq + q_pos_base
                kpos = ki * ckv + k_pos_base
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bgkd->bgrqd", p, vck.astype(jnp.float32))
            return (m_new, l_new, acc_new), ()

        init = (jnp.full((b, g, rep, cq), -1e30, jnp.float32),
                jnp.zeros((b, g, rep, cq), jnp.float32),
                jnp.zeros((b, g, rep, cq, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_body, init, (jnp.arange(nkv), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, out = jax.lax.scan(q_body, None, (jnp.arange(nq), qc))
    # (nq, b, g, rep, cq, hd) -> (b, sq, h, hd)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, hd)
    return out


def decode_attention(
    q: Array,                # (b, 1, h, hd)
    k_cache: Array,          # (b, s, kv, hd)  — bf16 (already dequantized)
    v_cache: Array,
    length: Optional[Array] = None,
    scale: Optional[float] = None,
) -> Array:
    """Single-token attention over the full cache, GQA-grouped.  When the
    cache's sequence axis is sharded over the 'model' mesh axis, GSPMD turns
    the softmax max/sum reductions into all-reduces — the TPU-native
    split-KV decode."""
    out = decode_attention_segments(q, [(k_cache, v_cache, 0)],
                                    length=length, scale=scale)
    return out


def decode_attention_segments(
    q: Array,                      # (b, 1, h, hd)
    segments: list,                # [(k, v, position_offset), ...]
    length: Optional[Array] = None,
    parts: tuple = (),             # [(m, l, o)] computed elsewhere
    scale: Optional[float] = None,
) -> Array:
    """Decode attention over disjoint cache segments with a score-level
    merge: the mixed-precision cache's hi (64-token int8) and lo (int4)
    regions are attended separately and their scores concatenated — K/V are
    never concatenated along the GSPMD-sharded sequence axis (that concat
    reshards the whole cache by a 64-token offset every layer; §Perf).
    Matmuls keep bf16 operands with f32 accumulation (MXU-native).
    ``parts`` adds softmax statistics of further positions computed
    elsewhere (the paged kernel's int4 pages): ``m, l`` (b, g, rep), ``o``
    (b, g, rep, hd), unnormalised.  ``scale`` multiplies the scores
    (None: ``1/sqrt(hd)``)."""
    b, _, h, hd = q.shape
    g = segments[0][0].shape[2] if segments else parts[0][0].shape[1]
    rep = h // g
    scale = _score_scale(scale, hd)
    dtype = segments[0][0].dtype if segments else q.dtype
    qg = (q.reshape(b, g, rep, hd) * scale).astype(dtype)

    # per-segment online-softmax statistics, merged at the end — NO
    # cross-segment concatenation (concatenating a replicated 64-token hi
    # segment with a 16-way-sharded lo segment makes GSPMD replicate the
    # whole thing, dragging the packed cache through an all-gather).
    parts = list(parts)
    for k_seg, v_seg, offset in segments:
        s_seg = k_seg.shape[1]
        sc = jnp.einsum("bgrd,bsgd->bgrs", qg, k_seg,
                        preferred_element_type=jnp.float32)
        if length is not None:
            pos = offset + jnp.arange(s_seg)[None, None, None, :]
            mask = pos < length[:, None, None, None]
            sc = jnp.where(mask, sc, -1e30)
        m = jnp.max(sc, axis=-1)                        # (b, g, rep)
        p = jnp.exp(sc - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bgrs,bsgd->bgrd", p.astype(k_seg.dtype), v_seg,
                       preferred_element_type=jnp.float32)
        parts.append((m, l, o))
    out = merge_softmax_parts(parts)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def merge_softmax_parts(parts: list) -> Array:
    """Normalised attention output from the unnormalised softmax statistics
    ``(m, l, o)`` of disjoint key sets (``o`` carries one more trailing
    axis than ``m`` and ``l``).  A part with ``m = −1e30`` (every key
    masked) weighs ``exp(−1e30 − m_tot) = 0``."""
    m_tot = parts[0][0]
    for m, _, _ in parts[1:]:
        m_tot = jnp.maximum(m_tot, m)
    l_tot = jnp.zeros_like(m_tot)
    o_tot = jnp.zeros_like(parts[0][2])
    for m, l, o in parts:
        corr = jnp.exp(m - m_tot)
        l_tot = l_tot + l * corr
        o_tot = o_tot + o * corr[..., None]
    return o_tot / jnp.maximum(l_tot, 1e-30)[..., None]


def chunked_prefill_attention(
    q: Array,                      # (b, c, h, hd) — chunk queries
    segments: list,                # [(k, v, position_offset), ...] cached
    k_self: Array,                 # (b, c, kv, hd) — this chunk's raw K
    v_self: Array,
    start: Array,                  # scalar or (b,) int32: tokens cached
    parts: tuple = (),             # [(m, l, o)] computed elsewhere
    scale: Optional[float] = None,
) -> Array:
    """Attention for one continuous-batching prefill chunk: queries at
    global positions ``start + i`` attend to the **cached prefix** (the
    dequantized paged segments, strictly ``kpos < start`` — the chunk's own
    freshly written tokens are excluded so they aren't double-counted) and
    **causally to the raw chunk itself**.  Same per-segment online-softmax
    merge as `decode_attention_segments`, generalized to multiple query
    rows; a fully-masked segment's ``m = −1e30`` correction underflows to
    exactly zero.

    ``start`` may be a scalar (one chunk, the two-call engine) or a ``(b,)``
    vector (the unified ragged step batches several requests' chunks as
    rows, each with its own cached-prefix length).  ``parts`` adds softmax
    statistics of further cached positions computed elsewhere (the paged
    kernel's int4 pages): ``m, l`` (b, g, rep, c), ``o`` (b, g, rep, c,
    hd), unnormalised.  ``scale`` multiplies the scores (None:
    ``1/sqrt(hd)``)."""
    b, c, h, hd = q.shape
    g = k_self.shape[2]
    rep = h // g
    scale = _score_scale(scale, hd)
    qg = q.reshape(b, c, g, rep, hd).astype(jnp.float32) * scale
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (b,))
    qpos = start[:, None] + jnp.arange(c)[None, :]           # (b, c)

    parts = list(parts)

    def score_part(k_seg, v_seg, mask):          # mask: (b, c, s_seg) bool
        sc = jnp.einsum("bcgrd,bsgd->bgrcs", qg,
                        k_seg.astype(jnp.float32))
        sc = jnp.where(mask[:, None, None], sc, -1e30)
        m = jnp.max(sc, axis=-1)                 # (b, g, rep, c)
        p = jnp.exp(sc - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bgrcs,bsgd->bgrcd", p, v_seg.astype(jnp.float32))
        parts.append((m, l, o))

    for k_seg, v_seg, offset in segments:
        kpos = offset + jnp.arange(k_seg.shape[1])
        score_part(k_seg, v_seg,
                   jnp.broadcast_to(
                       (kpos[None, None, :] < start[:, None, None]),
                       (b, c, k_seg.shape[1])))
    kpos_self = start[:, None] + jnp.arange(k_self.shape[1])  # (b, c_kv)
    score_part(k_self, v_self,
               kpos_self[:, None, :] <= qpos[:, :, None])

    out = merge_softmax_parts(parts)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------


def swiglu_mlp(x: Array, wi_gate: Array, wi_up: Array, wo: Array) -> Array:
    g = x @ wi_gate
    u = x @ wi_up
    return (jax.nn.silu(g) * u) @ wo


def _moe_fold(x: Array, group_size: int) -> tuple[Array, Array, int]:
    """Fold ``(bsz, seq, d)`` into fixed routing groups ``(b, gs, d)`` with
    the pad-tail validity mask (pad tokens must not occupy expert slots a
    real token would have used)."""
    bsz, seq, d = x.shape
    gs = min(group_size, seq)
    pad = -seq % gs
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((bsz, pad, d), x.dtype)], axis=1)
    seq_p = seq + pad
    x = x.reshape(bsz * (seq_p // gs), gs, d)
    valid = (jnp.arange(seq_p) < seq)                          # (seq_p,)
    valid = jnp.broadcast_to(valid[None], (bsz, seq_p)) \
        .reshape(x.shape[0], gs).astype(jnp.float32)
    return x, valid, seq_p


def moe_route(
    x: Array,                 # (b, s, d) — one folded routing group per row
    gate_w: Array,            # (d, E)
    experts_per_token: int,
    capacity_factor: float,
    valid: Array,             # (b, s) f32 pad mask
) -> tuple[Array, Array, Array]:
    """GShard capacity routing of the training MoE (`moe_ffn`).

    Returns ``(combine (b,s,E,C) in x.dtype, dispatch, counts (b,E)
    int32)``.  ``counts`` is each expert bucket's kept-token occupancy —
    kept slots form a prefix of ``[0, C)`` (the capacity cumsum assigns
    positions in flat routing order).  When a
    quant-telemetry scope is open, per-expert load / drop counters ride
    the same collection protocol as the site stats.
    """
    b, s, _ = x.shape
    e = gate_w.shape[-1]
    k = experts_per_token
    cap = max(int(np.ceil(s * k / e * capacity_factor)), 1)

    logits = (x.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (b, s, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (b, s, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)                # renormalize

    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)    # (b, s, k, E)
    onehot = onehot * valid[:, :, None, None]                  # drop padding
    # position of each (token, choice) within its expert queue, top-1 first
    flat = onehot.transpose(0, 2, 1, 3).reshape(b, k * s, e)   # (b, k*s, E)
    pos = jnp.cumsum(flat, axis=1) - flat                      # (b, k*s, E)
    pos = pos.reshape(b, k, s, e).transpose(0, 2, 1, 3)        # (b, s, k, E)
    keep = (pos < cap) * onehot                                # drop overflow
    pos_cap = jnp.einsum("bske,bske->bsk", pos, keep)          # position id
    cap_onehot = jax.nn.one_hot(pos_cap, cap, dtype=jnp.float32)  # (b,s,k,C)
    # (b, s, E, C) combine weights — cast to the compute dtype immediately:
    # routing positions need exact f32 cumsums, but the big dispatch/combine
    # einsums (and their cotangents, which GSPMD moves through expert
    # all-to-alls) must stay bf16 (§Perf arctic iter 3).
    combine = jnp.einsum("bsk,bske,bskc->bsec",
                         gate_vals, keep, cap_onehot).astype(x.dtype)
    dispatch = (combine > 0).astype(x.dtype)
    counts = jnp.sum(keep, axis=(1, 2)).astype(jnp.int32)      # (b, E)
    if QS.active():
        QS.record_extra("moe_router", {
            "expert_tokens": jnp.sum(keep, axis=(0, 1, 2)),    # (E,)
            "dropped_tokens": jnp.sum(onehot) - jnp.sum(keep),
            "capacity_slots": jnp.asarray(float(b * e * cap),
                                          jnp.float32),
        })
    return combine, dispatch, counts


def moe_ffn(
    x: Array,                 # (b, s, d)
    gate_w: Array,            # (d, E)
    w_gate: Array,            # (E, d, f)
    w_up: Array,              # (E, d, f)
    w_down: Array,            # (E_h, f, d) the held experts
    experts_per_token: int,
    capacity_factor: float,
    group_size: int = 1024,
    offset: int = 0,
) -> Array:
    """GShard/Switch-style capacity-based top-k MoE: the training path
    (serving routes dropless, `moe_ffn_dropless_fused`).  Routing runs
    over the router's ``E`` experts; only the buckets of the ``E_h`` held
    ones, ``[offset, offset + E_h)``, are computed (all of them when every
    expert is held).

    Tokens are routed in fixed groups of ``group_size`` (the batch axis is
    folded with sequence sub-blocks), so the dispatch/combine tensors are
    (G, g, E, C) with C = k·g/E·cf — total footprint linear in ``group_size``
    and independent of sequence length.  Partitions over ('data' → G,
    'model' → E) without ragged ops; the einsum forms lower to
    all-to-all-like collectives under GSPMD.  Overflowing tokens are dropped
    (standard capacity semantics).

    A sequence length that doesn't divide ``group_size`` pads the tail
    group with zero tokens; padding is masked out of routing *before* the
    capacity cumsum (`_moe_fold`) and carries zero combine weight, so it
    never contributes to any output.
    """
    bsz, seq, d = x.shape
    x, valid, seq_p = _moe_fold(x, group_size)
    combine, dispatch, _ = moe_route(x, gate_w, experts_per_token,
                                     capacity_factor, valid)
    held = slice(offset, offset + w_gate.shape[0])
    combine, dispatch = combine[:, :, held], dispatch[:, :, held]

    xin = jnp.einsum("bsec,bsd->becd", dispatch, x)            # (b, E, C, d)
    g = jnp.einsum("becd,edf->becf", xin, w_gate.astype(x.dtype))
    u = jnp.einsum("becd,edf->becf", xin, w_up.astype(x.dtype))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("becf,efd->becd", h, w_down.astype(x.dtype))
    y = jnp.einsum("bsec,becd->bsd", combine, out)
    return y.reshape(bsz, seq_p, d)[:, :seq]


class RaggedDispatch(NamedTuple):
    """Dropless routing of ``T`` tokens over the experts this chip holds,
    laid out for `stamp_quant_ragged_matmul`: each held expert's rows
    grouped at a ``block_m``-aligned offset."""

    table: Array       # (3·n_tiles,) int32 tile table: fetch | expert | rows
    row_token: Array   # (n_tiles·block_m,) int32 token of each row
    pair_row: Array    # (T, k) int32 row of each choice; n_tiles·block_m
    #                    (a zero row) where the choice is not held here
    gates: Array       # (T, k) f32 gate of each choice
    counts: Array      # (4,) int32: rows routed, held, expert groups, dropped


# what `RaggedDispatch.counts` holds, in order (the engine's counters)
MOE_COUNTS = ("moe_rows_routed", "moe_rows_held", "moe_expert_groups",
              "moe_rows_dropped")


def moe_topk(x: Array, gate_w: Array, k: int) -> tuple[Array, Array]:
    """Router of the dropless MoE: f32 logits over every expert, the top
    ``k``, and their gates as the softmax over those ``k`` logits (equal
    to softmax → top-k → renormalise).  ``x``: (T, d); returns (gates
    (T, k) f32, expert ids (T, k) int32)."""
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(logits, k)
    return jax.nn.softmax(top, axis=-1), idx.astype(jnp.int32)


def _record_router(per_expert: Array, slots: int) -> None:
    """Router load under the ``moe_router`` telemetry pseudo-site (as
    `moe_route` records it): rows per held expert, none dropped, and
    ``slots``, the static row capacity of the layer's expert walk."""
    if QS.active():
        QS.record_extra("moe_router", {
            "expert_tokens": per_expert.astype(jnp.float32),
            "dropped_tokens": jnp.zeros((), jnp.float32),
            "capacity_slots": jnp.asarray(float(slots), jnp.float32),
        })


def _held(idx: Array, valid: Array, offset: int, held: int) -> Array:
    """(T, k) bool: choices of valid tokens that land on a held expert."""
    local = idx - offset
    return (local >= 0) & (local < held) & valid[:, None]


def moe_route_dropless(x: Array, gate_w: Array, k: int, valid: Array, *,
                       offset: int, held: int, block_m: int
                       ) -> RaggedDispatch:
    """Route every valid token's top-``k`` choices (nothing is dropped)
    and lay the choices that land on experts ``[offset, offset + held)``
    out as ragged row groups: expert ``e``'s rows fill
    ``ceil(count_e / block_m)`` tiles from its own tile offset, in token
    order.  The tile count is static — enough for every valid token to
    send all ``k`` choices here — so no shape depends on the routing.
    ``valid`` (T,) bool masks pad tokens out of routing."""
    t = x.shape[0]
    gates, idx = moe_topk(x, gate_w, k)
    is_held = _held(idx, valid, offset, held)
    key = jnp.where(is_held, idx - offset, held).reshape(-1)     # (T·k,)
    cnt = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    n_tiles = -(-t * min(k, held) // block_m) + min(held, t * k)
    tiles_of = (cnt + block_m - 1) // block_m
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    # pairs sorted by expert (stable: token order within an expert)
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    group0 = jnp.concatenate([jnp.cumsum(cnt) - cnt, jnp.zeros((1,),
                                                               jnp.int32)])
    rank = jnp.arange(t * k, dtype=jnp.int32) - group0[skey]
    r_max = n_tiles * block_m
    row = jnp.where(skey < held,
                    jnp.concatenate([tile_start, jnp.zeros((1,), jnp.int32)]
                                    )[skey] * block_m + rank, r_max)
    pair_row = jnp.zeros((t * k,), jnp.int32).at[order].set(row)
    row_token = jnp.zeros((r_max + 1,), jnp.int32).at[row].set(
        (order // k).astype(jnp.int32))[:r_max]
    # per tile: its expert (the group whose tile range holds it) and rows
    tid = jnp.arange(n_tiles, dtype=jnp.int32)
    expert = jnp.sum(tid[:, None] >= tile_end[None, :], axis=1)
    expert = jnp.minimum(expert, held - 1).astype(jnp.int32)
    rows = jnp.clip(cnt[expert] - (tid - tile_start[expert]) * block_m, 0,
                    block_m)
    rows = jnp.where(tid < tile_end[-1], rows, 0).astype(jnp.int32)
    from repro.kernels.stamp_matmul import tile_table
    n_held = jnp.sum(cnt)
    _record_router(cnt, r_max)
    counts = jnp.stack([jnp.sum(valid.astype(jnp.int32)) * k, n_held,
                        jnp.sum((cnt > 0).astype(jnp.int32)),
                        n_held - jnp.sum(rows)]).astype(jnp.int32)
    return RaggedDispatch(tile_table(rows, expert), row_token,
                          pair_row.reshape(t, k), gates, counts)


def moe_block_f(d: int, f: int, budget: int = 12 * 2 ** 20) -> int:
    """The grouped kernel's f tile: the whole expert width where one
    expert's three int8 matrices fit ``budget`` bytes of VMEM (so an
    expert's weights stream once per call), else halved until they do."""
    bf = f
    while 3 * d * bf > budget and bf % 2 == 0 and bf > 128:
        bf //= 2
    return bf


def moe_ffn_dropless_fused(
    x: Array,                 # (T, d) the activation the experts read
    gate_w: Array,            # (d, E) router over every expert
    w_gate: dict,             # {"iq": (E_h, d, f) int8, "isw", "izw"}
    w_up: dict,
    w_down: dict,             # {"iq": (E_h, f, d) int8, ...}
    experts_per_token: int,
    valid: Array,             # (T,) bool
    *,
    offset: int,
    block_m: int = 32,
) -> tuple[Array, Array]:
    """Dropless MoE through the grouped int8 kernel, over the ``E_h``
    experts this chip holds: route over all ``E`` experts
    (`moe_route_dropless`, named scope ``moe.route``), quantize each token
    once at 8 bits (per token, min-max), gather the held choices' codes
    into their expert's row group, run gate/up, silu·mul and down in one
    `stamp_quant_ragged_matmul` call (``moe.experts``), and sum each
    token's held choices weighted by their gates (``moe.combine``).
    Choices held elsewhere contribute zero here.  Returns the (T, d) f32
    output and the (4,) int32 counts (`MOE_COUNTS`)."""
    from repro.core.stamp import token_quantize
    from repro.kernels import ops as kops
    t, d = x.shape
    held, _, f = w_gate["iq"].shape
    with jax.named_scope("moe.route"):
        disp = moe_route_dropless(x, gate_w, experts_per_token, valid,
                                  offset=offset, held=held, block_m=block_m)
    with jax.named_scope("moe.experts"):
        qd, sd, zd = token_quantize(x)
        rt = disp.row_token
        ye = kops.stamp_quant_ragged_matmul(
            disp.table, qd[rt], sd[rt], zd[rt],
            w_gate["iq"], w_gate["isw"], w_gate["izw"],
            w_up["iq"], w_up["isw"], w_up["izw"],
            w_down["iq"], w_down["isw"], w_down["izw"],
            block_m=block_m, block_f=moe_block_f(d, f))
    with jax.named_scope("moe.combine"):
        ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)])
        y = jnp.einsum("tk,tkd->td", disp.gates, ye[disp.pair_row],
                       precision=jax.lax.Precision.HIGHEST)
    return y, disp.counts


def moe_ffn_dropless(
    x: Array,                 # (T, d)
    gate_w: Array,            # (d, E)
    w_gate: Array,            # (E_h, d, f) held experts, float
    w_up: Array,
    w_down: Array,            # (E_h, f, d)
    experts_per_token: int,
    valid: Array,             # (T,) bool
    *,
    offset: int,
) -> tuple[Array, Array]:
    """Dropless MoE over the held experts with float weights, as a dense
    loop: every held expert runs on every token and its output is
    weighted by the token's gate for it (zero where the token did not
    choose it).  The reference of `moe_ffn_dropless_fused`, and the path
    of float or reference-execution trees.  Returns the (T, d) output and
    the (4,) int32 counts (`MOE_COUNTS`; none is dropped here)."""
    held = w_gate.shape[0]
    gates, idx = moe_topk(x, gate_w, experts_per_token)
    is_held = _held(idx, valid, offset, held)
    onehot = jax.nn.one_hot(idx - offset, held, dtype=gates.dtype)
    dense = jnp.einsum("tk,tke->te", gates * is_held, onehot)
    g = jnp.einsum("td,edf->tef", x, w_gate.astype(x.dtype))
    u = jnp.einsum("td,edf->tef", x, w_up.astype(x.dtype))
    out = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u,
                     w_down.astype(x.dtype))
    per_expert = jnp.einsum("tk,tke->e", is_held.astype(gates.dtype),
                            onehot)
    _record_router(per_expert, x.shape[0] * held)
    counts = jnp.stack([jnp.sum(valid.astype(jnp.int32)) * experts_per_token,
                        jnp.sum(is_held.astype(jnp.int32)),
                        jnp.sum((per_expert > 0).astype(jnp.int32)),
                        jnp.zeros((), jnp.int32)]).astype(jnp.int32)
    return jnp.einsum("te,ted->td", dense.astype(out.dtype), out), counts


# ---------------------------------------------------------------------------
# Mamba2 / SSD (chunked, state-passing scan)
# ---------------------------------------------------------------------------


def ssd_chunked(
    x: Array,        # (b, s, h, p)   — per-head inputs
    dt: Array,       # (b, s, h)      — softplus'd step sizes
    a_log: Array,    # (h,)           — per-head log decay (A = -exp(a_log))
    b_mat: Array,    # (b, s, n)      — input projection B (single group)
    c_mat: Array,    # (b, s, n)      — output projection C
    chunk: int = 256,
    init_state: Optional[Array] = None,   # (b, h, p, n)
) -> tuple[Array, Array]:
    """State Space Duality (Mamba2 §6) chunked algorithm.

    Within a chunk the recurrence is computed in its quadratic 'attention'
    dual form; across chunks a `lax.scan` carries the (b, h, p, n) state.
    Returns (y, final_state).
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    a = -jnp.exp(a_log.astype(jnp.float32))                    # (h,)
    dta = dt.astype(jnp.float32) * a[None, None, :]            # (b, s, h)

    xc = x.reshape(bsz, nc, chunk, h, p).transpose(1, 0, 2, 3, 4)
    dtac = dta.reshape(bsz, nc, chunk, h).transpose(1, 0, 2, 3)
    dtc = dt.astype(jnp.float32).reshape(bsz, nc, chunk, h).transpose(1, 0, 2, 3)
    bc = b_mat.reshape(bsz, nc, chunk, n).transpose(1, 0, 2, 3)
    cc = c_mat.reshape(bsz, nc, chunk, n).transpose(1, 0, 2, 3)

    if init_state is None:
        init_state = jnp.zeros((bsz, h, p, n), jnp.float32)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def body(state, inp):
        xk, dtak, dtk, bk, ck = inp        # leading dim = b
        # cumulative decay within the chunk
        cum = jnp.cumsum(dtak, axis=1)                      # (b, c, h)
        # intra-chunk 'attention' matrix L_ij = exp(cum_i - cum_j) (i >= j)
        seg = cum[:, :, None, :] - cum[:, None, :, :]       # (b, c, c, h)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        # mask before the exp: above the diagonal seg > 0 can overflow,
        # and an inf there turns the backward pass's zeros into NaN
        l = jnp.exp(jnp.where(tri[None, :, :, None], seg, -jnp.inf))
        # scores: C_i · B_j weighted by decay and dt_j
        cb = jnp.einsum("bin,bjn->bij", ck, bk)             # (b, c, c)
        w = cb[..., None] * l * dtk[:, None, :, :]          # (b, c, c, h)
        y_intra = jnp.einsum("bijh,bjhp->bihp", w, xk)
        # contribution of the incoming state
        decay_in = jnp.exp(cum)                             # (b, c, h)
        y_inter = jnp.einsum("bin,bhpn,bih->bihp", ck, state, decay_in)
        # chunk summary -> next state
        decay_out = jnp.exp(cum[:, -1:, :] - cum)           # (b, c, h)
        state_new = (state * jnp.exp(cum[:, -1])[:, :, None, None]
                     + jnp.einsum("bjn,bjhp,bjh,bjh->bhpn",
                                  bk, xk, decay_out, dtk))
        return state_new, (y_intra + y_inter)

    state, yc = jax.lax.scan(body, init_state, (xc, dtac, dtc, bc, cc))
    y = yc.transpose(1, 0, 2, 3, 4).reshape(bsz, s, h, p)
    return y.astype(x.dtype), state


def causal_conv1d(x: Array, w: Array, cache: Optional[Array] = None,
                  lengths: Optional[Array] = None,
                  bias: Optional[Array] = None) -> tuple[Array, Array]:
    """Depthwise causal conv along seq.  x: (b, s, d); w: (width, d);
    ``bias`` (d,) is added before the SiLU.
    Returns (y, new_cache) where cache holds the last (width-1) inputs.

    ``lengths`` (b,) int32 marks each row's valid token count when ``x`` is
    right-padded: the returned cache is then the (width-1) inputs ending at
    the *valid* boundary, not the padded tail — the conv state a decode
    step must continue from.  Outputs past a row's length are garbage the
    caller discards (causality keeps valid outputs exact either way), and
    ``lengths=None`` (or full rows) reproduces the unsliced tail
    bit-for-bit."""
    width = w.shape[0]
    if cache is None:
        cache = jnp.zeros((x.shape[0], width - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([cache, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(width))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if width <= 1:
        new_cache = cache
    elif lengths is None:
        new_cache = xp[:, -(width - 1):]
    else:
        # row r's tail = xp[r, lengths[r] : lengths[r] + width - 1]
        # (xp coordinates: the cache prefix shifts x by width-1, so index
        # `lengths` is the first of the last width-1 *valid* inputs);
        # lengths <= s keeps the gather in range without clamping
        idx = lengths[:, None] + jnp.arange(width - 1)[None, :]
        new_cache = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    return jax.nn.silu(y), new_cache
