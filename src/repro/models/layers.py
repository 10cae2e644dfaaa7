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
from typing import Optional

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
) -> Array:
    """Memory-bounded attention: outer scan over query chunks, inner scan
    over KV chunks with running (max, sum, acc) — the standard online-softmax
    recurrence.  GQA query heads are *grouped* against their KV head
    (no materialized KV repeat).  Causal masking is applied per
    (q-chunk, kv-chunk) pair; fully-masked pairs still lower (XLA cannot
    skip data-dependent work in a scan) — the wasted half of causal FLOPs is
    accounted for in the roofline's MODEL_FLOPS/HLO ratio.
    """
    b, sq, h, hd = q.shape
    skv, g = k.shape[1], k.shape[2]
    rep = h // g

    cq = min(chunks.q, sq)
    ckv = min(chunks.kv, skv)
    nq, nkv = sq // cq, skv // ckv
    assert sq % cq == 0 and skv % ckv == 0, (sq, cq, skv, ckv)

    scale = 1.0 / np.sqrt(hd)
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
) -> Array:
    """Single-token attention over the full cache, GQA-grouped.  When the
    cache's sequence axis is sharded over the 'model' mesh axis, GSPMD turns
    the softmax max/sum reductions into all-reduces — the TPU-native
    split-KV decode."""
    out = decode_attention_segments(q, [(k_cache, v_cache, 0)],
                                    length=length)
    return out


def decode_attention_segments(
    q: Array,                      # (b, 1, h, hd)
    segments: list,                # [(k, v, position_offset), ...]
    length: Optional[Array] = None,
    parts: tuple = (),             # [(m, l, o)] computed elsewhere
) -> Array:
    """Decode attention over disjoint cache segments with a score-level
    merge: the mixed-precision cache's hi (64-token int8) and lo (int4)
    regions are attended separately and their scores concatenated — K/V are
    never concatenated along the GSPMD-sharded sequence axis (that concat
    reshards the whole cache by a 64-token offset every layer; §Perf).
    Matmuls keep bf16 operands with f32 accumulation (MXU-native).
    ``parts`` adds softmax statistics of further positions computed
    elsewhere (the paged kernel's int4 pages): ``m, l`` (b, g, rep), ``o``
    (b, g, rep, hd), unnormalised."""
    b, _, h, hd = q.shape
    g = segments[0][0].shape[2] if segments else parts[0][0].shape[1]
    rep = h // g
    scale = 1.0 / np.sqrt(hd)
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
    hd), unnormalised."""
    b, c, h, hd = q.shape
    g = k_self.shape[2]
    rep = h // g
    scale = 1.0 / np.sqrt(hd)
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
    """GShard capacity routing, shared VERBATIM by the reference and fused
    MoE paths — both consume the same combine/dispatch tensors, so kept and
    capacity-dropped token sets are bit-identical by construction.

    Returns ``(combine (b,s,E,C) in x.dtype, dispatch, counts (b,E)
    int32)``.  ``counts`` is each expert bucket's kept-token occupancy —
    kept slots form a prefix of ``[0, C)`` (the capacity cumsum assigns
    positions in flat routing order), which is what lets the grouped
    kernel's scalar-prefetch table clamp empty capacity tails.  When a
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
    w_down: Array,            # (E, f, d)
    experts_per_token: int,
    capacity_factor: float,
    group_size: int = 1024,
) -> Array:
    """GShard/Switch-style capacity-based top-k MoE (reference path).

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

    xin = jnp.einsum("bsec,bsd->becd", dispatch, x)            # (b, E, C, d)
    g = jnp.einsum("becd,edf->becf", xin, w_gate.astype(x.dtype))
    u = jnp.einsum("becd,edf->becf", xin, w_up.astype(x.dtype))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("becf,efd->becd", h, w_down.astype(x.dtype))
    y = jnp.einsum("bsec,becd->bsd", combine, out)
    return y.reshape(bsz, seq_p, d)[:, :seq]


def moe_ffn_fused(
    x: Array,                 # (b, s, d) — the stamped round-trip activation
    gate_w: Array,            # (d, E) full-precision router
    w_gate: dict,             # {"iq": (E, d, f) int8, "isw", "izw"} prepared
    w_up: dict,
    w_down: dict,             # {"iq": (E, f, d) int8, ...}
    experts_per_token: int,
    capacity_factor: float,
    group_size: int = 1024,
) -> Array:
    """Capacity MoE through the grouped STaMP kernel.

    Routing is `moe_route` on the SAME stamped activation the reference
    path sees (bit-identical kept/dropped sets).  Then, instead of
    dispatching bf16 activations into ``(b, E, C, d)`` and re-materializing
    bf16 expert weights per call, each token is quantized ONCE
    (`token_quantize` — however many of its top-k buckets it lands in), the
    dispatch gather moves int8 codes, and `stamp_quant_grouped_matmul` runs
    the gate/up/down expert stack as grouped int8 GEMMs in one kernel with
    the per-bucket occupancy as its scalar-prefetch table.
    """
    from repro.core.stamp import token_quantize
    from repro.kernels import ops as kops
    bsz, seq, d = x.shape
    xg, valid, seq_p = _moe_fold(x, group_size)
    combine, dispatch, counts = moe_route(xg, gate_w, experts_per_token,
                                          capacity_factor, valid)
    b, _, e, cap = combine.shape
    qd, sd, zd = token_quantize(xg)
    # slot c of expert e holds the c-th kept token in sequence order, so
    # the argmax over the one-hot sequence axis IS the gather index;
    # empty slots gather token 0 and are zeroed by the kernel's count mask
    src = jnp.argmax(dispatch, axis=1)                         # (b, E, C)
    idx = src.reshape(b, e * cap, 1)

    def gather(t):
        return jnp.take_along_axis(t, idx, axis=1).reshape(b, e, cap, -1)

    ye = kops.stamp_quant_grouped_matmul(
        gather(qd), gather(sd), gather(zd), counts,
        w_gate["iq"], w_gate["isw"], w_gate["izw"],
        w_up["iq"], w_up["isw"], w_up["izw"],
        w_down["iq"], w_down["isw"], w_down["izw"])
    y = jnp.einsum("bsec,becd->bsd", combine, ye.astype(x.dtype))
    return y.reshape(bsz, seq_p, d)[:, :seq]


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
        l = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
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
                  lengths: Optional[Array] = None) -> tuple[Array, Array]:
    """Depthwise causal conv along seq.  x: (b, s, d); w: (width, d).
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
