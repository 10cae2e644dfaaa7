"""Pallas TPU kernel: fused decode attention over the packed int4/int8 KV
cache — the deployment form of STaMP's mixed-precision cache.

The XLA path (see §Perf decode iters) must materialize dequantized bf16
K/V in HBM (~67 MB/layer/device at 32k) before the attention einsums.  This
kernel reads the *packed* cache (0.52 B/value average) into VMEM,
dequantizes in-register, and runs both attention matmuls in one residency:

    per-(batch, kv-head, lo-block) program:
      k_hi (64, hd) int8 + k_lo (block_s, hd/2) u8 → dequant in VMEM
      scores (rep, ·) → online-softmax (m, l, acc) accumulated across
      lo-blocks in the revisited output ref → out (rep, hd)

HBM traffic per layer ≈ packed cache + scales + q + out ≈ 19 MB/device —
the ~34× memory-term headroom quantified in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _kernel(q_ref, khi_ref, klo_ref, kshi_ref, kzhi_ref, kslo_ref, kzlo_ref,
            vhi_ref, vlo_ref, vshi_ref, vzhi_ref, vslo_ref, vzlo_ref,
            len_ref, o_ref, *, hi_len: int, block_s: int, scale: float):
    blk = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale            # (rep, hd)
    hd = q.shape[-1]
    length = len_ref[0]          # this batch row's length (per-slot block)

    def dequant_hi(qref, sref, zref):
        codes = qref[0, :, 0].astype(jnp.float32)          # (hi, hd)
        s = sref[0, :, 0].astype(jnp.float32)[:, None]
        z = zref[0, :, 0].astype(jnp.float32)[:, None]
        return (codes - z) * s

    def dequant_lo(qref, sref, zref):
        packed = qref[0, :, 0]                             # (bs, hd/2)
        hi_nib = (packed >> 4).astype(jnp.float32)
        lo_nib = (packed & 0xF).astype(jnp.float32)
        vals = jnp.stack([hi_nib, lo_nib], axis=-1).reshape(
            packed.shape[0], hd)
        s = sref[0, :, 0].astype(jnp.float32)[:, None]
        z = zref[0, :, 0].astype(jnp.float32)[:, None]
        return (vals - z) * s

    k_lo = dequant_lo(klo_ref, kslo_ref, kzlo_ref)
    v_lo = dequant_lo(vlo_ref, vslo_ref, vzlo_ref)
    pos_lo = hi_len + blk * block_s + jnp.arange(block_s)
    s_lo = q @ k_lo.T                                      # (rep, bs)
    s_lo = jnp.where((pos_lo < length)[None, :], s_lo, -1e30)
    m_blk = jnp.max(s_lo, axis=-1)
    p_lo = jnp.exp(s_lo - m_blk[:, None])
    l_blk = jnp.sum(p_lo, axis=-1)
    o_blk = p_lo @ v_lo                                    # (rep, hd)

    @pl.when(blk == 0)
    def _first():
        k_hi = dequant_hi(khi_ref, kshi_ref, kzhi_ref)
        v_hi = dequant_hi(vhi_ref, vshi_ref, vzhi_ref)
        pos_hi = jnp.arange(hi_len)
        s_hi = q @ k_hi.T
        s_hi = jnp.where((pos_hi < length)[None, :], s_hi, -1e30)
        m0 = jnp.maximum(jnp.max(s_hi, axis=-1), m_blk)
        p_hi = jnp.exp(s_hi - m0[:, None])
        corr = jnp.exp(m_blk - m0)
        l0 = jnp.sum(p_hi, axis=-1) + l_blk * corr
        o0 = p_hi @ v_hi + o_blk * corr[:, None]
        o_ref[0, 0] = jnp.concatenate(
            [m0[:, None], l0[:, None], o0], axis=-1).astype(o_ref.dtype)

    @pl.when(blk > 0)
    def _rest():
        prev = o_ref[0, 0].astype(jnp.float32)
        m_prev, l_prev, o_prev = prev[:, 0], prev[:, 1], prev[:, 2:]
        m_new = jnp.maximum(m_prev, m_blk)
        c_prev = jnp.exp(m_prev - m_new)
        c_blk = jnp.exp(m_blk - m_new)
        l_new = l_prev * c_prev + l_blk * c_blk
        o_new = o_prev * c_prev[:, None] + o_blk * c_blk[:, None]
        o_ref[0, 0] = jnp.concatenate(
            [m_new[:, None], l_new[:, None], o_new], axis=-1
        ).astype(o_ref.dtype)


def cache_decode_attention(entry: dict, q: jax.Array, length: jax.Array,
                           block_s: int = 2048,
                           interpret: bool | None = None,
                           scale: float | None = None) -> jax.Array:
    """Fused attention over one layer's quantized cache (``scale``: the
    score scale, None for ``1/sqrt(hd)``).

    ``entry``: kvcache layer dict (no periods axis) — k_hi (b, hi, g, hd)
    int8, k_lo (b, S−hi, g, hd/2) uint8, *_scale/zp (b, S, g) f32;
    ``q``: (b, 1, h, hd); ``length``: (1,) int32 shared or (b,) per-slot.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    b, _, h, hd = q.shape
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    hi_len = entry["k_hi"].shape[1]
    g = entry["k_hi"].shape[2]
    rep = h // g
    s_lo = entry["k_lo"].shape[1]
    bs = min(block_s, s_lo)
    while s_lo % bs:
        bs //= 2
    bs = max(bs, 1)
    n_blocks = s_lo // bs
    scale = float(1.0 / np.sqrt(hd) if scale is None else scale)
    qg = q.reshape(b, h, hd).reshape(b, g, rep, hd)

    def split(name):
        full = entry[name]
        return full[:, :hi_len], full[:, hi_len:]

    kshi, kslo = split("k_scale")
    kzhi, kzlo = split("k_zp")
    vshi, vslo = split("v_scale")
    vzhi, vzlo = split("v_zp")

    kernel = functools.partial(_kernel, hi_len=hi_len, block_s=bs,
                               scale=scale)
    hi_spec = pl.BlockSpec((1, hi_len, 1, hd), lambda i, j, k: (i, 0, j, 0))
    lo_spec = pl.BlockSpec((1, bs, 1, hd // 2), lambda i, j, k: (i, k, j, 0))
    shi_spec = pl.BlockSpec((1, hi_len, 1), lambda i, j, k: (i, 0, j))
    slo_spec = pl.BlockSpec((1, bs, 1), lambda i, j, k: (i, k, j))

    stats = pl.pallas_call(
        kernel,
        grid=(b, g, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda i, j, k: (i, j, 0, 0)),
            hi_spec, lo_spec, shi_spec, shi_spec, slo_spec, slo_spec,
            hi_spec, lo_spec, shi_spec, shi_spec, slo_spec, slo_spec,
            pl.BlockSpec((1,), lambda i, j, k: (i,)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd + 2),
                               lambda i, j, k: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, g, rep, hd + 2), jnp.float32),
        interpret=interpret,
    )(qg, entry["k_hi"], entry["k_lo"], kshi, kzhi, kslo, kzlo,
      entry["v_hi"], entry["v_lo"], vshi, vzhi, vslo, vzlo, length)

    l = stats[..., 1]
    o = stats[..., 2:]
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, 1, h, hd).astype(q.dtype)
