"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
for the interpret-mode shape/dtype sweeps in tests/test_kernels.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import transforms as T


def haar_dwt_ref(x: jax.Array, levels: int = 3,
                 inverse: bool = False) -> jax.Array:
    fn = T.haar_idwt if inverse else T.haar_dwt
    return fn(x, levels=levels, axis=-2)


def wht_ref(x: jax.Array, axis: int = -2) -> jax.Array:
    return T.wht(x, axis=axis)


def quant_pack_ref(x: jax.Array, bits: int = 4):
    xf = x.astype(jnp.float32)
    n = float(2**bits - 1)
    mn = jnp.min(xf, axis=-1, keepdims=True)
    mx = jnp.max(xf, axis=-1, keepdims=True)
    scale = jnp.maximum((mx - mn) / n, 1e-8)
    zp = jnp.round(-mn / scale)
    q = jnp.clip(jnp.round(xf / scale) + zp, 0.0, n)
    if bits == 4:
        qi = q.astype(jnp.uint8)
        packed = (qi[..., 0::2] << 4) | qi[..., 1::2]
    else:
        packed = (q - 128.0).astype(jnp.int8)
        zp = zp - 128.0
    return packed, scale, zp


def unpack_dequant_ref(packed: jax.Array, scale: jax.Array, zp: jax.Array,
                       bits: int = 4, dtype=jnp.float32) -> jax.Array:
    if bits == 4:
        hi = (packed >> 4).astype(jnp.float32)
        lo = (packed & 0xF).astype(jnp.float32)
        q = jnp.stack([hi, lo], axis=-1).reshape(
            *packed.shape[:-1], packed.shape[-1] * 2)
    else:
        q = packed.astype(jnp.float32)
    return ((q - zp) * scale).astype(dtype)


def int8_matmul_ref(qx, qw, sx, zx, sw, zw, out_dtype=jnp.float32):
    x = (qx.astype(jnp.float32) - zx) * sx
    w = (qw.astype(jnp.float32) - zw) * sw
    return (x @ w).astype(out_dtype)


def stamp_decode_matmul_ref(x, qw, sw, zw, bias=None,
                            out_dtype=jnp.float32):
    """Unfused oracle for `stamp_decode_matmul`: per-row 8-bit fake quant of
    the token batch, then a dequantized-weight matmul."""
    xf = x.astype(jnp.float32)
    mn = jnp.min(xf, axis=-1, keepdims=True)
    mx = jnp.max(xf, axis=-1, keepdims=True)
    sc = jnp.maximum((mx - mn) / 255.0, 1e-8)
    zp = jnp.round(-mn / sc)
    q = jnp.clip(jnp.round(xf / sc) + zp, 0.0, 255.0)
    xq = (q - zp) * sc
    wd = (qw.astype(jnp.float32) - zw) * sw
    y = xq @ wd
    if bias is not None:
        y = y + bias.reshape(1, -1).astype(jnp.float32)
    return y.astype(out_dtype)


def paged_attention_ref(entry, q, lengths, hi_table, lo_table, block_size,
                        num_hi):
    """Gather-based oracle for `paged_decode_attention`, sharing no code
    with the cache's own readers: densify each slot's mapped pages token by
    token from the documented layout (`serving/paged_kvcache.py`), then a
    direct masked softmax over positions ``< length``."""
    hi_table = np.asarray(hi_table)
    lo_table = np.asarray(lo_table)
    s_slots, _, h, hd = q.shape
    kv_heads = entry["k_hi"].shape[2]
    bits = np.asarray(entry["lo_scale_zp"]).view(np.float16).astype(
        np.float32)                                  # (NL, rows, lanes)

    def hi_tokens(name):
        pages = hi_table.reshape(-1)
        codes = np.asarray(entry[f"{name}_hi"])[pages].astype(np.float32)
        sc = np.asarray(entry[f"{name}_hi_scale"])[pages].astype(np.float32)
        zp = np.asarray(entry[f"{name}_hi_zp"])[pages].astype(np.float32)
        vals = (codes - zp[..., None]) * sc[..., None]
        return vals.reshape(s_slots, -1, kv_heads, hd)

    def lo_tokens(name, kv):
        pages = np.repeat(lo_table.reshape(-1), block_size)
        t = np.tile(np.arange(block_size), lo_table.size)
        codes = np.asarray(entry[f"{name}_lo"])[pages, t].view(np.uint8)
        codes = codes.reshape(-1, kv_heads, hd // 2).astype(np.float32)
        # byte i of a head holds dims 2i (high nibble) and 2i + 1 (low)
        vals = np.stack([codes // 16, codes % 16], axis=-1).reshape(
            -1, kv_heads, hd)
        # token 4r + b: scale in row 2r, zero point in row 2r + 1, at lane
        # kv·4·kv_heads + b·kv_heads + head
        lanes = kv * 4 * kv_heads + (t % 4)[:, None] * kv_heads \
            + np.arange(kv_heads)[None, :]
        sc = bits[pages[:, None], 2 * (t // 4)[:, None], lanes]
        zp = bits[pages[:, None], 2 * (t // 4)[:, None] + 1, lanes]
        vals = (vals - zp[..., None]) * sc[..., None]
        return vals.reshape(s_slots, -1, kv_heads, hd)

    k = np.concatenate([hi_tokens("k"), lo_tokens("k", 0)], axis=1)
    v = np.concatenate([hi_tokens("v"), lo_tokens("v", 1)], axis=1)
    # the lo region starts at position num_hi, whatever the hi table spans
    pos = np.concatenate([np.arange(hi_table.shape[1] * block_size),
                          num_hi + np.arange(lo_table.shape[1] * block_size)])
    rep = h // kv_heads
    qf = np.asarray(q, np.float32).reshape(s_slots, kv_heads, rep, hd)
    sc = np.einsum("sgrd,stgd->sgrt", qf, k) / np.sqrt(hd)
    mask = pos[None, :] < np.asarray(lengths)[:, None]          # (S, T)
    sc = np.where(mask[:, None, None], sc, -np.inf)
    p = np.exp(sc - sc.max(axis=-1, keepdims=True))
    out = np.einsum("sgrt,stgd->sgrd", p / p.sum(axis=-1, keepdims=True), v)
    return jnp.asarray(out.reshape(s_slots, 1, h, hd))


def stamp_quant_matmul_ref(x, qw, sw, zw, bias=None, *, transform="dwt",
                           levels=3, skip_first=True, num_hi=64, hi_bits=8,
                           lo_bits=4, out_dtype=jnp.float32):
    """Unfused oracle for `stamp_quant_matmul`: transform → mixed-precision
    fake quant → dequantized matmul → inverse transform → bias, each step a
    separate jnp materialization (exactly the reference execution path).
    A head-split (b, s, nh, hd) input is merged up front (the kernel fuses
    that reshape with the quantize)."""
    from repro.core import quant as Q

    if x.ndim == 4:
        x = x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])
    xf = x.astype(jnp.float32)
    tx = T.sequence_transform(xf, transform, axis=-2, levels=levels,
                              skip_first=skip_first)
    bits = Q.mixed_precision_bits(tx.shape[-2], num_hi, hi_bits, lo_bits)
    tq = Q.fake_quant(tx, bits, axis=-1)
    wd = (qw.astype(jnp.float32) - zw) * sw
    y = tq @ wd
    y = T.inverse_sequence_transform(y, transform, axis=-2, levels=levels,
                                     skip_first=skip_first)
    if bias is not None:
        y = y + bias.reshape(1, -1).astype(jnp.float32)
    return y.astype(out_dtype)


def stamp_quant_dual_matmul_ref(x, qw_g, sw_g, zw_g, qw_u, sw_u, zw_u,
                                bias_g=None, bias_u=None, *, transform="dwt",
                                levels=3, skip_first=True, num_hi=64,
                                hi_bits=8, lo_bits=4, epilogue="silu_mul",
                                out_dtype=jnp.float32):
    """Unfused oracle for `stamp_quant_dual_matmul`: ONE shared transform +
    fake quant, two dequantized matmuls, per-output inverse transforms, then
    the optional silu·mul combine in the original (token) domain."""
    from repro.core import quant as Q

    if x.ndim == 4:
        x = x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])
    xf = x.astype(jnp.float32)
    tx = T.sequence_transform(xf, transform, axis=-2, levels=levels,
                              skip_first=skip_first)
    bits = Q.mixed_precision_bits(tx.shape[-2], num_hi, hi_bits, lo_bits)
    tq = Q.fake_quant(tx, bits, axis=-1)

    def one(qw, sw, zw, bias):
        y = tq @ ((qw.astype(jnp.float32) - zw) * sw)
        y = T.inverse_sequence_transform(y, transform, axis=-2,
                                         levels=levels,
                                         skip_first=skip_first)
        if bias is not None:
            y = y + bias.reshape(1, -1).astype(jnp.float32)
        return y

    g = one(qw_g, sw_g, zw_g, bias_g)
    u = one(qw_u, sw_u, zw_u, bias_u)
    if epilogue == "silu_mul":
        return (jax.nn.silu(g) * u).astype(out_dtype)
    return g.astype(out_dtype), u.astype(out_dtype)


def stamp_quant_ragged_matmul_ref(table, qx, sx, zx,
                                  qw_gate, sw_gate, zw_gate,
                                  qw_up, sw_up, zw_up,
                                  qw_down, sw_down, zw_down, *,
                                  block_m=32, block_f=512,
                                  out_dtype=jnp.float32):
    """Unfused oracle for `stamp_quant_ragged_matmul`: dequantize the
    grouped row codes and, for each row, the weights of its tile's expert
    (``table``), run the gate/up einsums + silu·mul, then the down-proj
    per ``block_f`` slab with the same per-row 8-bit requantize the kernel
    applies in VMEM (group-wise scales — one row scale per f tile).  Rows
    past their tile's count, and every row of an empty tile, are zero."""
    r = qx.shape[0]
    n = r // block_m
    f = qw_gate.shape[-1]
    tile = jnp.arange(r) // block_m
    expert = table[n:2 * n][tile]
    live = (jnp.arange(r) % block_m) < table[2 * n:][tile]

    def deq(q, sc, zp):                                      # per row
        return ((q.astype(jnp.float32) - zp) * sc)[expert]

    x = (qx.astype(jnp.float32) - zx) * sx                   # (R, d)
    wg = deq(qw_gate, sw_gate, zw_gate)                      # (R, d, f)
    wu = deq(qw_up, sw_up, zw_up)
    wd = deq(qw_down, sw_down, zw_down)                      # (R, f, d)
    a = (jax.nn.silu(jnp.einsum("rd,rdf->rf", x, wg))
         * jnp.einsum("rd,rdf->rf", x, wu))
    bf = min(block_f, f)
    while f % bf:
        bf //= 2
    out = jnp.zeros((r, qx.shape[1]), jnp.float32)
    for j in range(f // bf):
        blk = a[:, j * bf:(j + 1) * bf]
        mn = jnp.min(blk, axis=-1, keepdims=True)
        mx = jnp.max(blk, axis=-1, keepdims=True)
        sa = jnp.maximum((mx - mn) / 255.0, 1e-8)
        za = jnp.round(-mn / sa)
        qa = jnp.clip(jnp.round(blk / sa) + za, 0.0, 255.0) - za
        out = out + jnp.einsum("rf,rfd->rd", qa * sa,
                               wd[:, j * bf:(j + 1) * bf])
    return jnp.where(live[:, None], out, 0.0).astype(out_dtype)
