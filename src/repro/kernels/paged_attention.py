"""Pallas TPU kernel: attention over the int4 pages of the block-paged
mixed-precision KV cache, walking only each span's own pages.

The cache (`serving/paged_kvcache.py`) is two shared page pools: int8 sink
pages for the first ``num_hi`` tokens of every sequence and int4
nibble-packed pages for the rest, each span reaching its pages through a
block table.  The sink region is a fixed few tokens per span and stays on
the XLA gather (`gather_region` over the hi table); this kernel walks the
int4 region, which grows with the context and holds nearly every byte a
step reads.

    grid (spans,), scalar prefetch (lengths, flat lo tables):
      n_pages = ceil((length - num_hi) / bs) — the span's own pages only;
      per step: P pages (P·bs = 512 tokens, P ≥ 8) of K codes, V codes and
      their f16 scale / zero-point slab, copied HBM → VMEM by manual async
      copies into a double buffer (the next step's copies start before
      this step's wait); a page past the span's length is never copied;
      per kv head: nibble codes → (rows, keys) scores → online softmax,
      ``(m, l, acc)`` kept in the span's output block.

Every copy moves one whole page, lane-dense and contiguous in HBM (the lo
pool's layout exists for this), so it is legal for any kv-head count.  The
TPU compiler takes neither uint8 nor float16 arrays, so the pool stores
the nibble bytes as int8 and the f16 params as int16 bits, and the kernel
reads its page buffers through in-kernel bitcasts as 32-bit words: one
word row holds four tokens of a page, a byte each, and the params slab's
word row holds the scale (low half) and zero point (high half) of those
tokens.  Keys are taken a byte at a time; scores put
them on the lanes, so scales and zero points apply as transposed rows:
``q·k = s·(q·codes − z·Σq)`` and ``p·v = (p·s)·codes − (p·s)·z``.  The f16
bits decode with integer ops (`f16_bits_to_f32`, exact for every finite
f16, subnormals included).  Codes are exact in bf16, so the matmuls see
the codes themselves and dequantisation happens in f32 — finer than the
XLA fallback, which rounds each dequantised K/V to bf16.

A byte holds a head's dims ``2i`` (high nibble) and ``2i + 1`` (low
nibble); the kernel never re-interleaves them.  Each head's queries arrive
with their even and odd dims placed where that head's high and low nibbles
sit in a ``[high | low]`` tile of its 128-lane window, and the output
comes back in the same order, interleaved once outside.

The kernel returns the **unnormalised** softmax statistics ``(m, l, acc)``
of each query row over the span's int4 positions ``[num_hi, length)``; the
callers merge them with the sink segment and, for chunk rows, the chunk's
own raw K/V (``parts`` of `layers.chunked_prefill_attention`), the merge
the XLA fallback performs.  A span with no int4 position returns
``m = −1e30, l = 0, acc = 0``, whose merge weight underflows to zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

# tokens one step of the page walk covers: P = TILE_TOKENS // block_size
# pages (at least 8), so a byte's score tile is (rows, TILE_TOKENS / 4):
# 128 keys on the lanes
TILE_TOKENS = 512
_NEG = -1e30


def pages_per_step(block_size: int) -> int:
    return max(8, TILE_TOKENS // block_size)


def unsupported(block_size: int, head_bytes: int, row: int, *,
                tpu: bool) -> str | None:
    """Why the kernel cannot take lo pages of this geometry, or None.

    ``head_bytes``: one kv head's code bytes (``hd/2``); ``row``: a page
    row's (``kv·hd/2``); ``tpu``: also what the TPU compiler refuses.  The
    kernel reads four tokens of a page per 32-bit word, and a kv head's
    bytes within one 128-lane tile.  On a TPU a page is copied alone only
    out of rows of whole 128-lane tiles (narrower pools are stored
    page-minor), and a page's code rows (``bs``) and params rows (``bs/2``)
    must each be whole 8-row tiles or fewer than 8 rows: 4, 8 or a
    multiple of 16 tokens."""
    if block_size % 4:
        return (f"block_size {block_size}: the kernel reads four tokens of "
                f"a page per 32-bit word")
    if head_bytes % 128 and (128 % head_bytes or (row > 128 and row % 128)):
        return (f"kv heads of {head_bytes} code bytes in rows of {row} "
                f"straddle 128-lane tiles")
    if tpu and row % 128:
        return (f"rows of {row} code bytes are not whole 128-lane tiles: "
                f"no page of such a pool is copied alone")
    if tpu and block_size > 8 and block_size % 16:
        return (f"block_size {block_size}: a page's rows are neither whole "
                f"8-row tiles nor fewer than 8")
    return None


def compiles_for(block_size: int, kv_heads: int, head_dim: int) -> bool:
    """Whether the TPU compiler takes the kernel for a pool of this
    geometry (`unsupported`)."""
    hb = head_dim // 2
    return unsupported(block_size, hb, kv_heads * hb, tpu=True) is None


def lane_window(head_bytes: int, row: int) -> int:
    """Width of the lane window a kv head's code bytes are read in: one
    128-lane tile holding the head (several heads when they are narrower),
    or the whole row when it is narrower than a tile."""
    return head_bytes if head_bytes % 128 == 0 else min(row, 128)


def f16_bits_to_f32(h: jax.Array) -> jax.Array:
    """Exact float32 value of the float16 bit pattern in the low 16 bits of
    int32 ``h``, by integer ops only (the TPU compiler refuses f16 loads).
    Normal numbers rebias the exponent (15 → 127) and widen the mantissa;
    subnormals are ``mantissa · 2⁻²⁴``.  Every finite f16 decodes exactly;
    the cache writes no inf or NaN."""
    h = h & 0xFFFF
    exp = (h >> 10) & 0x1F
    normal = jax.lax.bitcast_convert_type(((h & 0x7FFF) << 13) + (112 << 23),
                                          jnp.float32)
    sub = (h & 0x3FF).astype(jnp.float32) * np.float32(2.0 ** -24)
    mag = jnp.where(exp == 0, sub, normal)
    return jnp.where((h >> 15) == 1, -mag, mag)


def _kernel(len_ref, lt_ref, q_ref, k_hbm, v_hbm, sz_hbm,
            m_ref, l_ref, acc_ref, k_buf, v_buf, sz_buf, st_ref, zt_ref,
            sems, *, g: int, bs: int, pages: int, width: int, num_hi: int,
            head_bytes: int, window: int):
    span = pl.program_id(0)
    tokens = pages * bs
    words = tokens // 4                  # 32-bit word rows of a step
    n_tok = jnp.maximum(len_ref[span] - num_hi, 0)
    n_pages = (n_tok + bs - 1) // bs
    n_steps = (n_pages + pages - 1) // pages
    pools = (k_hbm, v_hbm, sz_hbm)
    bufs = (k_buf, v_buf, sz_buf)
    dt = q_ref.dtype
    # stated, not left to the caller's default: the TPU compiler refuses a
    # bf16 matmul at f32 contract precision, which a process-wide
    # `default_matmul_precision("highest")` would otherwise ask for
    prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 \
        else jax.lax.Precision.DEFAULT

    def copies(step, slot, p):
        page = lt_ref[span * width + step * pages + p]
        return [pltpu.make_async_copy(src.at[page], dst.at[slot, p],
                                      sems.at[slot])
                for src, dst in zip(pools, bufs)]

    def for_pages(step, slot, act):
        def body(p, carry):
            for cp in copies(step, slot, p):
                act(cp)
            return carry
        jax.lax.fori_loop(0, jnp.clip(n_pages - step * pages, 0, pages),
                          body, 0)

    def as_words(buf):
        """(P, rows, lanes) 8- or 16-bit page buffer → (words, lanes)
        32-bit word rows, page-major."""
        return buf.reshape(-1, buf.shape[-1]).bitcast(jnp.int32)

    def nibbles(w, byte):
        """Byte ``byte`` of each word → [high nibbles | low nibbles]: the
        even then the odd head dims, as exact small integers."""
        b = (w >> (8 * byte)) & 0xFF
        return jnp.concatenate([b >> 4, b & 0xF], axis=1).astype(dt)

    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n_steps > 0)
    def _first():
        for_pages(0, 0, lambda cp: cp.start())

    def step_body(step, carry):
        slot = step % 2

        @pl.when(step + 1 < n_steps)
        def _prefetch():
            for_pages(step + 1, 1 - slot, lambda cp: cp.start())

        for_pages(step, slot, lambda cp: cp.wait())
        # Word row w of the step holds tokens 4w … 4w+3 (one per byte) of
        # the codes, and in the params the scale (low half) and zero point
        # (high half) of those tokens.  Keys are taken byte by byte: key i
        # of byte b is token 4i + b, and transposed params rows put the
        # keys on the lanes, where the scores are.
        sz = as_words(sz_buf.at[slot])[...]                # (words, M)
        st_ref[...] = f16_bits_to_f32(sz).T                # (M, words)
        zt_ref[...] = f16_bits_to_f32(sz >> 16).T
        key = 4 * jax.lax.broadcasted_iota(jnp.int32, (1, words), 1)
        left = n_tok - step * tokens
        k_words = as_words(k_buf.at[slot])
        v_words = as_words(v_buf.at[slot])

        def head(j, carry):
            lanes = pl.ds(pl.multiple_of(head_bytes * j // window * window,
                                         window), window)
            kw = k_words[:, lanes]                         # (words, window)
            vw = v_words[:, lanes]
            q = q_ref[0, j]                                # (R, 2·window)
            qsum = jnp.sum(q.astype(jnp.float32), axis=-1, keepdims=True)
            blocks = []
            for byte in range(4):
                rk = pl.ds(byte * g + j, 1)                # K params row
                rv = pl.ds(4 * g + byte * g + j, 1)        # V params row
                # q·k = s·(q·codes − z·Σq): scale and zero point apply to
                # the scores, where they are rows
                qc = jax.lax.dot_general(
                    q, nibbles(kw, byte), (((1,), (1,)), ((), ())),
                    precision=prec, preferred_element_type=jnp.float32)
                sc = st_ref[rk, :] * (qc - zt_ref[rk, :] * qsum)
                blocks.append((jnp.where(key + byte < left, sc, _NEG), rv))
            m_prev = m_ref[0, j]
            m_new = m_prev
            for sc, _ in blocks:
                m_new = jnp.maximum(m_new, jnp.max(sc, axis=-1,
                                                   keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_ref[0, j]
            acc = alpha * acc_ref[0, j]
            for byte, (sc, rv) in enumerate(blocks):
                p = jnp.exp(sc - m_new)
                l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
                # p·v = (p·s)·codes − (p·s)·z, with the same rounded weights
                ps = (p * st_ref[rv, :]).astype(dt)
                corr = jnp.sum(ps.astype(jnp.float32) * zt_ref[rv, :],
                               axis=-1, keepdims=True)
                acc = acc + jnp.dot(ps, nibbles(vw, byte), precision=prec,
                                    preferred_element_type=jnp.float32) \
                    - corr
            m_ref[0, j] = m_new
            l_ref[0, j] = l_new
            acc_ref[0, j] = acc
            return carry

        jax.lax.fori_loop(0, g, head, 0)
        return carry

    jax.lax.fori_loop(0, n_steps, step_body, 0)


def paged_prefix_stats(entry: dict, q: jax.Array, lengths: jax.Array,
                       lo_table: jax.Array, num_hi: int, block_size: int,
                       interpret: bool | None = None) -> tuple:
    """Unnormalised attention statistics of each span's query rows over its
    int4 pages, positions ``[num_hi, length)``.

    ``entry``: one layer's pools (no periods axis) — ``k_lo`` / ``v_lo``
    (NL, bs, g·hd/2) int8 nibble pairs, ``lo_scale_zp`` (NL, bs/2, M)
    int16 f16 bits (`serving/paged_kvcache.py` lays them out);
    ``q``: (n, g, R, hd) queries already multiplied by the softmax scale,
    in the dtype the matmuls take (f32 accumulation either way);
    ``lengths``: (n,) int32 positions each span attends (``< length``);
    ``lo_table``: (n, nl) int32 int4 block table (unmapped entries 0).

    Returns ``m, l`` (n, g, R) and ``acc`` (n, g, R, hd), all f32.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    n, g, rows, hd = q.shape
    bs = block_size
    hb, row = hd // 2, entry["k_lo"].shape[-1]
    why = unsupported(bs, hb, row, tpu=not interpret)
    if why:
        raise ValueError(why)
    width = lo_table.shape[1]
    pages = pages_per_step(bs)
    win = lane_window(hb, row)
    meta = entry["lo_scale_zp"].shape[-1]
    # each head's even / odd query dims placed where its code nibbles sit
    # in the kernel's [high | low] nibble tile: the k = window / hb heads
    # that share a window sit side by side, so placing is a block-diagonal
    # spread (exact: each output is one query value or zero)
    k = max(win // hb, 1)
    eye = jnp.eye(k, dtype=q.dtype)[:, None, :, None]          # (k,1,k,1)
    qw = jnp.concatenate(
        [(q[..., e::2].reshape(n, g // k, k, rows, 1, hb) * eye).reshape(
            n, g, rows, k * hb) for e in (0, 1)], axis=-1)
    pools = [entry["k_lo"], entry["v_lo"], entry["lo_scale_zp"]]
    idx = lambda i, ln, lt: (i, 0, 0, 0)                       # noqa: E731
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    kernel = functools.partial(_kernel, g=g, bs=bs, pages=pages, width=width,
                               num_hi=num_hi, head_bytes=hb, window=win)
    from repro.kernels.stamp_matmul import VMEM_LIMIT_BYTES
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[pl.BlockSpec((1, g, rows, 2 * win), idx),
                      hbm_spec, hbm_spec, hbm_spec],
            out_specs=(pl.BlockSpec((1, g, rows, 1), idx),
                       pl.BlockSpec((1, g, rows, 1), idx),
                       pl.BlockSpec((1, g, rows, 2 * win), idx)),
            scratch_shapes=[
                pltpu.VMEM((2, pages) + a.shape[1:], a.dtype) for a in pools
            ] + [
                pltpu.VMEM((meta, pages * bs // 4), jnp.float32),
                pltpu.VMEM((meta, pages * bs // 4), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, g, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, g, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, g, rows, 2 * win), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="paged_prefix_stats",
        interpret=interpret,
    )(lengths.astype(jnp.int32), lo_table.reshape(-1).astype(jnp.int32),
      qw, *pools)
    # each head's own lanes back out of its window (the block diagonal),
    # even and odd dims interleaved again
    acc = acc.reshape(n, g // k, k, rows, 2, k, hb)
    acc = jnp.sum(acc * jnp.eye(k, dtype=acc.dtype)[:, None, None, :, None],
                  axis=5)                                   # (n,g/k,k,R,2,hb)
    out = jnp.swapaxes(acc, -1, -2).reshape(n, g, rows, hd)
    return m[..., 0], l[..., 0], out


def _scaled(q: jax.Array, scale: float | None) -> jax.Array:
    """Queries times the softmax scale (the model's, or ``1/sqrt(hd)``
    where it states none), in f32, back in their own dtype."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _sink(entry: dict, hi_table: jax.Array, block_size: int, dtype) -> list:
    from repro.serving.paged_kvcache import gather_region
    if hi_table.shape[1] == 0:
        return []
    return [(*gather_region(entry, "hi", hi_table, block_size, dtype), 0)]


def paged_decode_attention(entry: dict, q: jax.Array, lengths: jax.Array,
                           hi_table: jax.Array, lo_table: jax.Array,
                           block_size: int, scale: float | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Decode attention over one layer's paged quantized pools: the kernel
    over each slot's int4 pages, merged with its sink pages.  ``scale``
    is the score scale (None: ``1/sqrt(hd)``).

    ``entry``: pool dict (no periods axis) — k_hi (NH, bs, g, hd) int8,
    k_lo (NL, bs, g, hd/2) uint8, *_scale/zp (N?, bs, g) f16;
    ``q``: (S, 1, h, hd); ``lengths``: (S,) int32 per-slot, the slot's own
    just-written token included; ``hi_table``: (S, nh) int32;
    ``lo_table``: (S, nl) int32 — unmapped logical blocks hold 0 (the null
    page) and are never read.
    """
    from repro.models.layers import decode_attention_segments
    s_slots, _, h, hd = q.shape
    g = entry["k_lo"].shape[-1] // (hd // 2)
    qg = _scaled(q, scale).reshape(s_slots, g, h // g, hd)
    stats = paged_prefix_stats(entry, qg, lengths, lo_table,
                               hi_table.shape[1] * block_size, block_size,
                               interpret=interpret)
    return decode_attention_segments(
        q, _sink(entry, hi_table, block_size, q.dtype), length=lengths,
        parts=[stats], scale=scale)


def paged_ragged_attention(entry: dict, q_pf: jax.Array, q_dec: jax.Array,
                           k_pf: jax.Array, v_pf: jax.Array,
                           lengths: jax.Array, hi_table: jax.Array,
                           lo_table: jax.Array, block_size: int,
                           scale: float | None = None,
                           interpret: bool | None = None) -> tuple:
    """Attention of one **unified ragged step**: ``n_pf`` prefill chunk
    rows, then ``S`` decode slots, through span-ordered tables.

    ``q_pf``: (n_pf, C, h, hd) chunk queries, rows padded to C;
    ``k_pf / v_pf``: (n_pf, C, g, hd) the chunks' own raw K/V;
    ``q_dec``: (S, 1, h, hd) one query per decode slot;
    ``lengths``: (n_pf+S,) int32 positions each span reads through its
    pages — a chunk row its cached prefix (``start``; the pages just
    written for the chunk are not read), a decode slot its length with its
    own just-written token;
    ``hi_table`` / ``lo_table``: (n_pf+S, ·) span-ordered block tables;
    ``scale``: the score scale (None: ``1/sqrt(hd)``).

    Decode slots are `paged_decode_attention`.  Chunk rows take the
    kernel's statistics over their cached int4 pages, the sink pages below
    ``start``, and causal attention over the raw chunk, merged by
    `chunked_prefill_attention` — the XLA fallback's result.  Pad query
    rows (beyond a chunk's valid length) are defined and discarded by the
    caller.

    Returns ``(out_pf (n_pf, C, h, hd), out_dec (S, 1, h, hd))``.
    """
    from repro.models.layers import chunked_prefill_attention
    n_pf, c_len, h, hd = q_pf.shape
    out_dec = paged_decode_attention(entry, q_dec, lengths[n_pf:],
                                     hi_table[n_pf:], lo_table[n_pf:],
                                     block_size, scale=scale,
                                     interpret=interpret)
    if n_pf == 0:
        return q_pf, out_dec
    g = entry["k_lo"].shape[-1] // (hd // 2)
    rep = h // g
    start = lengths[:n_pf]
    # rows (rep, C) per kv head: the layout chunked_prefill_attention's
    # statistics take, (n_pf, g, rep, C)
    qg = _scaled(q_pf, scale).reshape(n_pf, c_len, g, rep, hd).transpose(
        0, 2, 3, 1, 4).reshape(n_pf, g, rep * c_len, hd)
    m, l, acc = paged_prefix_stats(entry, qg, start, lo_table[:n_pf],
                                   hi_table.shape[1] * block_size,
                                   block_size, interpret=interpret)
    stats = (m.reshape(n_pf, g, rep, c_len), l.reshape(n_pf, g, rep, c_len),
             acc.reshape(n_pf, g, rep, c_len, hd))
    out_pf = chunked_prefill_attention(
        q_pf, _sink(entry, hi_table[:n_pf], block_size, q_pf.dtype),
        k_pf, v_pf, start, parts=[stats], scale=scale)
    return out_pf, out_dec
