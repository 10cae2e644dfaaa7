"""Pallas TPU kernels: the fused STaMP deployment linears (Fig. 2a, one pass).

The reference path (`repro.core.stamp.stamp_linear` with
``execution="reference"``) materializes four HBM-sized intermediates per
linear: the sequence-transformed activation ``T = L·X``, the fake-quantized
``Tq``, the matmul output ``Tq·W`` and the inverse-transformed ``L⁻¹(Tq·W)``.
The kernels here run the whole chain in one VMEM residency:

    1. ``T = L · X``          — multi-level Haar DWT / WHT as an (s, s)
                                matrix on the in-VMEM tile (sequence axis
                                fully resident), on the MXU;
    2. ``Q(T)``               — per-token asymmetric min-max quantize, first
                                ``num_hi`` rows at ``hi_bits`` and the rest at
                                ``lo_bits`` (the paper's mixed precision,
                                §3.3), codes shifted into signed int8;
    3. ``Q(T) · Wq``          — int8 × int8 MXU GEMM, int32 accumulation,
                                with the same per-row/per-column zero-point
                                correction epilogue as `int8_matmul.py`:
                                ``(Σ qx·qw − zx·Σqw − zw·Σqx + K·zx·zw)·sx·sw``;
    4. ``L⁻¹ · (…) + 1βᵀ``    — inverse transform then bias (exact per Eq. 7).

The activation therefore makes exactly **one** HBM round trip (read ``X``,
write ``Y``) per output-block program instead of four full materializations.
Weights arrive pre-quantized (signed int8 codes + per-output-channel
scale/zero-point) — see `repro.core.stamp.prepare_linear` — so no bf16
re-materialization of ``W`` happens per call either.

Grid: ``(batch, N / block_n)``.  Each program holds the full ``(s, K)``
activation tile plus a ``(K, block_n)`` weight block in VMEM; at s = 4k,
K = 4k f32 that is 64 MiB + 2 MiB — within v5p VMEM budgets for serving
shapes; shrink ``block_n`` (weight block) for larger K.  The transform +
quantize run **once per batch row** (on the first output-block grid step)
into VMEM scratch; subsequent output blocks reuse the int8 codes and
per-token scales, so widening N (e.g. a concatenated QKV weight) adds only
GEMM + epilogue work.  The activation block index is constant across the N
grid axis, so the pipeline fetches X from HBM once per row (Mosaic skips
re-copying revisited blocks).  The transform matrices come from
`repro.core.transforms.sequence_matrix`, checked there against the
butterfly oracle, including the identity-tail handling for
non-power-of-two sequence lengths and the first-token (attention sink)
exception; the inverse is ``Lᵀ`` per output block.

Three call-site variants share that structure:

* `stamp_quant_matmul_pallas` — the single-output kernel.  ``x`` may be
  ``(b, s, K)`` or, for the attention out-proj, the *raw head-split*
  ``(b, s, nh, hd)`` attention output: the head-merge reshape happens on
  the in-VMEM tile right before the transform, so no merged ``(b, s,
  nh·hd)`` activation ever materializes in HBM between attention and the
  projection.
* `stamp_quant_dual_matmul_pallas` — the dual-output (gate/up) kernel.
  Two weight sets with the same output width share ONE transform+quantize
  of the common activation (the scratch codes drive both GEMMs); the
  optional ``silu·mul`` epilogue combines the two inverse-transformed
  results in-VMEM, writing a single output — the down-proj input — so the
  whole SwiGLU front half costs one activation read and one write.
* `decode_matmul.stamp_decode_matmul_pallas` (sibling module) — the
  transform-free single-token variant for decode.

Every variant also takes its weights as the layer scan's stacks: codes
``(L, K, N)`` and scale / zero point ``(L, 1, N)`` with a ``layer`` index.
The index is a scalar-prefetch operand that the weight block index maps
read, so the kernel streams one layer's blocks straight out of the stack
and no per-layer copy of the codes is ever written to HBM.  A rank-2
weight (unstacked callers) takes the plain grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import transforms as T

# transforms the fused kernel can run in-VMEM; dct/klt/dwt2d fall back to
# the reference path (calibrated bases / latent-grid reads don't tile).
FUSABLE_TRANSFORMS = ("none", "dwt", "wht")

# Scoped VMEM the kernels request from the compiler.  The compiler's default
# limit is smaller than the down-proj working set at K = 14336 (a (s, K)
# activation tile, its f32 quantize temporaries and double-buffered (K, bn)
# int8 weight blocks); v5e has 128 MiB of VMEM per core.  The contract
# checker audits kernel footprints against this same number.
VMEM_LIMIT_BYTES = 64 * 2**20
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _seq_matrices(kind: str, s: int, levels: int, skip_first: bool):
    """``(L, Lᵀ)`` as (s, s) f32 kernel operands, or ``()`` for ``none``.
    The transform runs as ``L @ x`` on the MXU (s/N of the GEMM's
    multiply-adds, but each at f32 ``HIGHEST`` precision, several bf16
    passes, against the int8 GEMM's double rate): the strided butterflies
    of `repro.core.transforms` do not lower to Mosaic, and that module
    stays the oracle the matrices are read from."""
    if kind == "none":
        return ()
    if kind not in FUSABLE_TRANSFORMS:
        raise ValueError(f"transform {kind!r} not fusable")
    m = T.sequence_matrix(kind, s, levels, skip_first)
    return jnp.asarray(m), jnp.asarray(m.T)


def _apply(m_ref, x):
    """``M @ x`` for an (s, s) f32 transform matrix and an (s, n) f32 tile;
    a missing matrix (transform ``none``) is the identity."""
    if m_ref is None:
        return x
    return jnp.dot(m_ref[...], x, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _transform_quantize(x_ref, l_ref, qx_ref, sx_ref, zx_ref, *,
                        num_hi: int, hi_bits: int, lo_bits: int):
    """Transform + mixed-precision quantize the in-VMEM activation tile into
    scratch.  Runs on the first output-block grid step of each batch row;
    later blocks (and, in the dual kernel, the second GEMM) reuse the codes.
    A head-split ``(s, nh, hd)`` tile is merged to ``(s, nh·hd)`` here — the
    head-merge reshape is fused with the quantize, entirely in VMEM."""
    x = x_ref[0].astype(jnp.float32)
    x = x.reshape(x.shape[0], -1)                      # (s, K) head merge
    tx = _apply(l_ref, x)
    s = tx.shape[0]
    # mixed-precision per-token min-max quantize (Eq. 1 with b_ij = b_i)
    row = jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0)
    n_lev = jnp.where(row < num_hi, 2.0 ** hi_bits - 1.0,
                      2.0 ** lo_bits - 1.0)
    mn = jnp.min(tx, axis=-1, keepdims=True)
    mx = jnp.max(tx, axis=-1, keepdims=True)
    sx = jnp.maximum((mx - mn) / n_lev, 1e-8)
    zx = jnp.round(-mn / sx)
    q = jnp.clip(jnp.round(tx / sx) + zx, 0.0, n_lev)
    qx_ref[...] = (q - 128.0).astype(jnp.int8)      # unsigned → signed codes
    sx_ref[...] = sx
    zx_ref[...] = zx - 128.0               # shift zp identically (exact)


def _split_mats(refs, has_mats: bool):
    """Peel the optional leading ``(L, Lᵀ)`` refs off a kernel's refs."""
    if has_mats:
        return refs[0], refs[1], refs[2:]
    return None, None, refs


def _int_gemm(qx, sx, zxs, qw, sw, zw, *, k_total: int):
    """int8×int8 GEMM with the zero-point-correction epilogue; reads each
    operand once.  Takes in-VMEM *values* (``(K, bn)`` int8 codes plus
    ``(1, bn)`` scale / shifted zp) so callers can slice away leading
    block axes first.  Returns the dequantized (s, bn) f32 partial
    product."""
    acc = jnp.dot(qx, qw, preferred_element_type=jnp.int32).astype(jnp.float32)
    qw_sum = jnp.sum(qw.astype(jnp.int32), axis=0,
                     keepdims=True).astype(jnp.float32)
    qx_sum = jnp.sum(qx.astype(jnp.int32), axis=1,
                     keepdims=True).astype(jnp.float32)
    sw = sw.astype(jnp.float32)                        # (1, bn)
    zw = zw.astype(jnp.float32)
    corr = acc - zxs * qw_sum - zw * qx_sum + float(k_total) * zxs * zw
    return corr * sx * sw                              # (s, bn) f32


def _stamp_kernel(*refs, has_mats: bool, num_hi: int, hi_bits: int,
                  lo_bits: int, k_total: int):
    l_ref, lt_ref, refs = _split_mats(refs, has_mats)
    (x_ref, qw_ref, sw_ref, zw_ref, b_ref, o_ref,
     qx_ref, sx_ref, zx_ref) = refs

    @pl.when(pl.program_id(1) == 0)
    def _tq():
        _transform_quantize(x_ref, l_ref, qx_ref, sx_ref, zx_ref,
                            num_hi=num_hi, hi_bits=hi_bits, lo_bits=lo_bits)

    y = _int_gemm(qx_ref[...], sx_ref[...], zx_ref[...],
                  qw_ref[...], sw_ref[...], zw_ref[...], k_total=k_total)
    # inverse transform commutes with the right-multiplication by W, so it
    # applies per output block; bias afterwards is exact (Eq. 7).
    y = _apply(lt_ref, y)
    o_ref[0] = (y + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _stamp_dual_kernel(*refs, has_mats: bool, num_hi: int, hi_bits: int,
                       lo_bits: int, k_total: int, epilogue: str):
    """Two GEMMs (gate/up) off ONE scratch-resident quantized activation.

    With ``epilogue="silu_mul"`` the inverse-transformed pair combines to
    ``silu(g)·u`` in-VMEM and a single output block is written; with
    ``epilogue="none"`` both projections are written separately."""
    l_ref, lt_ref, refs = _split_mats(refs, has_mats)
    (x_ref, qwg_ref, swg_ref, zwg_ref, bg_ref,
     qwu_ref, swu_ref, zwu_ref, bu_ref) = refs[:9]
    if epilogue == "silu_mul":
        o_ref, qx_ref, sx_ref, zx_ref = refs[9:]
    else:
        og_ref, ou_ref, qx_ref, sx_ref, zx_ref = refs[9:]

    @pl.when(pl.program_id(1) == 0)
    def _tq():
        _transform_quantize(x_ref, l_ref, qx_ref, sx_ref, zx_ref,
                            num_hi=num_hi, hi_bits=hi_bits, lo_bits=lo_bits)

    qx, sx, zxs = qx_ref[...], sx_ref[...], zx_ref[...]
    yg = _int_gemm(qx, sx, zxs, qwg_ref[...], swg_ref[...], zwg_ref[...],
                   k_total=k_total)
    yu = _int_gemm(qx, sx, zxs, qwu_ref[...], swu_ref[...], zwu_ref[...],
                   k_total=k_total)
    # both outputs return to the original domain before the gating
    # nonlinearity — silu does NOT commute with L⁻¹, the element-wise
    # product must happen on tokens, not wavelet coefficients.
    yg = _apply(lt_ref, yg) + bg_ref[...].astype(jnp.float32)
    yu = _apply(lt_ref, yu) + bu_ref[...].astype(jnp.float32)
    if epilogue == "silu_mul":
        o_ref[0] = (jax.nn.silu(yg) * yu).astype(o_ref.dtype)
    else:
        og_ref[0] = yg.astype(og_ref.dtype)
        ou_ref[0] = yu.astype(ou_ref.dtype)


def _pick_block_n(block_n: int, n: int) -> int:
    # halve until the block divides N — never fall back to a full-width
    # block (a concatenated QKV width like 3200 would otherwise force the
    # whole (K, N) weight + (s, N) f32 output into one VMEM residency)
    bn = min(block_n, n)
    while n % bn:
        bn //= 2
    return bn


def _x_spec(x: jax.Array) -> tuple[pl.BlockSpec, int, int, int]:
    """Activation BlockSpec for a (b, s, K) or raw head-split (b, s, nh, hd)
    input.  The 4-D case maps the full (s, nh, hd) tile per batch row; the
    kernel merges heads in VMEM (`_transform_quantize`), so the out-proj
    consumes the attention output without a merged HBM intermediate."""
    if x.ndim == 4:
        b, s, nh, hd = x.shape
        return pl.BlockSpec((1, s, nh, hd), lambda i, j, *_: (i, 0, 0, 0)), \
            b, s, nh * hd
    b, s, k = x.shape
    return pl.BlockSpec((1, s, k), lambda i, j, *_: (i, 0, 0)), b, s, k


def _m_spec(s: int) -> pl.BlockSpec:
    # constant block index: each transform matrix is fetched once per call
    return pl.BlockSpec((s, s), lambda i, j, *_: (0, 0))


def weight_specs(qw: jax.Array, layer, bn: int
                 ) -> tuple[pl.BlockSpec, pl.BlockSpec]:
    """BlockSpecs of a weight's ``(K, bn)`` code block and ``(1, bn)``
    scale / zero-point block at output block ``j`` (the grid's last
    index).  A ``(L, K, N)`` stack with its ``layer`` index reads layer
    ``l[0]``'s blocks in place (the index is the call's scalar-prefetch
    operand); a ``(K, N)`` weight is read as it is."""
    if (qw.ndim == 3) != (layer is not None):
        raise ValueError(f"a {qw.ndim}-D weight with layer={layer!r}: a "
                         f"stacked weight needs its layer index, and only "
                         f"a stacked weight takes one")
    k = qw.shape[-2]
    if layer is None:
        return (pl.BlockSpec((k, bn), lambda *g: (0, g[-1])),
                pl.BlockSpec((1, bn), lambda *g: (0, g[-1])))
    return (pl.BlockSpec((None, k, bn), lambda *g: (g[-1][0], 0, g[-2])),
            pl.BlockSpec((None, 1, bn), lambda *g: (g[-1][0], 0, g[-2])))


def layer_grid(kernel, layer, **grid_kw) -> tuple:
    """``(kernel, pallas_call keywords, leading operands)`` for a grid of
    ``grid_kw`` (``grid``, ``in_specs``, ``out_specs``, ``scratch_shapes``)
    whose weights `weight_specs` placed.  With a ``layer`` index that index
    is the one scalar-prefetch operand: only the index maps read it, so the
    kernel body takes the same refs either way."""
    if layer is None:
        return kernel, grid_kw, ()
    return ((lambda l_ref, *refs: kernel(*refs)),
            {"grid_spec": pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, **grid_kw)},
            (jnp.reshape(layer, (1,)).astype(jnp.int32),))


def stamp_quant_matmul_pallas(
    x: jax.Array,            # (b, s, K) float — or (b, s, nh, hd) head-split
    qw: jax.Array,           # (K, N) int8 signed codes
    sw: jax.Array,           # (1, N) f32 per-output-channel scale
    zw: jax.Array,           # (1, N) f32 signed-shifted zero point
    bias: jax.Array,         # (1, N) f32 (zeros when the layer has no bias)
    *,
    transform: str = "dwt",
    levels: int = 3,
    skip_first: bool = True,
    num_hi: int = 64,
    hi_bits: int = 8,
    lo_bits: int = 4,
    block_n: int = 256,
    layer: jax.Array | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused STaMP linear: ``L⁻¹(Q(L·x) · Wq_deq) + bias`` in one kernel.
    ``qw``/``sw``/``zw`` may be ``(L, K, N)``/``(L, 1, N)`` stacks read in
    place at ``layer`` (see `weight_specs`)."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    if transform not in FUSABLE_TRANSFORMS:
        raise ValueError(f"transform {transform!r} is not fusable "
                         f"(expected one of {FUSABLE_TRANSFORMS})")
    x_spec, b, s, k = _x_spec(x)
    k2, n = qw.shape[-2:]
    if k != k2:
        raise ValueError(f"activation K={k} does not match weight K={k2}")
    bn = _pick_block_n(block_n, n)
    mats = _seq_matrices(transform, s, levels, skip_first)
    w_spec, c_spec = weight_specs(qw, layer, bn)
    kernel, grid_kw, lead = layer_grid(
        functools.partial(_stamp_kernel, has_mats=bool(mats), num_hi=num_hi,
                          hi_bits=hi_bits, lo_bits=lo_bits, k_total=k),
        layer,
        grid=(b, n // bn),
        in_specs=[_m_spec(s)] * len(mats) + [
            x_spec, w_spec, c_spec, c_spec,
            pl.BlockSpec((1, bn), lambda i, j, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, s, bn), lambda i, j, *_: (i, 0, j)),
        scratch_shapes=[
            pltpu.VMEM((s, k), jnp.int8),      # quantized activation codes
            pltpu.VMEM((s, 1), jnp.float32),   # per-token scale
            pltpu.VMEM((s, 1), jnp.float32),   # per-token (shifted) zp
        ])
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, s, n), out_dtype or x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        **grid_kw,
    )(*lead, *mats, x, qw, sw, zw, bias)


def stamp_quant_dual_matmul_pallas(
    x: jax.Array,            # (b, s, K) float
    qw_g: jax.Array,         # (K, N) int8 gate codes
    sw_g: jax.Array,         # (1, N) f32
    zw_g: jax.Array,         # (1, N) f32
    bias_g: jax.Array,       # (1, N) f32
    qw_u: jax.Array,         # (K, N) int8 up codes
    sw_u: jax.Array,
    zw_u: jax.Array,
    bias_u: jax.Array,
    *,
    transform: str = "dwt",
    levels: int = 3,
    skip_first: bool = True,
    num_hi: int = 64,
    hi_bits: int = 8,
    lo_bits: int = 4,
    block_n: int = 256,
    epilogue: str = "silu_mul",   # "silu_mul" | "none"
    layer: jax.Array | None = None,
    out_dtype=None,
    interpret: bool | None = None,
):
    """Fused STaMP gate/up pair: ONE transform+quantize of the shared input
    drives both integer GEMMs.  ``epilogue="silu_mul"`` returns
    ``silu(L⁻¹(Q·Wg)+bg) · (L⁻¹(Q·Wu)+bu)`` as a single array;
    ``epilogue="none"`` returns the ``(gate, up)`` tuple.  Both weight
    sets may be stacks read in place at ``layer`` (see `weight_specs`)."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    if transform not in FUSABLE_TRANSFORMS:
        raise ValueError(f"transform {transform!r} is not fusable "
                         f"(expected one of {FUSABLE_TRANSFORMS})")
    if epilogue not in ("silu_mul", "none"):
        raise ValueError(f"unknown epilogue {epilogue!r}")
    x_spec, b, s, k = _x_spec(x)
    k2, n = qw_g.shape[-2:]
    if k != k2:
        raise ValueError(f"activation K={k} does not match weight K={k2}")
    if qw_u.shape != qw_g.shape:
        raise ValueError(f"gate/up weight shapes differ: "
                         f"{qw_g.shape} vs {qw_u.shape}")
    bn = _pick_block_n(block_n, n)
    mats = _seq_matrices(transform, s, levels, skip_first)
    w_spec, c_spec = weight_specs(qw_g, layer, bn)
    b_spec = pl.BlockSpec((1, bn), lambda i, j, *_: (0, j))
    o_spec = pl.BlockSpec((1, s, bn), lambda i, j, *_: (i, 0, j))
    o_shape = jax.ShapeDtypeStruct((b, s, n), out_dtype or x.dtype)
    single = epilogue == "silu_mul"
    kernel, grid_kw, lead = layer_grid(
        functools.partial(_stamp_dual_kernel, has_mats=bool(mats),
                          num_hi=num_hi, hi_bits=hi_bits, lo_bits=lo_bits,
                          k_total=k, epilogue=epilogue),
        layer,
        grid=(b, n // bn),
        in_specs=[_m_spec(s)] * len(mats) + [
            x_spec,
            w_spec, c_spec, c_spec, b_spec,
            w_spec, c_spec, c_spec, b_spec],
        out_specs=o_spec if single else (o_spec, o_spec),
        scratch_shapes=[
            pltpu.VMEM((s, k), jnp.int8),      # shared quantized codes
            pltpu.VMEM((s, 1), jnp.float32),   # per-token scale
            pltpu.VMEM((s, 1), jnp.float32),   # per-token (shifted) zp
        ])
    return pl.pallas_call(
        kernel,
        out_shape=o_shape if single else (o_shape, o_shape),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        **grid_kw,
    )(*lead, *mats, x, qw_g, sw_g, zw_g, bias_g, qw_u, sw_u, zw_u, bias_u)


def stamp_quant_segment_matmul_pallas(
    x: jax.Array,            # (b, n_seg·seg_len, K) flattened uniform spans
    qw: jax.Array,
    sw: jax.Array,
    zw: jax.Array,
    bias: jax.Array,
    *,
    seg_len: int,
    **kwargs,
) -> jax.Array:
    """Segment-aware fused STaMP linear for the unified ragged serving step.

    ``x`` is a flattened batch of uniform ``seg_len``-token sequence spans
    (several requests' prefill chunks concatenated along axis 1).  The
    sequence transform must run **per span, never across the flattened
    batch** — so spans fold into the kernel's batch grid axis (each grid
    row's transform+quantize scratch is private), and the output unfolds
    back to the flattened layout.  Identical math to calling
    `stamp_quant_matmul_pallas` once per span."""
    b, t = x.shape[0], x.shape[1]
    if t % seg_len:
        raise ValueError(f"flattened length {t} is not a whole number of "
                         f"{seg_len}-token segments")
    xf = x.reshape(b * (t // seg_len), seg_len, *x.shape[2:])
    y = stamp_quant_matmul_pallas(xf, qw, sw, zw, bias, **kwargs)
    return y.reshape(b, t, y.shape[-1])


# ---------------------------------------------------------------------------
# Grouped MoE expert GEMMs over the quantized dispatch buffer
# ---------------------------------------------------------------------------


def _rowwise_quantize(a):
    """Per-row 8-bit asymmetric min-max quantize of an in-VMEM f32 tile —
    the same quantizer `_transform_quantize` applies per token, without the
    transform (the grouped down-proj input lives in the token domain).
    Returns signed int8 codes plus (rows, 1) f32 scale / shifted zp."""
    mn = jnp.min(a, axis=-1, keepdims=True)
    mx = jnp.max(a, axis=-1, keepdims=True)
    sa = jnp.maximum((mx - mn) / 255.0, 1e-8)
    za = jnp.round(-mn / sa)
    qa = (jnp.clip(jnp.round(a / sa) + za, 0.0, 255.0) - 128.0) \
        .astype(jnp.int8)
    return qa, sa, za - 128.0


def _ragged_moe_kernel(tab_ref, qx_ref, sx_ref, zx_ref,
                       qwg_ref, swg_ref, zwg_ref,
                       qwu_ref, swu_ref, zwu_ref,
                       qwd_ref, swd_ref, zwd_ref,
                       o_ref, acc_ref, *,
                       n_tiles: int, block_m: int, block_f: int, nf: int,
                       d: int):
    """One (row tile, f tile) grid step of the grouped MoE FFN: dual
    gate/up int8 GEMMs off the tile's quantized rows (all of one expert),
    silu·mul epilogue in VMEM, per-row requantize of the activation slab,
    and the partial down-proj accumulated over the f axis into scratch.
    ``tab_ref`` is the scalar-prefetched tile table (see
    `stamp_quant_ragged_matmul_pallas`): a tile with no rows does no work
    and writes nothing; rows past a tile's count are written as zeros."""
    t, j = pl.program_id(0), pl.program_id(1)
    rows = tab_ref[2 * n_tiles + t]

    @pl.when(rows > 0)
    def _tile():
        @pl.when(j == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        qx, sx, zxs = qx_ref[...], sx_ref[...], zx_ref[...]   # (bm, ·)
        g = _int_gemm(qx, sx, zxs, qwg_ref[0], swg_ref[0], zwg_ref[0],
                      k_total=d)
        u = _int_gemm(qx, sx, zxs, qwu_ref[0], swu_ref[0], zwu_ref[0],
                      k_total=d)
        a = jax.nn.silu(g) * u                             # (bm, bf) f32
        # the down-proj consumes the activation slab as int8 too: per-row
        # quantize within this f block (group-wise scales — each f tile
        # gets its own row scale, so the partial products dequantize
        # exactly; one block when bf = f)
        qa, sa, zas = _rowwise_quantize(a)
        acc_ref[...] += _int_gemm(qa, sa, zas, qwd_ref[0], swd_ref[0],
                                  zwd_ref[0], k_total=block_f)

        @pl.when(j == nf - 1)
        def _write():
            row = jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
            o_ref[...] = jnp.where(row < rows, acc_ref[...],
                                   0.0).astype(o_ref.dtype)


def stamp_quant_ragged_matmul_pallas(
    table: jax.Array,        # (3 * n_tiles,) int32 tile table
    qx: jax.Array,           # (n_tiles * bm, d) int8 row codes
    sx: jax.Array,           # (n_tiles * bm, 1) f32 per-row scale
    zx: jax.Array,           # (n_tiles * bm, 1) f32 per-row shifted zp
    qw_gate: jax.Array,      # (E, d, f) int8 stacked expert gate codes
    sw_gate: jax.Array,      # (E, 1, f) f32
    zw_gate: jax.Array,      # (E, 1, f) f32
    qw_up: jax.Array,        # (E, d, f) int8
    sw_up: jax.Array,
    zw_up: jax.Array,
    qw_down: jax.Array,      # (E, f, d) int8
    sw_down: jax.Array,      # (E, 1, d) f32
    zw_down: jax.Array,
    *,
    block_m: int = 32,
    block_f: int = 512,
    layer: jax.Array | None = None,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:
    """Grouped STaMP MoE FFN over ragged per-expert row groups: the full
    expert stack (gate/up, silu·mul, down) in ONE kernel.

    Rows arrive grouped by expert, each expert's group starting at a
    ``block_m``-aligned row offset, so every row tile belongs to one
    expert.  The walk is ``(n_tiles, f / block_f)``; the scalar-prefetch
    ``table`` holds three int32 vectors of length ``n_tiles``:

    * ``fetch[t]`` — the row tile whose codes (and output block) tile
      ``t`` maps to: ``t`` itself for a tile with rows, the last tile
      with rows before it for an empty one, so an empty tile copies
      nothing and writes nothing (its index maps repeat the previous
      step's blocks);
    * ``expert[t]`` — the stacked-weight index of ``fetch[t]``'s group;
    * ``rows[t]`` — the valid rows of tile ``t`` (0: empty).

    Consecutive tiles of one expert keep the weight block index when
    ``block_f`` covers ``f``, so an expert's weights stream from HBM once
    per call however many tiles its rows fill.  Returns the ``(n_tiles *
    block_m, d)`` expert outputs; rows past a tile's count are zero, and
    the rows of empty tiles are undefined (no caller reads them).

    The weights may also be the layer scan's ``(L, E, ·, ·)`` stacks with
    a ``layer`` index, which rides the table as one more entry: the
    weight index maps read that layer's experts in place.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    r, d = qx.shape
    f = qw_gate.shape[-1]
    bm = block_m
    if r % bm:
        raise ValueError(f"{r} rows are not whole tiles of {bm}")
    n_tiles = r // bm
    if table.shape != (3 * n_tiles,):
        raise ValueError(f"tile table {table.shape} does not match "
                         f"{n_tiles} tiles")
    if (qw_gate.ndim == 4) != (layer is not None):
        raise ValueError(f"{qw_gate.ndim}-D expert weights with "
                         f"layer={layer!r}: a stack of layers needs its "
                         f"layer index, and only a stack takes one")
    bf = _pick_block_n(block_f, f)
    nf = f // bf
    n = n_tiles

    def w(block, at):
        # tile t's expert tab[n + t]; in a stack, of layer tab[3n]
        if layer is None:
            return pl.BlockSpec((1, *block), lambda t, j, tab: (
                tab[n + t], *at(j)))
        return pl.BlockSpec((None, 1, *block), lambda t, j, tab: (
            tab[3 * n], tab[n + t], *at(j)))

    x_spec = pl.BlockSpec((bm, d), lambda t, j, tab: (tab[t], 0))
    s_spec = pl.BlockSpec((bm, 1), lambda t, j, tab: (tab[t], 0))
    win_spec = w((d, bf), lambda j: (0, j))
    cin_spec = w((1, bf), lambda j: (0, j))
    wdn_spec = w((bf, d), lambda j: (j, 0))
    cdn_spec = w((1, d), lambda j: (0, 0))
    if layer is not None:
        table = jnp.concatenate([table.astype(jnp.int32),
                                 jnp.reshape(layer, (1,)).astype(jnp.int32)])
    kernel = functools.partial(
        _ragged_moe_kernel, n_tiles=n_tiles, block_m=bm, block_f=bf, nf=nf,
        d=d)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles, nf),
            in_specs=[
                x_spec, s_spec, s_spec,
                win_spec, cin_spec, cin_spec,
                win_spec, cin_spec, cin_spec,
                wdn_spec, cdn_spec, cdn_spec,
            ],
            out_specs=pl.BlockSpec((bm, d), lambda t, j, tab: (tab[t], 0)),
            scratch_shapes=[
                pltpu.VMEM((bm, d), jnp.float32),   # down-proj accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((r, d), out_dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="moe_grouped_matmul",
    )(table.astype(jnp.int32), qx, sx, zx,
      qw_gate, sw_gate, zw_gate,
      qw_up, sw_up, zw_up,
      qw_down, sw_down, zw_down)


def tile_table(rows: jax.Array, expert: jax.Array) -> jax.Array:
    """The ragged kernel's tile table from each tile's valid rows and its
    group's expert index: an empty tile fetches (and names the expert of)
    the last tile with rows before it, tile 0 where there is none."""
    n = rows.shape[0]
    t = jnp.arange(n, dtype=jnp.int32)
    fetch = jax.lax.cummax(jnp.where(rows > 0, t, 0), axis=0)
    return jnp.concatenate([fetch, expert[fetch], rows]).astype(jnp.int32)
