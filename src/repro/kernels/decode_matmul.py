"""Pallas TPU kernel: single-token STaMP decode matmul.

Decode feeds one token per slot through each linear, so there is no sequence
axis to transform — STaMP degenerates to per-token activation quantization
against the **already-prepared** int8 weight buffers
(`repro.core.stamp.prepare_linear`).  Before this kernel the decode path
re-dequantized those buffers to bf16 every step (the ROADMAP open item):
per linear per step that re-materializes the full (K, N) weight in HBM.
Here the int8 codes stream in directly:

    1. ``Q(x)``      — per-row (per-slot) asymmetric min-max quantize at
                       8 bits, codes shifted into signed int8 (one decode
                       token always sits in the hi-precision budget);
    2. ``Q(x) · Wq`` — int8 × int8 MXU GEMM, int32 accumulation, with the
                       per-row/per-column zero-point-correction epilogue
                       shared with `stamp_matmul.py`;
    3. ``+ 1βᵀ``     — bias inside the same VMEM residency.

Grid: ``(N / block_n,)``.  The (B, K) token batch is VMEM-resident across
all output blocks; quantization runs once (first grid step) into scratch.
HBM per step: B·K activation + K·N **int8** weight + B·N output — vs the
dequant path's extra K·N bf16 write + read every call.

Place in the unified ragged step: the single compiled step program
contains both regions, and `_linear`'s token-dim shape guard routes only
the decode sub-tensors ``(S, 1, d)`` here — chunk rows (C > 1) never
match, so the sequence transform can't be skipped on prefill work.  The
all-decode steady-state step (n_pf = 0) delegates to the plain decode
graph, where this kernel serves every prepared-weight linear exactly as
it did for the two-call engine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.stamp_matmul import COMPILER_PARAMS


def _kernel(x_ref, qw_ref, sw_ref, zw_ref, b_ref, o_ref,
            qx_ref, sx_ref, zx_ref, *, k_total: int):
    @pl.when(pl.program_id(0) == 0)
    def _quantize():
        x = x_ref[...].astype(jnp.float32)                 # (B, K)
        mn = jnp.min(x, axis=-1, keepdims=True)
        mx = jnp.max(x, axis=-1, keepdims=True)
        sx = jnp.maximum((mx - mn) / 255.0, 1e-8)
        zx = jnp.round(-mn / sx)
        q = jnp.clip(jnp.round(x / sx) + zx, 0.0, 255.0)
        qx_ref[...] = (q - 128.0).astype(jnp.int8)
        sx_ref[...] = sx
        zx_ref[...] = zx - 128.0

    qx = qx_ref[...]                                       # (B, K) int8
    qw = qw_ref[...]                                       # (K, bn) int8
    acc = jnp.dot(qx, qw, preferred_element_type=jnp.int32).astype(jnp.float32)
    qw_sum = jnp.sum(qw.astype(jnp.int32), axis=0,
                     keepdims=True).astype(jnp.float32)
    qx_sum = jnp.sum(qx.astype(jnp.int32), axis=1,
                     keepdims=True).astype(jnp.float32)
    sw = sw_ref[...].astype(jnp.float32)                   # (1, bn)
    zw = zw_ref[...].astype(jnp.float32)
    zxs = zx_ref[...]
    corr = acc - zxs * qw_sum - zw * qx_sum + float(k_total) * zxs * zw
    y = corr * sx_ref[...] * sw
    o_ref[...] = (y + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def stamp_decode_matmul_pallas(
    x: jax.Array,            # (B, K) float — one token per decode slot
    qw: jax.Array,           # (K, N) int8 signed codes
    sw: jax.Array,           # (1, N) f32 per-output-channel scale
    zw: jax.Array,           # (1, N) f32 signed-shifted zero point
    bias: jax.Array,         # (1, N) f32
    *,
    block_n: int = 512,
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused decode linear: ``Q8(x) · Wq_deq + bias`` in one kernel."""
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    b, k = x.shape
    k2, n = qw.shape
    if k != k2:
        raise ValueError(f"activation K={k} does not match weight K={k2}")
    bn = min(block_n, n)
    while n % bn:
        bn //= 2
    kernel = functools.partial(_kernel, k_total=k)
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((b, k), lambda j: (0, 0)),
            pl.BlockSpec((k, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((b, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), out_dtype or x.dtype),
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.int8),      # quantized token codes
            pltpu.VMEM((b, 1), jnp.float32),   # per-token scale
            pltpu.VMEM((b, 1), jnp.float32),   # per-token (shifted) zp
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(x, qw, sw, zw, bias)
