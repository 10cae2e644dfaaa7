"""jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels compile to Mosaic.  On any other backend
(tests and CPU runs select it with ``JAX_PLATFORMS=cpu``) they execute via
``interpret=True``: the kernel body runs in Python, which validates
BlockSpec indexing and kernel math against the `ref.py` oracles.
`default_interpret` is the one switch between the two; every wrapper also
takes an explicit ``interpret=`` so tests can pin the mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_matmul import stamp_decode_matmul_pallas
from repro.kernels.haar_dwt import haar_dwt_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.quant_pack import quant_pack_pallas
from repro.kernels.stamp_matmul import (stamp_quant_dual_matmul_pallas,
                                        stamp_quant_matmul_pallas,
                                        stamp_quant_ragged_matmul_pallas)
from repro.kernels.wht import wht_pallas


def default_interpret() -> bool:
    """Shared ``interpret=`` default for every Pallas kernel in this package:
    interpret-mode everywhere except on a real TPU backend.  Kernel entry
    points accept ``interpret=None`` and resolve it through this one switch,
    so tests can still pin the mode explicitly."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("levels", "inverse", "block_d",
                                             "interpret"))
def haar_dwt_seq(x, levels: int = 3, inverse: bool = False,
                 block_d: int = 128, interpret: bool | None = None):
    """Multi-level sequence-axis Haar DWT, fused over levels.  x: (b, s, d)."""
    if interpret is None:
        interpret = default_interpret()
    d = x.shape[2]
    block_d = min(block_d, d)
    while d % block_d:
        block_d //= 2
    # keep the per-program VMEM tile (s × block_d × 4B) under ~8 MiB
    while x.shape[1] * block_d * 4 > 8 * 2**20 and block_d > 8:
        block_d //= 2
    while d % block_d:
        block_d //= 2
    return haar_dwt_pallas(x, levels=levels, inverse=inverse,
                           block_d=max(block_d, 1), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("axis", "interpret"))
def walsh_hadamard(x, axis: int = -2, interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return wht_pallas(x, axis=axis, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_pack(x, bits: int = 4, interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return quant_pack_pallas(x, bits=bits, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def int8_matmul(qx, qw, sx, zx, sw, zw, out_dtype=jnp.bfloat16,
                interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return int8_matmul_pallas(qx, qw, sx, zx, sw, zw, out_dtype=out_dtype,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "transform", "levels", "skip_first", "num_hi", "hi_bits", "lo_bits",
    "out_dtype", "interpret"))
def stamp_quant_matmul(x, qw, sw, zw, bias=None, *, layer=None,
                       transform: str = "dwt", levels: int = 3,
                       skip_first: bool = True, num_hi: int = 64,
                       hi_bits: int = 8, lo_bits: int = 4, out_dtype=None,
                       interpret: bool | None = None):
    """Fused STaMP deployment linear (see `stamp_matmul.py`).

    x: (b, s, K) float — or the raw head-split (b, s, nh, hd) attention
    output (out-proj site: the head-merge reshape fuses with the in-VMEM
    quantize); qw: (K, N) signed int8 codes; sw/zw: (1, N) f32.
    ``bias=None`` lowers a zero bias block (the add is free inside the
    epilogue's VMEM residency).  With a ``layer`` index, qw/sw/zw are the
    layer scan's (L, K, N)/(L, 1, N) stacks, read in place at that layer.
    """
    if interpret is None:
        interpret = default_interpret()
    if bias is None:
        bias = jnp.zeros((1, qw.shape[-1]), jnp.float32)
    return stamp_quant_matmul_pallas(
        x, qw, sw, zw, bias.reshape(1, -1).astype(jnp.float32),
        transform=transform, levels=levels, skip_first=skip_first,
        num_hi=num_hi, hi_bits=hi_bits, lo_bits=lo_bits, layer=layer,
        out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "transform", "levels", "skip_first", "num_hi", "hi_bits", "lo_bits",
    "epilogue", "out_dtype", "interpret"))
def stamp_quant_dual_matmul(x, qw_g, sw_g, zw_g, qw_u, sw_u, zw_u,
                            bias_g=None, bias_u=None, *, layer=None,
                            transform: str = "dwt", levels: int = 3,
                            skip_first: bool = True, num_hi: int = 64,
                            hi_bits: int = 8, lo_bits: int = 4,
                            epilogue: str = "silu_mul", out_dtype=None,
                            interpret: bool | None = None):
    """Fused STaMP gate/up pair (see `stamp_matmul.py`): the shared input's
    sequence transform + mixed-precision quantize run ONCE into VMEM scratch
    and feed both integer GEMMs.  ``epilogue="silu_mul"`` (the SwiGLU front
    half) returns one array; ``"none"`` returns the (gate, up) tuple.
    ``layer``: both weight sets are stacks read in place at that layer.
    """
    if interpret is None:
        interpret = default_interpret()
    if bias_g is None:
        bias_g = jnp.zeros((1, qw_g.shape[-1]), jnp.float32)
    if bias_u is None:
        bias_u = jnp.zeros((1, qw_u.shape[-1]), jnp.float32)
    return stamp_quant_dual_matmul_pallas(
        x, qw_g, sw_g, zw_g, bias_g.reshape(1, -1).astype(jnp.float32),
        qw_u, sw_u, zw_u, bias_u.reshape(1, -1).astype(jnp.float32),
        transform=transform, levels=levels, skip_first=skip_first,
        num_hi=num_hi, hi_bits=hi_bits, lo_bits=lo_bits, epilogue=epilogue,
        layer=layer, out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def stamp_decode_matmul(x, qw, sw, zw, bias=None, *, layer=None,
                        out_dtype=None, interpret: bool | None = None):
    """Fused single-token decode linear (see `decode_matmul.py`).

    x: (B, K) float — one token per slot; qw: (K, N) signed int8 codes from
    `prepare_linear`; sw/zw: (1, N) f32.  No sequence transform: a lone
    decode token is its own (trivially Toeplitz) sequence, so STaMP reduces
    to the 8-bit per-token quantize + integer GEMM.  ``layer``: qw/sw/zw
    are stacks read in place at that layer.
    """
    if interpret is None:
        interpret = default_interpret()
    if bias is None:
        bias = jnp.zeros((1, qw.shape[-1]), jnp.float32)
    return stamp_decode_matmul_pallas(
        x, qw, sw, zw, bias.reshape(1, -1).astype(jnp.float32),
        layer=layer, out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_f",
                                             "out_dtype", "interpret"))
def stamp_quant_ragged_matmul(table, qx, sx, zx,
                              qw_gate, sw_gate, zw_gate,
                              qw_up, sw_up, zw_up,
                              qw_down, sw_down, zw_down, *, layer=None,
                              block_m: int = 32, block_f: int = 512,
                              out_dtype=jnp.float32,
                              interpret: bool | None = None):
    """Grouped MoE expert FFN over ragged per-expert row groups (see
    `stamp_matmul.stamp_quant_ragged_matmul_pallas`): ``table`` is the
    (3·n_tiles,) scalar-prefetch tile table (fetch tile | expert | rows),
    qx/sx/zx the (n_tiles·block_m, d) int8 row codes with their per-row
    scale / shifted zp, grouped by expert at ``block_m``-aligned offsets.
    Returns the (n_tiles·block_m, d) expert outputs.  ``layer``: the
    expert weights are (L, E, ·, ·) stacks read in place at that layer."""
    if interpret is None:
        interpret = default_interpret()
    return stamp_quant_ragged_matmul_pallas(
        table, qx, sx, zx, qw_gate, sw_gate, zw_gate, qw_up, sw_up, zw_up,
        qw_down, sw_down, zw_down, block_m=block_m, block_f=block_f,
        layer=layer, out_dtype=out_dtype, interpret=interpret)
