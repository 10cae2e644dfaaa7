"""Orthogonal sequence transforms (paper §3, §3.2).

All transforms act along an arbitrary ``axis`` (default ``-2``, the sequence
axis of ``(..., s, d)`` activations) and are exactly orthonormal, so
``inverse(forward(x)) == x`` and the Frobenius norm is preserved (the premise
of Theorem 1 / Eq. 10).

Implemented bases, in the paper's cost order:

* **KLT** — eigenbasis of the sequence autocorrelation ``S = E[XXᵀ]``
  (optimal energy compaction; needs calibration; O(s²) apply).
* **DCT-II** (orthonormal) — near-KLT for Toeplitz autocorrelation (Szegő);
  O(s²) as a matrix here, O(s log s) on device via the Pallas/FFT path.
* **WHT** — sign-only Fourier approximation; O(s log s) butterfly.
* **Haar DWT** — O(s) lifting; ``levels`` passes halve the low-pass band each
  time, concentrating energy in the first ``s / 2^levels`` tokens with
  *discrete* energy levels (§3.3 argues this suits 2-level mixed precision).

Non-power-of-two lengths: WHT/DWT operate on the largest admissible prefix at
each stage and pass the remainder through untouched — the resulting operator
is block-diagonal with an identity block, hence still orthonormal.  This also
implements the paper's first-token exception (§B.2) via ``skip_first``:
``L = blockdiag(I₁, L')`` keeps the attention-sink token unmixed.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_SQRT2 = float(np.sqrt(2.0))


# ---------------------------------------------------------------------------
# axis helpers
# ---------------------------------------------------------------------------


def _moveaxis_last(x: Array, axis: int) -> tuple[Array, int]:
    axis = axis % x.ndim
    return jnp.moveaxis(x, axis, -1), axis


def _restore_axis(x: Array, axis: int) -> Array:
    return jnp.moveaxis(x, -1, axis)


# ---------------------------------------------------------------------------
# Haar DWT (lifting form, orthonormal)
# ---------------------------------------------------------------------------


def _haar_level(x: Array) -> Array:
    """One orthonormal Haar pass along the last axis.

    Odd tail elements are passed through (identity block) to keep the
    operator square and orthonormal for any length.
    """
    n = x.shape[-1]
    pairs = n // 2
    even = x[..., : 2 * pairs : 2]
    odd = x[..., 1 : 2 * pairs : 2]
    approx = (even + odd) / _SQRT2
    detail = (even - odd) / _SQRT2
    out = jnp.concatenate([approx, detail], axis=-1)
    if n % 2:
        out = jnp.concatenate([out, x[..., -1:]], axis=-1)
    return out


def _haar_level_inv(y: Array) -> Array:
    n = y.shape[-1]
    pairs = n // 2
    approx = y[..., :pairs]
    detail = y[..., pairs : 2 * pairs]
    even = (approx + detail) / _SQRT2
    odd = (approx - detail) / _SQRT2
    out = jnp.stack([even, odd], axis=-1).reshape(*y.shape[:-1], 2 * pairs)
    if n % 2:
        out = jnp.concatenate([out, y[..., -1:]], axis=-1)
    return out


def haar_dwt(x: Array, levels: int = 3, axis: int = -2,
             skip_first: bool = False) -> Array:
    """Multi-level Haar DWT along ``axis``.

    After each level only the low-pass (first) half is transformed again, so
    energy accumulates in the leading ``s / 2^levels`` coefficients.
    """
    x, axis = _moveaxis_last(x, axis)
    if skip_first:
        head, x0 = x[..., :1], x[..., 1:]
    else:
        head, x0 = None, x
    n = x0.shape[-1]
    lo = n
    out = x0
    for _ in range(levels):
        if lo < 2:
            break
        low = _haar_level(out[..., :lo])
        out = jnp.concatenate([low, out[..., lo:]], axis=-1)
        lo = (lo + 1) // 2 if lo % 2 else lo // 2
    if head is not None:
        out = jnp.concatenate([head, out], axis=-1)
    return _restore_axis(out, axis)


def haar_idwt(y: Array, levels: int = 3, axis: int = -2,
              skip_first: bool = False) -> Array:
    """Inverse of :func:`haar_dwt` (same ``levels``/``skip_first``)."""
    y, axis = _moveaxis_last(y, axis)
    if skip_first:
        head, y0 = y[..., :1], y[..., 1:]
    else:
        head, y0 = None, y
    n = y0.shape[-1]
    # reconstruct the sequence of low-pass band sizes used by the forward
    sizes = [n]
    lo = n
    for _ in range(levels):
        if lo < 2:
            break
        lo = (lo + 1) // 2 if lo % 2 else lo // 2
        sizes.append(lo)
    out = y0
    for lo_prev, lo in zip(sizes[-1:0:-1], sizes[-2::-1]):
        low = _haar_level_inv(out[..., :lo])
        out = jnp.concatenate([low, out[..., lo:]], axis=-1)
    if head is not None:
        out = jnp.concatenate([head, out], axis=-1)
    return _restore_axis(out, axis)


@functools.lru_cache(maxsize=32)
def _subband_order(h: int, w: int, levels: int) -> np.ndarray:
    """Permutation putting the final LL quadrant first, then per-level detail
    subbands — so 'first k tokens' aligns with descending energy.  The
    permutation is orthogonal, so Theorem 1's preconditions still hold."""
    lh, lw = h, w
    sizes = []
    for _ in range(levels):
        if lh < 2 or lw < 2:
            break
        sizes.append((lh, lw))
        lh, lw = lh // 2, lw // 2
    grid = np.arange(h * w).reshape(h, w)
    order = [grid[:lh, :lw].ravel()]          # LL_L first
    for ph, pw in sizes[::-1]:                # coarsest detail bands first
        hh, hw_ = ph // 2, pw // 2
        order.append(grid[:hh, hw_:pw].ravel())    # LH
        order.append(grid[hh:ph, :hw_].ravel())    # HL
        order.append(grid[hh:ph, hw_:pw].ravel())  # HH
    return np.concatenate(order)


def haar_dwt_2d(x: Array, hw: tuple[int, int], levels: int = 3,
                axis: int = -2) -> Array:
    """2-D Haar DWT for LVM activations whose sequence axis flattens an
    ``H × W`` latent grid (paper §5.1 uses 2-D DWT; the block-Toeplitz
    autocorrelation of Fig. 3a comes from exactly this flattening).

    Each level transforms rows then columns of the current low-pass quadrant,
    pushing energy into the top-left ``(H/2ˡ, W/2ˡ)`` corner; the output is
    read out in subband order (LL first) so high-energy coefficients lead the
    sequence.
    """
    h, w = hw
    x, axis = _moveaxis_last(x, axis)
    if x.shape[-1] != h * w:
        raise ValueError(f"sequence {x.shape[-1]} != H*W {h * w}")
    img = x.reshape(*x.shape[:-1], h, w)
    lh, lw = h, w
    for _ in range(levels):
        if lh < 2 or lw < 2:
            break
        quad = img[..., :lh, :lw]
        quad = _haar_level(quad)                      # rows (last axis = W)
        quad = jnp.swapaxes(_haar_level(jnp.swapaxes(quad, -1, -2)), -1, -2)
        img = img.at[..., :lh, :lw].set(quad)
        lh, lw = lh // 2, lw // 2
    out = img.reshape(*x.shape[:-1], h * w)
    perm = jnp.asarray(_subband_order(h, w, levels))
    out = jnp.take(out, perm, axis=-1)
    return _restore_axis(out, axis)


def haar_idwt_2d(y: Array, hw: tuple[int, int], levels: int = 3,
                 axis: int = -2) -> Array:
    h, w = hw
    y, axis = _moveaxis_last(y, axis)
    perm = _subband_order(h, w, levels)
    inv_perm = jnp.asarray(np.argsort(perm))
    y = jnp.take(y, inv_perm, axis=-1)
    img = y.reshape(*y.shape[:-1], h, w)
    sizes = []
    lh, lw = h, w
    for _ in range(levels):
        if lh < 2 or lw < 2:
            break
        sizes.append((lh, lw))
        lh, lw = lh // 2, lw // 2
    for lh, lw in reversed(sizes):
        quad = img[..., :lh, :lw]
        quad = jnp.swapaxes(_haar_level_inv(jnp.swapaxes(quad, -1, -2)), -1, -2)
        quad = _haar_level_inv(quad)
        img = img.at[..., :lh, :lw].set(quad)
    out = img.reshape(*y.shape[:-1], h * w)
    return _restore_axis(out, axis)


# ---------------------------------------------------------------------------
# DCT-II (orthonormal)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def dct_matrix(n: int) -> np.ndarray:
    """Orthonormal DCT-II basis, rows = basis vectors (row 0 = DC)."""
    k = np.arange(n)[:, None]
    i = np.arange(n)[None, :]
    m = np.cos(np.pi * k * (2 * i + 1) / (2 * n))
    m[0] *= np.sqrt(1.0 / n)
    m[1:] *= np.sqrt(2.0 / n)
    return m.astype(np.float32)


def dct(x: Array, axis: int = -2, skip_first: bool = False) -> Array:
    x, axis = _moveaxis_last(x, axis)
    if skip_first:
        head, x0 = x[..., :1], x[..., 1:]
    else:
        head, x0 = None, x
    m = jnp.asarray(dct_matrix(x0.shape[-1]), x0.dtype)
    out = jnp.einsum("...i,ki->...k", x0, m)
    if head is not None:
        out = jnp.concatenate([head, out], axis=-1)
    return _restore_axis(out, axis)


def idct(y: Array, axis: int = -2, skip_first: bool = False) -> Array:
    y, axis = _moveaxis_last(y, axis)
    if skip_first:
        head, y0 = y[..., :1], y[..., 1:]
    else:
        head, y0 = None, y
    m = jnp.asarray(dct_matrix(y0.shape[-1]), y0.dtype)
    out = jnp.einsum("...k,ki->...i", y0, m)
    if head is not None:
        out = jnp.concatenate([head, out], axis=-1)
    return _restore_axis(out, axis)


# ---------------------------------------------------------------------------
# Walsh–Hadamard (fast butterfly, orthonormal, pow2 prefix)
# ---------------------------------------------------------------------------


def _largest_pow2(n: int) -> int:
    return 1 << (n.bit_length() - 1) if n else 0


def wht(x: Array, axis: int = -2, skip_first: bool = False) -> Array:
    """Fast Walsh–Hadamard transform, O(s log s) butterfly (§3.2: retain the
    sign of the Fourier coefficients).  Operates on the largest power-of-two
    prefix; the remainder passes through (identity block)."""
    x, axis = _moveaxis_last(x, axis)
    if skip_first:
        head, x0 = x[..., :1], x[..., 1:]
    else:
        head, x0 = None, x
    n = x0.shape[-1]
    p = _largest_pow2(n)
    body, tail = x0[..., :p], x0[..., p:]
    h = 1
    while h < p:
        shaped = body.reshape(*body.shape[:-1], p // (2 * h), 2, h)
        a = shaped[..., 0, :]
        b = shaped[..., 1, :]
        shaped = jnp.stack([a + b, a - b], axis=-2)
        body = shaped.reshape(*body.shape[:-1], p)
        h *= 2
    body = body / float(np.sqrt(p))
    out = jnp.concatenate([body, tail], axis=-1) if tail.shape[-1] else body
    if head is not None:
        out = jnp.concatenate([head, out], axis=-1)
    return _restore_axis(out, axis)


# orthonormal WHT is involutive on the pow2 block
def iwht(y: Array, axis: int = -2, skip_first: bool = False) -> Array:
    return wht(y, axis=axis, skip_first=skip_first)


# ---------------------------------------------------------------------------
# dense (s, s) forms of the fast transforms
# ---------------------------------------------------------------------------


def _haar_matrix(n: int, levels: int) -> np.ndarray:
    out = np.eye(n)
    lo = n
    for _ in range(levels):
        if lo < 2:
            break
        pairs = lo // 2
        i = np.arange(pairs)
        step = np.eye(n)
        step[:lo, :lo] = 0.0
        step[i, 2 * i] = step[i, 2 * i + 1] = 1.0 / _SQRT2     # approx rows
        step[pairs + i, 2 * i] = 1.0 / _SQRT2                  # detail rows
        step[pairs + i, 2 * i + 1] = -1.0 / _SQRT2
        if lo % 2:
            step[lo - 1, lo - 1] = 1.0                         # odd tail
        out = step @ out
        lo = (lo + 1) // 2 if lo % 2 else lo // 2
    return out


def _wht_matrix(n: int) -> np.ndarray:
    p = _largest_pow2(n)
    h = np.ones((1, 1))
    while h.shape[0] < p:
        h = np.block([[h, h], [h, -h]])
    out = np.eye(n)
    out[:p, :p] = h / np.sqrt(p)
    return out


@functools.lru_cache(maxsize=64)
def sequence_matrix(kind: str, n: int, levels: int = 3,
                    skip_first: bool = False) -> np.ndarray:
    """The orthonormal ``(n, n)`` matrix ``L`` of the ``dwt`` or ``wht``
    transform: ``sequence_transform(x, kind) == L @ x`` along the sequence
    axis, and the inverse is ``L.T``.  Kernels apply it as a matmul, which
    the TPU compiler lowers where the strided butterflies do not."""
    body = n - 1 if skip_first else n
    if kind == "dwt":
        m = _haar_matrix(body, levels)
    elif kind == "wht":
        m = _wht_matrix(body)
    else:
        raise ValueError(f"no dense matrix form for transform {kind!r}")
    if skip_first:
        m = np.block([[np.ones((1, 1)), np.zeros((1, body))],
                      [np.zeros((body, 1)), m]])
    m = m.astype(np.float32)
    m.setflags(write=False)          # cached: every caller gets this array
    return m


# ---------------------------------------------------------------------------
# KLT (calibrated eigenbasis)
# ---------------------------------------------------------------------------


def klt_basis(autocorr: np.ndarray) -> np.ndarray:
    """Rows = eigenvectors of S sorted by descending eigenvalue (§3.2: the
    optimal L is Uᵀ).  ``autocorr`` must be (s, s) symmetric."""
    s = np.asarray(autocorr, np.float64)
    s = (s + s.T) / 2
    vals, vecs = np.linalg.eigh(s)
    order = np.argsort(vals)[::-1]
    return vecs[:, order].T.astype(np.float32)


def apply_matrix(x: Array, m: Array, axis: int = -2,
                 inverse: bool = False) -> Array:
    """Apply an orthonormal basis ``m`` (rows = basis vectors) along
    ``axis``; ``inverse=True`` applies ``mᵀ``."""
    x, axis = _moveaxis_last(x, axis)
    m = jnp.asarray(m, x.dtype)
    eq = "...i,ki->...k" if not inverse else "...k,ki->...i"
    out = jnp.einsum(eq, x, m)
    return _restore_axis(out, axis)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def sequence_transform(
    x: Array,
    kind: str,
    axis: int = -2,
    levels: int = 3,
    skip_first: bool = False,
    hw: Optional[tuple[int, int]] = None,
    basis: Optional[Array] = None,
) -> Array:
    """Dispatch on the paper's transform family names."""
    if kind in ("none", "identity"):
        return x
    if kind == "dwt":
        return haar_dwt(x, levels=levels, axis=axis, skip_first=skip_first)
    if kind == "dwt2d":
        assert hw is not None, "dwt2d needs the (H, W) latent grid"
        return haar_dwt_2d(x, hw, levels=levels, axis=axis)
    if kind == "dct":
        return dct(x, axis=axis, skip_first=skip_first)
    if kind == "wht":
        return wht(x, axis=axis, skip_first=skip_first)
    if kind == "klt":
        assert basis is not None, "klt needs a calibrated basis"
        return apply_matrix(x, basis, axis=axis)
    raise ValueError(f"unknown sequence transform {kind!r}")


def inverse_sequence_transform(
    y: Array,
    kind: str,
    axis: int = -2,
    levels: int = 3,
    skip_first: bool = False,
    hw: Optional[tuple[int, int]] = None,
    basis: Optional[Array] = None,
) -> Array:
    if kind in ("none", "identity"):
        return y
    if kind == "dwt":
        return haar_idwt(y, levels=levels, axis=axis, skip_first=skip_first)
    if kind == "dwt2d":
        assert hw is not None
        return haar_idwt_2d(y, hw, levels=levels, axis=axis)
    if kind == "dct":
        return idct(y, axis=axis, skip_first=skip_first)
    if kind == "wht":
        return iwht(y, axis=axis, skip_first=skip_first)
    if kind == "klt":
        assert basis is not None
        return apply_matrix(y, basis, axis=axis, inverse=True)
    raise ValueError(f"unknown sequence transform {kind!r}")
