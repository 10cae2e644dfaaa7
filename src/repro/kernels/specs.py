"""``kernel_spec()`` registry: the exact grid / BlockSpec / scratch metadata
every Pallas kernel in this package hands to ``pl.pallas_call``.

The static contract checker (`repro.analysis.contracts.kernel_contracts`)
must reason about the SAME specs the kernels execute with — not a parallel
hand-maintained description that drifts.  So instead of duplicating the
tiling here, each registry entry is a small *representative example call*
(concrete shapes at the kernel's default block sizes), and `kernel_spec()`
runs it under a capture shim: ``pallas_call`` is swapped for a recorder
that snapshots the grid, every BlockSpec's ``(block_shape, index_map)``,
the operand/output shapes and dtypes, the VMEM scratch allocations, and —
for `PrefetchScalarGridSpec` kernels — the concrete scalar-prefetch tables
(block tables, lengths, query starts), then returns zeros of the declared
``out_shape`` so the caller's epilogue still runs.  No kernel body ever
executes; a capture is pure metadata.

Index maps are captured as the live closures the kernel built, so the
checker can evaluate them over the full grid (including the
null-page/inactive-span clamp idioms of `paged_attention`) against the
recorded operand shapes.

Adding a kernel: give it an entry in ``KERNEL_EXAMPLES`` returning
``(fn, args, kwargs)`` with *small* concrete inputs (the grid is
enumerated exhaustively by the checker).  CI fails if a module under
``kernels/`` calls ``pallas_call`` with no registry coverage.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class BufferSpec:
    """One operand/output: its full shape+dtype and its BlockSpec halves."""
    shape: tuple
    dtype: Any
    block_shape: Optional[tuple]        # None => no BlockSpec (whole array)
    index_map: Optional[Callable]
    hbm: bool = False                   # left in HBM (memory_space ANY)


@dataclasses.dataclass
class KernelCapture:
    """One recorded ``pallas_call`` invocation."""
    name: str
    grid: tuple
    inputs: list            # list[BufferSpec] — non-prefetch operands
    outputs: list           # list[BufferSpec]
    scratch: list           # list[(shape, dtype)] — VMEM allocations
    num_scalar_prefetch: int
    prefetch: tuple         # concrete numpy tables fed to the index maps
    interpret: bool


@dataclasses.dataclass
class KernelExample:
    """A registry entry after capture: the example call + its captures."""
    name: str
    fn: Callable
    args: tuple
    kwargs: dict
    captures: list          # list[KernelCapture] (delegation may emit >1)


def _flatten_specs(specs):
    from jax.experimental import pallas as pl
    if specs is None:
        return [None]
    if isinstance(specs, pl.BlockSpec):
        return [specs]
    out = []
    for s in specs:
        out.extend(_flatten_specs(s))
    return out


def _shape_dtype(x):
    return tuple(x.shape), jnp.asarray(x).dtype if not hasattr(x, "dtype") \
        else x.dtype


def _is_memory(dtype) -> bool:
    """A scratch allocation that takes memory (semaphores take none)."""
    try:
        np.dtype(dtype)
    except TypeError:
        return False
    return True


@contextlib.contextmanager
def _capture_pallas(records: list, name: str):
    """Swap ``jax.experimental.pallas.pallas_call`` for a recorder.  Kernel
    modules resolve ``pl.pallas_call`` at call time through the module
    object, so patching the module attribute intercepts every call."""
    import jax.experimental.pallas as pl_mod

    real = pl_mod.pallas_call

    def fake(kernel, *, grid=None, grid_spec=None, in_specs=None,
             out_specs=None, out_shape=None, scratch_shapes=(),
             interpret=False, **kw):
        if grid_spec is not None:
            g = tuple(grid_spec.grid)
            ins = _flatten_specs(grid_spec.in_specs)
            outs = _flatten_specs(grid_spec.out_specs)
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
            scratch = list(getattr(grid_spec, "scratch_shapes", ()) or ())
        else:
            g = tuple(grid) if grid is not None else ()
            ins = _flatten_specs(in_specs)
            outs = _flatten_specs(out_specs)
            nsp = 0
            scratch = list(scratch_shapes or ())

        out_leaves = jax.tree_util.tree_leaves(out_shape)

        def runner(*operands):
            prefetch = tuple(np.asarray(o) for o in operands[:nsp])
            data = operands[nsp:]
            inputs = []
            for spec, op in zip(ins, data):
                blocked = spec is not None and spec.block_shape is not None
                inputs.append(BufferSpec(
                    shape=tuple(op.shape), dtype=jnp.asarray(op).dtype
                    if not hasattr(op, "dtype") else op.dtype,
                    block_shape=tuple(spec.block_shape) if blocked else None,
                    index_map=spec.index_map if blocked else None,
                    hbm=spec is not None and spec.memory_space is pl_mod.ANY))
            outputs = []
            for spec, sd in zip(outs, out_leaves):
                outputs.append(BufferSpec(
                    shape=tuple(sd.shape), dtype=sd.dtype,
                    block_shape=tuple(spec.block_shape) if spec else None,
                    index_map=spec.index_map if spec else None))
            records.append(KernelCapture(
                name=name, grid=g, inputs=inputs, outputs=outputs,
                scratch=[(tuple(s.shape), s.dtype) for s in scratch
                         if _is_memory(s.dtype)],
                num_scalar_prefetch=nsp, prefetch=prefetch,
                interpret=bool(interpret)))
            return jax.tree_util.tree_map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), out_shape)

        return runner

    pl_mod.pallas_call = fake
    try:
        yield
    finally:
        pl_mod.pallas_call = real


# ---------------------------------------------------------------------------
# representative examples — concrete shapes at the DEFAULT block sizes
# ---------------------------------------------------------------------------


def _rng():
    return np.random.default_rng(0)


def _prepared_weight(r, k, n):
    qw = r.integers(-128, 128, size=(k, n), dtype=np.int8)
    sw = r.uniform(1e-3, 1e-2, size=(1, n)).astype(np.float32)
    zw = r.integers(-8, 8, size=(1, n)).astype(np.float32)
    bias = r.standard_normal((1, n)).astype(np.float32)
    return qw, sw, zw, bias


def _ex_stamp_single():
    from repro.kernels.stamp_matmul import stamp_quant_matmul_pallas
    r = _rng()
    x = r.standard_normal((2, 16, 32)).astype(np.float32)
    qw, sw, zw, bias = _prepared_weight(r, 32, 256)
    return stamp_quant_matmul_pallas, (x, qw, sw, zw, bias), dict(num_hi=4)


def _ex_stamp_single_headsplit():
    from repro.kernels.stamp_matmul import stamp_quant_matmul_pallas
    r = _rng()
    x = r.standard_normal((2, 16, 4, 16)).astype(np.float32)  # K = 64
    qw, sw, zw, bias = _prepared_weight(r, 64, 256)
    return stamp_quant_matmul_pallas, (x, qw, sw, zw, bias), dict(num_hi=4)


def _ex_stamp_dual():
    from repro.kernels.stamp_matmul import stamp_quant_dual_matmul_pallas
    r = _rng()
    x = r.standard_normal((2, 16, 32)).astype(np.float32)
    qg, sg, zg, bg = _prepared_weight(r, 32, 256)
    qu, su, zu, bu = _prepared_weight(r, 32, 256)
    return stamp_quant_dual_matmul_pallas, \
        (x, qg, sg, zg, bg, qu, su, zu, bu), dict(num_hi=4)


def _ex_stamp_segment():
    from repro.kernels.stamp_matmul import stamp_quant_segment_matmul_pallas
    r = _rng()
    x = r.standard_normal((1, 32, 32)).astype(np.float32)  # 2 spans of 16
    qw, sw, zw, bias = _prepared_weight(r, 32, 256)
    return stamp_quant_segment_matmul_pallas, (x, qw, sw, zw, bias), \
        dict(seg_len=16, num_hi=4)


def _ex_stamp_grouped():
    from repro.kernels.stamp_matmul import stamp_quant_ragged_matmul_pallas
    r = _rng()
    e, bm, d, f = 4, 8, 32, 64
    # tile table: experts 0, 1 and 3 hold 8, 5 and 8 rows (expert 2
    # none), then an EMPTY tail tile that re-fetches tile 2 — the checker
    # proves the table-driven index maps in-bounds on exactly this table
    # (KC001)
    fetch = np.array([0, 1, 2, 2], np.int32)
    expert = np.array([0, 1, 3, 3], np.int32)
    rows = np.array([8, 5, 8, 0], np.int32)
    table = np.concatenate([fetch, expert, rows])
    n_rows = len(rows) * bm
    qx = r.integers(-128, 128, size=(n_rows, d), dtype=np.int8)
    sx = r.uniform(1e-3, 1e-2, size=(n_rows, 1)).astype(np.float32)
    zx = r.integers(-8, 8, size=(n_rows, 1)).astype(np.float32)

    def expert_w(k, n):
        qw = r.integers(-128, 128, size=(e, k, n), dtype=np.int8)
        sw = r.uniform(1e-3, 1e-2, size=(e, 1, n)).astype(np.float32)
        zw = r.integers(-8, 8, size=(e, 1, n)).astype(np.float32)
        return qw, sw, zw

    qg, sg, zg = expert_w(d, f)
    qu, su, zu = expert_w(d, f)
    qd, sd, zd = expert_w(f, d)
    return stamp_quant_ragged_matmul_pallas, \
        (table, qx, sx, zx, qg, sg, zg, qu, su, zu, qd, sd, zd), \
        dict(block_m=bm, block_f=32)


def _ex_decode_matmul():
    from repro.kernels.decode_matmul import stamp_decode_matmul_pallas
    r = _rng()
    x = r.standard_normal((4, 32)).astype(np.float32)
    qw, sw, zw, bias = _prepared_weight(r, 32, 512)
    return stamp_decode_matmul_pallas, (x, qw, sw, zw, bias), {}


def _ex_int8_matmul():
    from repro.kernels.int8_matmul import int8_matmul_pallas
    r = _rng()
    m, k, n = 128, 128, 128           # defaults: one (128, 128, 128) block
    qx = r.integers(-128, 128, size=(m, k), dtype=np.int8)
    qw = r.integers(-128, 128, size=(k, n), dtype=np.int8)
    sx = r.uniform(1e-3, 1e-2, size=(m, 1)).astype(np.float32)
    zx = r.integers(-8, 8, size=(m, 1)).astype(np.float32)
    sw = r.uniform(1e-3, 1e-2, size=(1, n)).astype(np.float32)
    zw = r.integers(-8, 8, size=(1, n)).astype(np.float32)
    return int8_matmul_pallas, (qx, qw, sx, zx, sw, zw), {}


def _ex_haar_dwt():
    from repro.kernels.haar_dwt import haar_dwt_pallas
    x = _rng().standard_normal((2, 16, 256)).astype(np.float32)
    return haar_dwt_pallas, (x,), {}


def _ex_wht_seq():
    from repro.kernels.wht import wht_pallas
    x = _rng().standard_normal((2, 16, 256)).astype(np.float32)
    return wht_pallas, (x,), dict(axis=-2)


def _ex_wht_feat():
    from repro.kernels.wht import wht_pallas
    x = _rng().standard_normal((2, 256, 128)).astype(np.float32)
    return wht_pallas, (x,), dict(axis=-1)


def _ex_quant_pack():
    from repro.kernels.quant_pack import quant_pack_pallas
    x = _rng().standard_normal((2, 256, 64)).astype(np.float32)
    return quant_pack_pallas, (x,), dict(bits=4)


def _ex_cache_attention():
    from repro.kernels.cache_attention import cache_decode_attention
    r = _rng()
    b, h, g, hd, hi, s_lo = 2, 4, 2, 32, 16, 64
    s = hi + s_lo
    entry = {
        "k_hi": r.integers(-128, 128, size=(b, hi, g, hd), dtype=np.int8),
        "v_hi": r.integers(-128, 128, size=(b, hi, g, hd), dtype=np.int8),
        "k_lo": r.integers(0, 256, size=(b, s_lo, g, hd // 2),
                           dtype=np.uint8),
        "v_lo": r.integers(0, 256, size=(b, s_lo, g, hd // 2),
                           dtype=np.uint8),
        "k_scale": r.uniform(1e-3, 1e-2, size=(b, s, g)).astype(np.float32),
        "k_zp": r.integers(0, 8, size=(b, s, g)).astype(np.float32),
        "v_scale": r.uniform(1e-3, 1e-2, size=(b, s, g)).astype(np.float32),
        "v_zp": r.integers(0, 8, size=(b, s, g)).astype(np.float32),
    }
    q = r.standard_normal((b, 1, h, hd)).astype(np.float32)
    lengths = np.array([20, 70], np.int32)
    return cache_decode_attention, (entry, q, lengths), dict(block_s=32)


def _paged_pools(r, g, hd, bs, n_hi_pages, n_lo_pages):
    return {
        "k_hi": r.integers(-128, 128, size=(n_hi_pages, bs, g, hd),
                           dtype=np.int8),
        "v_hi": r.integers(-128, 128, size=(n_hi_pages, bs, g, hd),
                           dtype=np.int8),
        "k_hi_scale": r.uniform(1e-3, 1e-2, size=(n_hi_pages, bs, g)
                                ).astype(np.float32),
        "k_hi_zp": r.integers(0, 8, size=(n_hi_pages, bs, g)
                              ).astype(np.float32),
        "v_hi_scale": r.uniform(1e-3, 1e-2, size=(n_hi_pages, bs, g)
                                ).astype(np.float32),
        "v_hi_zp": r.integers(0, 8, size=(n_hi_pages, bs, g)
                              ).astype(np.float32),
        "k_lo": r.integers(-128, 128, size=(n_lo_pages, bs, g * hd // 2),
                           dtype=np.int8),
        "v_lo": r.integers(-128, 128, size=(n_lo_pages, bs, g * hd // 2),
                           dtype=np.int8),
        "lo_scale_zp": _lo_params(r, g, bs, n_lo_pages),
    }


def _lo_params(r, g, bs, n_pages):
    """Random ``lo_scale_zp`` pages: f16 scale rows, then zero-point rows,
    stored as their int16 bits."""
    from repro.serving.paged_kvcache import lo_param_shape
    rows, lanes = lo_param_shape(bs, g)
    out = np.empty((n_pages, rows, lanes), np.float16)
    out[:, 0::2] = r.uniform(1e-3, 1e-2, size=(n_pages, rows // 2, lanes))
    out[:, 1::2] = r.integers(0, 8, size=(n_pages, rows // 2, lanes))
    return out.view(np.int16)


def _ex_paged_decode():
    from repro.kernels.paged_attention import paged_decode_attention
    r = _rng()
    g, h, hd, bs = 2, 4, 32, 16
    entry = _paged_pools(r, g, hd, bs, n_hi_pages=4, n_lo_pages=6)
    q = r.standard_normal((3, 1, h, hd)).astype(np.float32)
    lengths = np.array([20, 40, 9], np.int32)
    # unmapped logical blocks hold 0 — the null page — and mask via lengths
    hi_table = np.array([[1], [2], [0]], np.int32)
    lo_table = np.array([[1, 2, 0], [3, 4, 5], [0, 0, 0]], np.int32)
    return paged_decode_attention, \
        (entry, q, lengths, hi_table, lo_table, bs), {}


def _ex_paged_ragged():
    from repro.kernels.paged_attention import paged_ragged_attention
    r = _rng()
    g, h, hd, bs, c_len = 2, 4, 32, 16, 8
    n_pf, s_slots = 2, 3
    entry = _paged_pools(r, g, hd, bs, n_hi_pages=4, n_lo_pages=6)
    q_pf = r.standard_normal((n_pf, c_len, h, hd)).astype(np.float32)
    k_pf = r.standard_normal((n_pf, c_len, g, hd)).astype(np.float32)
    v_pf = r.standard_normal((n_pf, c_len, g, hd)).astype(np.float32)
    q_dec = r.standard_normal((s_slots, 1, h, hd)).astype(np.float32)
    # positions read through pages: chunk rows their cached prefix (0: a
    # first chunk), decode slots their length
    lengths = np.array([0, 16, 20, 40, 9], np.int32)
    hi_table = np.array([[1], [3], [1], [2], [0]], np.int32)
    lo_table = np.array([[0, 0, 0], [1, 2, 0],
                         [1, 2, 0], [3, 4, 5], [0, 0, 0]], np.int32)
    return paged_ragged_attention, \
        (entry, q_pf, q_dec, k_pf, v_pf, lengths, hi_table, lo_table,
         bs), {}


KERNEL_EXAMPLES: dict = {
    "stamp_matmul.single": _ex_stamp_single,
    "stamp_matmul.single_headsplit": _ex_stamp_single_headsplit,
    "stamp_matmul.dual": _ex_stamp_dual,
    "stamp_matmul.segment": _ex_stamp_segment,
    "stamp_matmul.grouped": _ex_stamp_grouped,
    "decode_matmul": _ex_decode_matmul,
    "int8_matmul": _ex_int8_matmul,
    "haar_dwt": _ex_haar_dwt,
    "wht.seq": _ex_wht_seq,
    "wht.feat": _ex_wht_feat,
    "quant_pack": _ex_quant_pack,
    "cache_attention": _ex_cache_attention,
    "paged_attention.decode": _ex_paged_decode,
    "paged_attention.ragged": _ex_paged_ragged,
}


def kernel_spec(name: str) -> KernelExample:
    """Run one registry example under the capture shim and return its
    recorded ``pallas_call`` metadata (no kernel body executes)."""
    builder = KERNEL_EXAMPLES[name]
    fn, args, kwargs = builder()
    records: list = []
    with _capture_pallas(records, name):
        fn(*args, **kwargs)
    if not records:
        raise RuntimeError(f"kernel example {name!r} made no pallas_call")
    return KernelExample(name=name, fn=fn, args=args, kwargs=kwargs,
                         captures=records)


def all_kernel_specs() -> dict:
    """Capture every registered kernel example: {name: KernelExample}."""
    return {name: kernel_spec(name) for name in KERNEL_EXAMPLES}
