"""Pass 1 — Pallas kernel contract checker.

Consumes the capture registry (`repro.kernels.specs`): for every registered
kernel example this pass

* **proves in-bounds access** (``KC001``): each BlockSpec index map is
  evaluated at every grid cell with the example's *concrete*
  scalar-prefetch tables (block tables, lengths), and the selected block
  ``idx·block … idx·block+block`` must sit inside the operand.  This is
  exactly the property the null-page and inactive-span clamp idioms in
  `paged_attention` exist to uphold — a table entry past the pool, or a
  clamp off by one, fails here without running the kernel;
* **checks divisibility** (``KC003``): every blocked dimension must tile
  its operand exactly (Pallas pads reads but a partial tail block means
  the kernel math sees garbage rows);
* **sums the VMEM footprint** (``KC002``): one block per operand and
  output (×2 for Mosaic's double buffering) plus every scratch allocation
  must fit the budget (default: the kernels' requested
  ``VMEM_LIMIT_BYTES``);
* **checks accumulator dtypes** (``KC004``/``KC005``): the example is
  re-traced with ``jax.make_jaxpr`` (tracing only — no kernel executes on
  device) and every ``dot_general`` in the program, including the kernel
  jaxprs carried in ``pallas_call`` params, must not accumulate in f16,
  and int8×int8 GEMMs must accumulate in int32.
"""

from __future__ import annotations

import functools
import itertools

import jax
import numpy as np

from repro.analysis.contracts.findings import Finding
from repro.kernels.stamp_matmul import VMEM_LIMIT_BYTES

# the scoped-VMEM limit the kernels request from the compiler
DEFAULT_VMEM_BUDGET = VMEM_LIMIT_BYTES
_MAX_GRID_CELLS = 200_000             # exhaustive-enumeration backstop


def _itemsize(dtype) -> int:
    return np.dtype(dtype).itemsize


def _block_bytes(buf) -> int:
    shape = buf.block_shape if buf.block_shape is not None else buf.shape
    n = 1
    for d in shape:
        n *= int(d)
    return n * _itemsize(buf.dtype)


def _check_capture(cap, vmem_budget: int, out: list) -> None:
    pseudo = f"kernels/{cap.name}"
    buffers = [("in", i, b) for i, b in enumerate(cap.inputs)] + \
              [("out", i, b) for i, b in enumerate(cap.outputs)]

    # -- divisibility ----------------------------------------------------
    for role, i, buf in buffers:
        if buf.block_shape is None:
            continue
        if len(buf.block_shape) != len(buf.shape):
            out.append(Finding(
                "KC003", pseudo, cap.name,
                f"{role}[{i}]: block rank {len(buf.block_shape)} != operand "
                f"rank {len(buf.shape)}"))
            continue
        for d, (blk, dim) in enumerate(zip(buf.block_shape, buf.shape)):
            if blk is None:
                continue
            if dim % blk:
                out.append(Finding(
                    "KC003", pseudo, cap.name,
                    f"{role}[{i}] dim {d}: shape {dim} % block {blk} != 0"))

    # -- VMEM footprint --------------------------------------------------
    resident = sum(_block_bytes(b) for _, _, b in buffers
                   if not b.hbm) * 2                         # dbl-buffer
    resident += sum(int(np.prod(shape)) * _itemsize(dt)
                    for shape, dt in cap.scratch)
    if resident > vmem_budget:
        out.append(Finding(
            "KC002", pseudo, cap.name,
            f"VMEM footprint {resident} B exceeds budget {vmem_budget} B "
            f"(blocks ×2 + scratch)"))

    # -- in-bounds index maps over the full grid -------------------------
    total = 1
    for g in cap.grid:
        total *= int(g)
    if total > _MAX_GRID_CELLS:
        out.append(Finding(
            "KC001", pseudo, cap.name,
            f"grid {cap.grid} has {total} cells — example too large to "
            f"enumerate; shrink the registry example"))
        return
    prefetch = cap.prefetch
    for ids in itertools.product(*(range(int(g)) for g in cap.grid)):
        for role, i, buf in buffers:
            if buf.index_map is None:
                continue
            idx = buf.index_map(*ids, *prefetch)
            if not isinstance(idx, tuple):
                idx = (idx,)
            try:
                idx = tuple(int(v) for v in idx)
            except TypeError:
                out.append(Finding(
                    "KC001", pseudo, cap.name,
                    f"{role}[{i}] index map returned non-integer {idx!r} "
                    f"at grid cell {ids}"))
                continue
            for d, (bi, blk, dim) in enumerate(
                    zip(idx, buf.block_shape, buf.shape)):
                if blk is None:
                    blk = 1
                if bi < 0 or (bi + 1) * blk > dim:
                    out.append(Finding(
                        "KC001", pseudo, cap.name,
                        f"{role}[{i}] dim {d}: block index {bi} × block "
                        f"{blk} reaches past shape {dim} at grid cell "
                        f"{ids}"))
                    return  # one cell is proof enough for this capture


def _iter_subjaxprs(params: dict):
    from jax.extend.core import Jaxpr, ClosedJaxpr
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for item in vs:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def _walk_dots(jaxpr, visit, seen=None):
    seen = seen if seen is not None else set()
    if id(jaxpr) in seen:
        return
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        visit(eqn)
        for sub in _iter_subjaxprs(eqn.params):
            _walk_dots(sub, visit, seen)


def check_accumulators(fn, args, kwargs, name: str, out: list) -> None:
    """``KC004``/``KC005`` over a traced example (kernel jaxprs included)."""
    pseudo = f"kernels/{name}"
    # trace with python scalars (block sizes &c.) kept static
    dyn_idx = [i for i, a in enumerate(args)
               if not isinstance(a, (bool, int, float, str))]

    def wrapper(*dyn):
        full = list(args)
        for i, v in zip(dyn_idx, dyn):
            full[i] = v
        return fn(*full, **kwargs)

    try:
        closed = jax.make_jaxpr(wrapper)(*[args[i] for i in dyn_idx])
    except Exception as e:  # pragma: no cover - registry example broke
        out.append(Finding("KC005", pseudo, name,
                           f"could not trace example: {e!r}"))
        return

    def visit(eqn):
        if eqn.primitive.name != "dot_general":
            return
        in_dt = [v.aval.dtype for v in eqn.invars]
        out_dt = eqn.outvars[0].aval.dtype
        if out_dt == np.float16:
            out.append(Finding(
                "KC004", pseudo, name,
                f"dot_general accumulates in f16 (inputs "
                f"{[str(d) for d in in_dt]})"))
        if all(d == np.int8 for d in in_dt) and out_dt != np.int32:
            out.append(Finding(
                "KC005", pseudo, name,
                f"int8×int8 dot_general accumulates in {out_dt}, not int32"))

    _walk_dots(closed.jaxpr, visit)


def check_kernels(vmem_budget: int = DEFAULT_VMEM_BUDGET,
                  names=None) -> list:
    """Run the kernel contract pass over the capture registry."""
    from repro.kernels import specs as KS
    out: list = []
    for name in (names or KS.KERNEL_EXAMPLES):
        ex = KS.kernel_spec(name)
        for cap in ex.captures:
            _check_capture(cap, vmem_budget, out)
        check_accumulators(ex.fn, ex.args, ex.kwargs, name, out)
    return out


def check_capture(cap, vmem_budget: int = DEFAULT_VMEM_BUDGET) -> list:
    """Check a single externally-built capture (test fixtures use this)."""
    out: list = []
    _check_capture(cap, vmem_budget, out)
    return out
