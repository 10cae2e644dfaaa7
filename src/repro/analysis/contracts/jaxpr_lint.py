"""Pass 3 — jaxpr dispatch & dtype lint.

Traces the serving entry points — dense ``prefill`` (reference and fused
execution), contiguous ``decode_step`` and the paged ``paged_decode_step``
(the graph the unified step's all-decode steady state delegates to) — on a
reduced representative config with ``jax.make_jaxpr`` and walks every
equation (sub-jaxprs included) for dtype-discipline violations:

* ``JX001`` — any f64 value: the serving stack is bf16/f32 + integer
  codes; a float64 means an accidental Python-float promotion doubling
  HBM traffic;
* ``JX002`` — a ``dot_general`` producing f16: GEMMs accumulate in f32 or
  int32, never half precision (the KC004 rule, applied to the whole
  program rather than one kernel);
* ``JX003`` — ``convert_element_type`` round trips ``A → B → A`` with a
  *narrower* B: the value silently lost precision in transit — exactly
  the class of bug ResQ-style bf16-residual-over-int4 schemes introduce
  at each new dtype boundary;
* ``JX004`` — host callback primitives inside the step program: one
  device dispatch per engine step is a load-bearing serving contract
  (PR 4), and a ``pure_callback``/``io_callback`` breaks it silently.

Tracing executes no device code; the pass costs a few seconds of Python.
"""

from __future__ import annotations

import numpy as np

from repro.analysis.contracts.findings import Finding

_CALLBACK_PRIMS = ("pure_callback", "io_callback", "python_callback",
                   "outside_call", "host_callback_call", "debug_callback")

REPRESENTATIVE_CONFIG = "llama3_8b"


def _iter_subjaxprs(params: dict):
    from jax.extend.core import Jaxpr, ClosedJaxpr
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for item in vs:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def lint_jaxpr(closed, entry_name: str, path: str = "models/lm.py") -> list:
    """Walk one traced entry point; returns its findings."""
    out: list = []
    reported: set = set()

    def report(code, msg):
        if (code, msg) in reported:      # one finding per distinct defect
            return
        reported.add((code, msg))
        out.append(Finding(code, path, entry_name, msg))

    def walk(jaxpr, conv_src, seen):
        # conv_src: var -> source dtype of the convert that produced it
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "dtype", None) is not \
                        None and aval.dtype == np.float64:
                    report("JX001", f"{prim} produces f64")
            if prim == "dot_general":
                if eqn.outvars[0].aval.dtype == np.float16:
                    report("JX002",
                           f"dot_general accumulates in f16 (inputs "
                           f"{[str(v.aval.dtype) for v in eqn.invars]})")
            if prim == "convert_element_type":
                src_v = eqn.invars[0]
                src_dt = src_v.aval.dtype
                dst_dt = eqn.outvars[0].aval.dtype
                origin = conv_src.get(id(src_v))
                if origin is not None:
                    import jax.numpy as jnp
                    a, b = origin, src_dt
                    # jnp.issubdtype, not np: bfloat16 is an ml_dtypes
                    # extension outside numpy's floating hierarchy
                    if a == dst_dt and jnp.issubdtype(a, jnp.floating) and \
                            jnp.issubdtype(b, jnp.floating) and \
                            np.dtype(b).itemsize < np.dtype(a).itemsize:
                        report("JX003",
                               f"convert round trip {a} -> {b} -> {dst_dt} "
                               f"loses precision in transit")
                conv_src[id(eqn.outvars[0])] = src_dt
            if any(prim == c or prim.endswith(c) for c in _CALLBACK_PRIMS):
                report("JX004", f"host callback primitive {prim!r}")
            for sub in _iter_subjaxprs(eqn.params):
                walk(sub, {}, seen)

    walk(closed.jaxpr, {}, set())
    return out


def _traced_entry_points(config_name: str = REPRESENTATIVE_CONFIG):
    """Yield (entry_name, closed_jaxpr) for the representative traces."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.core.stamp import StampConfig
    from repro.models import lm
    from repro.serving import kvcache as KV
    from repro.serving import paged_kvcache as PKV

    cfg = get_reduced(config_name)
    params = lm.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    tokens = jnp.zeros((1, 16), jnp.int32)

    for execution in ("reference", "fused"):
        stamp = StampConfig(execution=execution, num_hi_tokens=4)
        serve = lm.ServeConfig(stamp=stamp, kv=KV.KVCacheConfig())
        p = lm.prepare_fused_weights(params, stamp) \
            if execution == "fused" else params
        yield (f"prefill[{config_name}:{execution}]",
               jax.make_jaxpr(lambda pp, t, s=serve: lm.prefill(
                   pp, {"tokens": t}, cfg, s))(p, tokens))
        if execution == "fused":
            serve_dec = lm.ServeConfig(
                stamp=stamp,
                kv=KV.KVCacheConfig(quantized=True, num_hi=16),
                cache_capacity=48, fused_decode_matmul=True)
            toks_dec = jnp.zeros((1, 32), jnp.int32)
            _, cache = lm.prefill(p, {"tokens": toks_dec}, cfg, serve_dec)
            yield (f"decode_step[{config_name}:{execution}]",
                   jax.make_jaxpr(lambda pp, c, t, pos, s=serve_dec:
                                  lm.decode_step(pp, c, t, pos, cfg, s))
                   (p, cache, jnp.zeros((1,), jnp.int32),
                    jnp.int32(32)))

    # paged decode step — the unified step's all-decode steady state
    stamp = StampConfig(execution="fused", num_hi_tokens=4)
    pcfg = PKV.PagedCacheConfig(
        block_size=8, num_lo_blocks=8, num_hi_blocks=4,
        max_blocks_per_seq=4,
        quant=KV.KVCacheConfig(quantized=True, num_hi=8))
    serve = lm.ServeConfig(stamp=stamp, kv=pcfg.quant, paged=pcfg,
                           fused_decode_matmul=True)
    p = lm.prepare_fused_weights(params, stamp)
    pools = lm.init_paged_cache(cfg, pcfg)
    s_slots = 2
    yield (f"paged_decode_step[{config_name}:fused]",
           jax.make_jaxpr(lambda pp, pls, t, pos, ht, lt, pg, off, ih:
                          lm.paged_decode_step(pp, pls, t, pos, ht, lt,
                                               pg, off, ih, cfg, serve))
           (p, pools,
            jnp.zeros((s_slots,), jnp.int32),
            jnp.array([9, 12], jnp.int32),
            jnp.zeros((s_slots, pcfg.hi_blocks_per_seq), jnp.int32),
            jnp.zeros((s_slots, pcfg.max_blocks_per_seq), jnp.int32),
            jnp.zeros((s_slots,), jnp.int32),
            jnp.zeros((s_slots,), jnp.int32),
            jnp.zeros((s_slots,), bool)))


def check_entry_points(config_name: str = REPRESENTATIVE_CONFIG) -> list:
    out: list = []
    for entry_name, closed in _traced_entry_points(config_name):
        out.extend(lint_jaxpr(closed, entry_name))
    return out
