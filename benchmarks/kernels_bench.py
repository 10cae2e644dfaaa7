"""Kernel micro-benchmarks (interpret mode on CPU — correctness +
derived TPU traffic estimates; wall times are NOT TPU latencies).

``--out BENCH_kernels.json`` writes the rows as JSON; CI runs that on every
push and commits the refreshed file on main, so the repo accumulates a
per-PR perf trajectory instead of expiring artifacts."""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.kernels import ops


def run() -> list[dict]:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 1024, 256)).astype(np.float32))
    rows = []

    us, _ = timed(lambda: ops.haar_dwt_seq(x, levels=4, interpret=True), reps=2)
    hbm = 2 * x.size * 4
    rows.append({"name": "kernels/haar_dwt_seq_1k", "us_per_call": us,
                 "derived": f"tpu_hbm_bytes={hbm}"})

    us, _ = timed(lambda: ops.walsh_hadamard(x, axis=-2, interpret=True), reps=2)
    rows.append({"name": "kernels/wht_seq_1k", "us_per_call": us,
                 "derived": f"tpu_hbm_bytes={hbm}"})

    us, _ = timed(lambda: ops.quantize_pack(x, bits=4, interpret=True), reps=2)
    rows.append({"name": "kernels/quant_pack_int4", "us_per_call": us,
                 "derived": f"tpu_hbm_bytes={int(x.size * 4.5)}"})

    m = k = n = 256
    qx = jnp.asarray(rng.integers(0, 16, (m, k)), jnp.int8)
    qw = jnp.asarray(rng.integers(0, 16, (k, n)), jnp.int8)
    ones = jnp.ones((m, 1), jnp.float32)
    onesn = jnp.ones((1, n), jnp.float32)
    us, _ = timed(lambda: ops.int8_matmul(qx, qw, ones, ones, onesn, onesn,
                                          interpret=True), reps=2)
    rows.append({"name": "kernels/int8_matmul_256", "us_per_call": us,
                 "derived": f"tpu_int_macs={2 * m * n * k}"})
    rows.extend(_stamp_linear_rows(rng))
    rows.extend(fused_site_rows())
    rows.extend(moe_site_rows())
    return rows


def _stamp_linear_rows(rng) -> list[dict]:
    """Fused vs reference STaMP linear (prefill hot path).

    Derived HBM traffic per linear for a (s, din) activation and (din, dout)
    weight, f32 accounting:

    * reference — four activation round trips: transform out+in, fake-quant
      out+in, matmul out+in, inverse write, plus the bf16 weight
      re-materialized from int codes every call;
    * fused — exactly one: read X once, write Y once, stream the int8 weight.
    """
    import dataclasses

    from repro.core.quant import rtn_quantize_weight
    from repro.core.stamp import StampConfig, prepare_linear, stamp_linear

    s, din, dout = 1024, 256, 256
    x = jnp.asarray(rng.normal(size=(1, s, din)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(din, dout)).astype(np.float32) * 0.05)
    cfg_ref = StampConfig(num_hi_tokens=64)
    cfg_fused = dataclasses.replace(cfg_ref, execution="fused")
    # both rows deploy the same int8 weight codes: the reference path
    # dequantizes them to a dense weight every call, the fused path streams
    # them into the kernel directly
    wq = rtn_quantize_weight(w, bits=8, axis=0)
    prep = prepare_linear(w_quant=wq)

    us_ref, _ = timed(
        lambda: stamp_linear(x, w, None, cfg_ref, w_quant=wq), reps=2)
    us_fused, _ = timed(
        lambda: stamp_linear(x, None, None, cfg_fused, prepared=prep), reps=2)

    ref_bytes, fused_bytes = stamp_site_bytes(s, din, dout)
    return [
        {"name": "kernels/stamp_linear_reference_1k", "us_per_call": us_ref,
         "derived": f"tpu_hbm_bytes={ref_bytes},act_roundtrips=4"},
        {"name": "kernels/stamp_linear_fused_1k", "us_per_call": us_fused,
         "derived": f"tpu_hbm_bytes={fused_bytes},act_roundtrips=1"},
    ]


def stamp_site_bytes(s: int, din: int, dout: int,
                     dual: bool = False) -> tuple[int, int]:
    """Derived per-call HBM traffic of one STaMP linear site, f32 activation
    accounting (the reference path materializes f32 intermediates).

    Reference (per linear): transform write+read, fake-quant write+read,
    matmul out write + inverse read, inverse write, original X read, int8
    weight codes read, and the bf16 weight re-materialized from the codes
    (dequant write + matmul read).  A gate/up ``dual`` site shares one
    transform+quant round trip but doubles everything per-projection and
    adds the silu·mul combine (g and u re-read, product written).

    Fused: read X once, write the output once, stream the int8 codes —
    for the dual site both weight sets stream but X is still read once and
    only the silu·mul product is written.
    """
    act, out = s * din * 4, s * dout * 4
    wbytes = din * dout                  # int8 codes read
    wremat = 2 * din * dout * 2          # bf16 dequant write + matmul read
    shared = (2 * act                # L·X written + read back
              + 2 * act              # Q(T) written + read back
              + act)                 # original X read
    per_proj = (2 * out              # matmul out written + read by inverse
                + out                # inverse write
                + wbytes + wremat)
    if not dual:
        return shared + per_proj, act + out + wbytes
    # reference gate/up: hq read by the second matmul too, then the
    # silu·mul combine reads both projections and writes the product
    ref = shared + 2 * per_proj + act + 2 * out + out
    fused = act + out + 2 * wbytes
    return ref, fused


@functools.lru_cache(maxsize=1)
def fused_site_rows() -> list[dict]:
    """Fused-vs-reference rows for EVERY model site wired through the fused
    integer kernels (`repro.models.lm.FUSED_SITES` + the merged QKV):
    attention QKV / out-proj, the MLP gate+up pair and down projection, and
    the Mamba in/out projections.  Cached so `run.py` (which imports this
    from both kernels_bench and table4_sites) measures once."""
    import dataclasses

    from repro.core.stamp import (StampConfig, prepare_linear, stamp_linear,
                                  stamp_dual_linear)

    rng = np.random.default_rng(7)
    s, d = 256, 128
    nh, hd = 4, 32                       # out-proj head split (nh·hd = d)
    di = 2 * d                           # mamba inner dim
    cfg_ref = StampConfig(num_hi_tokens=64)
    cfg_fused = dataclasses.replace(cfg_ref, execution="fused")

    def acts(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32))

    def weight(k, n):
        return jnp.asarray(rng.normal(size=(k, n)).astype(np.float32) * .05)

    sites = {
        # name -> (din, dout, head-split input?, dual?)
        "attn.qkv": (d, 2 * d, False, False),      # merged q + 2·kv widths
        "attn.out_proj": (d, d, True, False),
        "mlp.gate_up": (d, 2 * d, False, True),
        "mlp.down_proj": (2 * d, d, False, False),
        "mamba.in_proj": (d, 2 * di + 2 * 32 + 16, False, False),
        "mamba.out_proj": (di, d, False, False),
    }
    rows = []
    for name, (din, dout, split, dual) in sites.items():
        x = acts(1, s, nh, din // nh) if split else acts(1, s, din)
        if dual:
            wg, wu = weight(din, dout), weight(din, dout)
            pg, pu = prepare_linear(wg), prepare_linear(wu)
            us_ref, _ = timed(lambda: stamp_dual_linear(
                x, pg.dequant(jnp.float32), pu.dequant(jnp.float32),
                cfg_ref), reps=2)
            us_fused, _ = timed(lambda: stamp_dual_linear(
                x, None, None, cfg_fused, prepared_gate=pg, prepared_up=pu),
                reps=2)
        else:
            w = weight(din, dout)
            prep = prepare_linear(w)
            us_ref, _ = timed(lambda: stamp_linear(
                x, prep.dequant(jnp.float32), None, cfg_ref,
                merge_heads=split), reps=2)
            us_fused, _ = timed(lambda: stamp_linear(
                x, None, None, cfg_fused, prepared=prep,
                merge_heads=split), reps=2)
        ref_b, fused_b = stamp_site_bytes(s, din, dout, dual=dual)
        rows.append({"name": f"kernels/site/{name}/reference",
                     "us_per_call": us_ref,
                     "derived": f"tpu_hbm_bytes={ref_b}"})
        rows.append({"name": f"kernels/site/{name}/fused",
                     "us_per_call": us_fused,
                     "derived": (f"tpu_hbm_bytes={fused_b},"
                                 f"hbm_savings={ref_b / fused_b:.2f}x")})
    return rows


def moe_site_bytes(s: int, d: int, f: int, e: int,
                   k: int) -> tuple[int, int]:
    """Derived HBM traffic of the dropless MoE expert site over ``s``
    tokens, every expert held, f32 activation accounting (same convention
    as `stamp_site_bytes`).

    Reference (`moe_ffn_dropless`, the dense loop): every expert runs on
    every token — gate and up each re-materialize a bf16 expert weight
    from the int8 codes (dequant write + matmul read), the (s,E,f)
    gate/up/silu·mul intermediates all round-trip, down re-materializes
    its weight, the (s,E,d) expert outputs are written and re-read by the
    gate-weighted sum.

    Fused (`moe_ffn_dropless_fused`): read the activation once (token
    quantize), move the ``s·k`` chosen rows' int8 codes into their
    expert groups (write + kernel read), stream the int8 expert codes,
    write the rows' outputs once, combine.  The (rows, f) intermediates
    never leave VMEM.
    """
    rows = s * k
    act = s * d * 4
    hid = s * e * f * 4                  # f32 (s, E, f) intermediate
    outs = s * e * d * 4                 # f32 (s, E, d) expert outputs
    w_gu = e * d * f                     # int8 codes, gate or up
    w_dn = e * f * d
    remat = lambda codes: codes + 2 * codes * 2   # read + bf16 write/read
    ref = (act                           # x read
           + 2 * remat(w_gu)            # gate/up weight re-materialized
           + 2 * hid                    # g, u written
           + 3 * hid                    # silu·mul: g, u read, h written
           + hid + remat(w_dn)         # down: h read, weight re-materialized
           + outs                       # expert outputs written
           + outs + act)                # weighted sum: read, y written
    fused = (act                         # activation read once
             + 2 * rows * d             # int8 row codes written + read
             + 2 * w_gu + w_dn          # int8 expert codes streamed
             + rows * d * 4             # row outputs written
             + rows * d * 4 + act)      # combine: outputs read, y written
    return ref, fused


@functools.lru_cache(maxsize=1)
def moe_site_rows() -> list[dict]:
    """Fused grouped-kernel vs dense-loop MoE expert site (dropless, every
    expert held).  Both rows deploy the same prepared int8 expert codes;
    the dense loop dequantizes them per call."""
    from repro.core.stamp import prepare_linear
    from repro.models import layers as L

    rng = np.random.default_rng(11)
    s, d, f, e, k = 256, 128, 128, 8, 2
    x = jnp.asarray(rng.normal(size=(s, d)).astype(np.float32))
    valid = jnp.ones((s,), bool)
    gate_w = jnp.asarray(rng.normal(size=(d, e)).astype(np.float32))

    def expert(k_dim, n_dim, seed):
        r = np.random.default_rng(seed)
        w = jnp.asarray(r.normal(size=(e, k_dim, n_dim)
                                 ).astype(np.float32) * 0.05)
        p = prepare_linear(w, bits=8)
        return {"iq": p.qw, "isw": p.sw, "izw": p.zw}

    prep = {"g": expert(d, f, 1), "u": expert(d, f, 2), "d": expert(f, d, 3)}
    deq = {n: (w["iq"].astype(jnp.float32) - w["izw"]) * w["isw"]
           for n, w in prep.items()}

    us_ref, _ = timed(lambda: L.moe_ffn_dropless(
        x, gate_w, deq["g"], deq["u"], deq["d"], k, valid, offset=0),
        reps=2)
    us_fused, _ = timed(lambda: L.moe_ffn_dropless_fused(
        x, gate_w, prep["g"], prep["u"], prep["d"], k, valid, offset=0),
        reps=2)
    ref_b, fused_b = moe_site_bytes(s, d, f, e, k)
    return [
        {"name": "kernels/site/moe.experts/reference", "us_per_call": us_ref,
         "derived": f"tpu_hbm_bytes={ref_b}"},
        {"name": "kernels/site/moe.experts/fused", "us_per_call": us_fused,
         "derived": (f"tpu_hbm_bytes={fused_b},"
                     f"hbm_savings={ref_b / fused_b:.2f}x")},
    ]


def main() -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="also write rows to this JSON file "
                         "(BENCH_kernels.json is committed by CI)")
    args = ap.parse_args()
    rows = run()
    for r in rows:
        print(r)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"suite": "kernels", "rows": rows}, f, indent=1)
            f.write("\n")
        print(f"wrote {len(rows)} rows to {args.out}")


if __name__ == "__main__":
    main()
