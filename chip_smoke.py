"""Smoke test of the serving main path on one TPU chip.

Builds Llama-3-8B at its published widths from ``--seed`` (random weights,
packed to int4 one period at a time), calibrates STaMP through the packed
weights, and serves 8 requests through `PagedServingEngine` on the fused
STaMP integer kernels with prefix caching on — the same set-up functions
``python -m repro.launch.serve`` runs.  Then it checks the result:

* every request finished, and no sampled logits row was non-finite (the
  engine's numerics guard is on, so a NaN/Inf row fails its request);
* the request served last reused the 128-token prefix it shares with an
  earlier one from the prefix cache;
* no STaMP site fell back to the reference path and the engine never
  demoted itself to reference execution;
* the compiled unified step calls Pallas kernels (``tpu_custom_call``);
* one ``stamp_quant_matmul`` and one ``stamp_decode_matmul`` at real width,
  on layer 0's served weights, match the `repro.kernels.ref` oracles within
  the relative error the interpret-mode kernel tests allow, on activations
  whose quantized codes are exact;
* on random activations the same ``stamp_quant_matmul`` stays within a
  looser bound that two faults the exact case cannot show — every row
  quantized at 8 bits, and a one-pass bf16 forward transform — exceed.

    python chip_smoke.py [--seed 0]

One process does everything and starts no other.  Without a TPU it exits
non-zero at once and prints no result line; any failed check exits
non-zero too.  The last line of a passing run is the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from repro.configs import get_config                           # noqa: E402
from repro.core import transforms as T                         # noqa: E402
from repro.kernels import ops as kops, ref                     # noqa: E402
from repro.launch.serve import (build_model, enable_compile_cache,  # noqa: E402
                                paged_engine, peak_device_bytes,
                                print_eligibility, print_paged_stats,
                                with_execution)

PROMPT_LENS = (64, 200, 300, 64, 200, 300, 64, 300)
SHARED = (2, 7)            # these two requests share their first 128 tokens
SHARED_PREFIX = 128
MAX_NEW = 16
PREFILL_CHUNK = 128
MAX_SEQ = 320              # longest prompt + MAX_NEW, rounded to pages
DECODE_ROWS = 8            # one token per decode slot
# relative error the interpret-mode kernel-vs-oracle tests allow
# (tests/test_stamp_fused.py, tests/test_paged_serving.py)
KERNEL_RTOL = 1e-5
# On random activations an f32 rounding difference between the kernel's
# matrix transform and the oracle's butterflies can move a value across a
# rounding tie; one 4-bit code off at (128, 4096) is a relative error of
# about 6.5e-4.  Every row at 8 bits is about 1.4e-1 off, a one-pass bf16
# forward transform about 2.5e-2; this bound lies between.
OFF_GRID_RTOL = 5e-3
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def build_engine(cfg, seed: int):
    """Model, PTQ and the fused, prefix-caching paged engine, with the
    numerics guard on so every sampled logits row is checked finite."""
    params, serve, _ = build_model(cfg, seed)
    serve = dataclasses.replace(with_execution(serve, "fused"),
                                numerics_guard=True)
    return paged_engine(params, cfg, serve, prefill_chunk=PREFILL_CHUNK,
                        max_seq=MAX_SEQ, prefix_caching=True)


def make_prompts(vocab: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in PROMPT_LENS]
    a, b = SHARED
    prompts[b][:SHARED_PREFIX] = prompts[a][:SHARED_PREFIX]
    return prompts


def serve_requests(engine, prompts) -> tuple[list, float]:
    """Serve every prompt but the second of the pair that shares a prefix,
    then that one, which finds the shared prefix in the cache (all 8 slots
    admit at once, before any prefix is registered); returns (done, wall
    seconds)."""
    late = SHARED[1]
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        if i != late:
            engine.submit(p, max_new_tokens=MAX_NEW)
    done = engine.run()
    engine.submit(prompts[late], max_new_tokens=MAX_NEW)
    done += engine.run()
    return done, time.perf_counter() - t0


def check_serving(engine, done, n_requests: int) -> list:
    """Failures of the request-level checks (empty list: all hold)."""
    st = engine.stats
    failures = []
    finished = [r for r in done if r.status == "finished"]
    if len(finished) != n_requests:
        failures.append(f"{len(finished)}/{n_requests} requests finished "
                        f"(statuses {sorted(r.status for r in done)})")
    if any(len(r.out_tokens) != MAX_NEW for r in finished):
        failures.append("a finished request holds fewer than "
                        f"{MAX_NEW} tokens")
    if st["nan_quarantines"]:
        failures.append(f"{st['nan_quarantines']} non-finite logits rows")
    if st["reference_fallback_sites"]:
        failures.append(f"reference_fallback_sites="
                        f"{st['reference_fallback_sites']}")
    if st["demotions"]:
        failures.append(f"demotions={st['demotions']}")
    if st["prefix_tokens_reused"] < SHARED_PREFIX:
        failures.append(f"prefix_tokens_reused={st['prefix_tokens_reused']}"
                        f" (the shared prefix is {SHARED_PREFIX} tokens)")
    return failures


def on_grid(rng, levels: np.ndarray, k: int) -> np.ndarray:
    """``(rows, k)`` f32 values lying exactly on each row's min-max
    quantizer grid: integer codes spanning ``[0, levels[row]]``, a random
    per-row scale and zero point.  A random activation leaves some values
    at rounding ties, which two float paths to the same quantizer may break
    differently; on the grid a code can only differ through a fault."""
    rows = len(levels)
    n = levels[:, None].astype(np.int64)
    codes = rng.integers(0, n + 1, size=(rows, k))
    codes[:, 0] = 0
    codes[:, 1] = n[:, 0]
    zp = rng.integers(1, n)
    scale = rng.uniform(0.01, 0.1, size=(rows, 1))
    return ((codes - zp) * scale).astype(np.float32)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def bf16_forward(x, kw: dict, s: int):
    """The activation whose exact sequence transform is ``L @ x`` taken in
    one bf16 pass (operands rounded to bf16, f32 accumulation: what
    ``Precision.DEFAULT`` does on the MXU), so the oracle fed this input
    gives what a kernel with that forward transform would."""
    m = T.sequence_matrix(kw["transform"], s, kw["levels"], kw["skip_first"])
    t = jnp.einsum("ts,bsk->btk", jnp.asarray(m, jnp.bfloat16),
                   x.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return T.inverse_sequence_transform(t, kw["transform"], axis=-2,
                                        levels=kw["levels"],
                                        skip_first=kw["skip_first"])


def off_grid_errors(rng, w: dict, kw: dict, s: int) -> dict:
    """Relative errors against the oracle on random f32 activations: the
    kernel's, and those of two faults read against the same bound."""
    x = jnp.asarray(rng.standard_normal((1, s, w["iq"].shape[0])),
                    jnp.float32)
    args = (w["iq"], w["isw"], w["izw"], None)

    def kernel(**over):
        return kops.stamp_quant_matmul(x, *args, out_dtype=jnp.float32,
                                       **{**kw, **over})

    with jax.default_matmul_precision("highest"):
        yr = ref.stamp_quant_matmul_ref(x, *args, **kw)
        yb = ref.stamp_quant_matmul_ref(bf16_forward(x, kw, s), *args, **kw)
    return {"kernel": _rel(kernel(), yr),
            "all rows at 8 bits": _rel(kernel(num_hi=s), yr),
            "bf16 forward transform": _rel(yb, yr)}


def check_off_grid(rng, w: dict, kw: dict, s: int) -> list:
    """The kernel within `OFF_GRID_RTOL` of the oracle on random
    activations, and each fault control outside it (else the bound could
    not tell that fault from a pass)."""
    errs = off_grid_errors(rng, w, kw, s)
    print(f"[smoke:kernel] stamp_quant_matmul off-grid rel_err_vs_oracle "
          + " ".join(f"{k.replace(' ', '_')}={v:.3e}"
                     for k, v in errs.items())
          + f" (limit {OFF_GRID_RTOL:g}; the kernel under it, the two "
            f"controls over it)")
    kernel = errs.pop("kernel")
    failures = []
    if not kernel < OFF_GRID_RTOL:
        failures.append(f"stamp_quant_matmul off-grid rel_err {kernel:.3e}")
    failures += [f"control '{k}' within the off-grid bound ({v:.3e})"
                 for k, v in errs.items() if v < OFF_GRID_RTOL]
    return failures


def check_kernels(engine, seed: int) -> list:
    """Layer 0's merged-QKV prefill kernel and down-proj decode kernel
    against their oracles, at the served widths and weights."""
    rng = np.random.default_rng(seed)
    stamp = engine.serve.stamp
    layer = jax.tree.map(lambda a: a[0], engine.params["period"][0])
    s = engine.ecfg.prefill_chunk
    kw = dict(transform=stamp.seq_transform,
              levels=stamp.resolved_levels(s),
              skip_first=stamp.skip_first_token,
              num_hi=stamp.num_hi_tokens, hi_bits=stamp.hi_bits,
              lo_bits=stamp.lo_bits)
    failures = []

    w = layer["wqkv"]
    levels = np.where(np.arange(s) < stamp.num_hi_tokens,
                      2 ** stamp.hi_bits - 1, 2 ** stamp.lo_bits - 1)
    t = jnp.asarray(on_grid(rng, levels, w["iq"].shape[0]))[None]
    # the activation whose sequence transform lands on the grid
    x = T.inverse_sequence_transform(t, kw["transform"], axis=-2,
                                     levels=kw["levels"],
                                     skip_first=kw["skip_first"])
    y = kops.stamp_quant_matmul(x, w["iq"], w["isw"], w["izw"], None,
                                out_dtype=jnp.float32, **kw)
    with jax.default_matmul_precision("highest"):
        yr = ref.stamp_quant_matmul_ref(x, w["iq"], w["isw"], w["izw"],
                                        None, **kw)
    rel = _rel(y, yr)
    print(f"[smoke:kernel] stamp_quant_matmul x{tuple(x.shape)} "
          f"w{tuple(w['iq'].shape)} {kw['transform']} "
          f"rel_err_vs_oracle={rel:.3e} (limit {KERNEL_RTOL:g})")
    if not rel < KERNEL_RTOL:
        failures.append(f"stamp_quant_matmul rel_err {rel:.3e}")
    failures += check_off_grid(rng, w, kw, s)

    w = layer["wo_mlp"]
    x = jnp.asarray(on_grid(rng, np.full(DECODE_ROWS, 255),
                            w["iq"].shape[0]))
    y = kops.stamp_decode_matmul(x, w["iq"], w["isw"], w["izw"], None,
                                 out_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        yr = ref.stamp_decode_matmul_ref(x, w["iq"], w["isw"], w["izw"])
    rel = _rel(y, yr)
    print(f"[smoke:kernel] stamp_decode_matmul x{tuple(x.shape)} "
          f"w{tuple(w['iq'].shape)} rel_err_vs_oracle={rel:.3e} "
          f"(limit {KERNEL_RTOL:g})")
    if not rel < KERNEL_RTOL:
        failures.append(f"stamp_decode_matmul rel_err {rel:.3e}")
    return failures


def result_line(devices) -> str:
    """The closing JSON line; refuses to describe anything but a TPU."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"not a TPU: {dev.platform!r}")
    return json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[smoke] no TPU: JAX found {devices[0].platform!r}; this "
              f"smoke test runs only on the chip", file=sys.stderr)
        return 1

    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == BACKEND_COMPILE:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    print(f"[smoke] device {devices[0].device_kind} x{len(devices)}")
    print(f"[smoke] compile cache: {enable_compile_cache()}")
    cfg = get_config("llama3-8b")
    print(f"[smoke] model {cfg.name}: layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} (published widths)")

    t0 = time.perf_counter()
    engine = build_engine(cfg, args.seed)
    setup_s = time.perf_counter() - t0
    setup_compile_s = compile_s[0]
    print(f"[smoke] execution={engine.serve.stamp.execution} "
          f"prefix_caching={engine.ecfg.prefix_caching}")
    print_eligibility(engine)

    prompts = make_prompts(cfg.vocab_size, args.seed)
    done, wall_s = serve_requests(engine, prompts)
    serve_compile_s = compile_s[0] - setup_compile_s
    print(f"[smoke] set-up {setup_s:.1f}s (compile {setup_compile_s:.1f}s); "
          f"serving {wall_s:.1f}s wall = compile {serve_compile_s:.1f}s "
          f"+ serving {wall_s - serve_compile_s:.1f}s; "
          f"compile total {compile_s[0]:.1f}s")
    print_paged_stats(engine)
    failures = check_serving(engine, done, len(prompts))

    peak = peak_device_bytes()
    limit = devices[0].memory_stats()["bytes_limit"]
    print(f"[smoke] peak_bytes_in_use={peak} bytes_limit={limit}")
    if not peak < limit:
        failures.append(f"peak HBM {peak} B not under {limit} B")

    prog = engine.step_program()
    print(f"[smoke] unified step: {prog.memory_analysis()}")
    if "tpu_custom_call" not in prog.as_text():
        failures.append("the unified step calls no Pallas kernel")

    failures += check_kernels(engine, args.seed)
    for f in failures:
        print(f"[smoke] FAIL {f}", file=sys.stderr)
    if failures:
        return 1
    print(result_line(devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
