"""Serving driver: random-init a model straight into its packed serving
form, calibrate STaMP through it, and serve batched requests through a
STaMP-quantized engine.  The set-up functions here are also what
``chip_smoke.py`` drives.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
        --requests 16 --prompt-len 96 --max-new 16 \
        [--engine paged|bucketed] [--no-stamp] [--execution fused] \
        [--no-prefix-cache] \
        [--deadline-s 2.0 --ttft-deadline-s 0.5 --max-waiting 32 \
         --shed-policy reject_newest --watermark 0.9 --numerics-guard \
         --chaos SEED]

``--engine bucketed`` is the lockstep slot-batching engine; ``--engine
paged`` (default) is the continuous-batching engine over the block-paged
mixed-precision cache — see `repro/serving/engine.py` for when to pick each.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import Optional

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.core.ptq import PTQReport, calibrate
from repro.data.pipeline import DataConfig, calibration_batches
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving.engine import (BucketedEngine, EngineConfig,
                                  PagedEngineConfig, PagedServingEngine)

REPO = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here; otherwise the cache lives at the fixed
    ``<repo>/.jax_cache``, so every run of this checkout finds the
    programs the last one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_model(cfg: ModelConfig, seed: int
                ) -> tuple[dict, lm.ServeConfig, PTQReport]:
    """Random-init ``cfg`` from ``seed`` straight into its served form
    (bf16, int4-packed linears, one period at a time) and run STaMP
    calibration through those packed weights."""
    weight_bits = 4                    # W4, the paper's served weights
    params = lm.init_params(jax.random.PRNGKey(seed), cfg,
                            weight_bits=weight_bits)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=128, global_batch=4,
                      seed=seed)
    serve, report = calibrate(params, calibration_batches(dcfg, num_batches=2),
                              cfg, weight_bits=weight_bits)
    print(f"[ptq] num_hi={report.num_hi} avg_bits={report.avg_bits:.3f} "
          f"toeplitz={report.toeplitz_fraction:.3f} "
          f"head_energy={report.energy_head_fraction:.3f}")
    return params, serve, report


def with_execution(serve: lm.ServeConfig, execution: str) -> lm.ServeConfig:
    """``serve`` with its STaMP linears on the ``reference`` (pure jnp) or
    ``fused`` (Pallas integer kernel) path."""
    if serve.stamp is None:
        return serve
    return dataclasses.replace(
        serve, stamp=dataclasses.replace(serve.stamp, execution=execution))


def paged_engine(params, cfg: ModelConfig, serve: lm.ServeConfig, *,
                 block_size: int = 16, fault=None, **ecfg
                 ) -> PagedServingEngine:
    """The continuous-batching engine over 8 decode slots; ``ecfg`` are
    further `PagedEngineConfig` fields.  A page holds one precision, so
    ``block_size`` becomes ``num_hi`` where it does not divide it."""
    num_hi = serve.kv.num_hi if serve.kv.quantized else 0
    if num_hi % block_size:
        block_size = num_hi
        print(f"[serve] block_size adjusted to {block_size} "
              f"(num_hi={num_hi})")
    return PagedServingEngine(
        params, cfg, serve,
        PagedEngineConfig(max_slots=8, block_size=block_size, **ecfg),
        fault=fault)


def print_eligibility(engine) -> None:
    """Per-site fused/reference matrix: which linears run integer kernels
    and, for every reference site, the structured reason why."""
    for site, cell in engine.eligibility.items():
        why = f" ({','.join(cell['reasons'])})" if cell["reasons"] else ""
        print(f"[serve:eligibility] {site:<12} {cell['status']:<9} "
              f"kernel={cell['kernel'] or '-'} "
              f"layers={cell['layers']}{why}")
    n_ref = engine.stats["reference_fallback_sites"]
    print(f"[serve:eligibility] reference_fallback_sites={n_ref}")
    if n_ref == 0 and "moe" in engine.eligibility:
        # the MoE expert einsums were the last structurally-ineligible
        # site — call out full coverage explicitly on expert configs
        print("[serve:eligibility] full fused coverage: every STaMP site "
              "incl. grouped MoE runs the integer kernels")


def print_paged_stats(engine: PagedServingEngine) -> None:
    st = engine.stats
    print(f"[serve:paged:{engine.ecfg.step_mode}] steps={st['steps']} "
          f"prefill_chunks={st['prefill_chunks']} "
          f"preemptions={st['preemptions']} "
          f"dispatches/step="
          f"{st['device_dispatches'] / max(st['steps'], 1):.2f} "
          f"recompiles={st['recompiles']} "
          f"prefix_hit_rate={st['prefix_cache_hit_rate']:.2f} "
          f"prefix_tokens_reused={st['prefix_tokens_reused']}")
    print(f"[serve:lifecycle] finished={st['finished']} "
          f"failed={st['failed']} cancelled={st['cancelled']} "
          f"rejected={st['rejected']} shed={st['shed']} "
          f"deadline_misses={st['deadline_misses']} "
          f"nan_quarantines={st['nan_quarantines']} "
          f"demotions={st['demotions']} "
          f"watchdog_trips={st['watchdog_trips']}")


def peak_device_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` of the first device, where its backend
    reports memory statistics (TPU does; CPU does not)."""
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--no-stamp", action="store_true")
    ap.add_argument("--engine", choices=("paged", "bucketed"),
                    default="paged",
                    help="paged = continuous batching over the block-paged "
                         "cache + slot-dense SSM state pool (dense, MoE, "
                         "hybrid and pure-SSM stacks); bucketed = lockstep "
                         "slot batching (required for enc-dec stacks)")
    ap.add_argument("--execution", choices=("reference", "fused"),
                    default="reference",
                    help="STaMP linear path: pure-jnp reference or the "
                         "fused Pallas integer kernel (interpret mode off "
                         "the TPU)")
    ap.add_argument("--fused-cache-attention", action="store_true",
                    help="decode attention through the Pallas packed-cache "
                         "kernel off the TPU too (interpret mode); the paged "
                         "engine takes it on a TPU anyway")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per cache page (paged engine)")
    ap.add_argument("--prefill-chunk", type=int, default=128,
                    help="prompt tokens per prefill chunk row (paged)")
    ap.add_argument("--step-mode", choices=("unified", "two_call"),
                    default="unified",
                    help="unified = ONE ragged device program per step "
                         "(prefill chunks + decode batch); two_call = the "
                         "old prefill-then-decode jit pair (parity/A-B)")
    ap.add_argument("--max-prefills", type=int, default=2,
                    help="prefill chunk rows per unified step")
    ap.add_argument("--prefix-cache", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="hash-addressed prefix page reuse: requests whose "
                         "prompt shares a cached prefix start prefill at "
                         "the first uncached token (paged engine; tokens "
                         "are bit-identical either way)")
    ap.add_argument("--seed", type=int, default=0)
    # -- robustness / admission control (paged engine) ------------------
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request total latency budget in seconds; "
                         "requests past it FAIL at plan time")
    ap.add_argument("--ttft-deadline-s", type=float, default=None,
                    help="per-request first-token budget in seconds")
    ap.add_argument("--max-waiting", type=int, default=None,
                    help="bounded waiting queue: beyond this depth the "
                         "shed policy decides who is turned away")
    ap.add_argument("--shed-policy", choices=("reject_newest",
                                              "shed_oldest"),
                    default="reject_newest")
    ap.add_argument("--watermark", type=float, default=1.0,
                    help="page-pool occupancy fraction that triggers early "
                         "preemption (1.0 = only on true exhaustion)")
    ap.add_argument("--numerics-guard", action="store_true",
                    help="check step outputs for NaN/Inf and quarantine "
                         "the offending request (fused STaMP engines also "
                         "demote to reference execution)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="inject seeded faults (page exhaustion, swap "
                         "corruption, NaN) via a FaultPlan — a smoke of "
                         "the degradation machinery, not a benchmark")
    # -- observability ---------------------------------------------------
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the engine metrics registry snapshot "
                         "(counters/gauges/histograms) as JSON on exit")
    ap.add_argument("--metrics-prom", default=None, metavar="PATH",
                    help="write the registry in Prometheus text "
                         "exposition format on exit")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the engine event ring as Chrome "
                         "trace-event JSON (open in ui.perfetto.dev)")
    ap.add_argument("--quant-telemetry", action="store_true",
                    help="collect per-STaMP-site quant-health stats "
                         "(clip rate, hi-token coverage, scale range) in "
                         "the same device program as each step")
    args = ap.parse_args()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.engine == "paged" and cfg.encoder_layers:
        # fail at the CLI boundary with the fix in hand, not five frames
        # deep in cache init: enc-dec cross-attention K/V is computed once
        # from the encoder output and held dense per request — not paged.
        ap.error(f"--engine paged does not support encoder-decoder stacks "
                 f"({cfg.name}: encoder_layers={cfg.encoder_layers}); "
                 f"run with --engine bucketed")
    print(f"[serve] compile cache: {enable_compile_cache()}")
    sparams, serve, _ = build_model(cfg, args.seed)
    if args.no_stamp:
        serve = lm.ServeConfig(stamp=None, kv=serve.kv,
                               weight_bits=serve.weight_bits)
    serve = with_execution(serve, args.execution)
    if args.fused_cache_attention:
        serve = dataclasses.replace(serve, fused_cache_attention=True)
    if args.numerics_guard:
        serve = dataclasses.replace(serve, numerics_guard=True)
    if args.quant_telemetry:
        serve = dataclasses.replace(serve, quant_telemetry=True)

    max_seq = 128 + args.max_new
    if args.engine == "paged":
        fault = None
        if args.chaos is not None:
            from repro.serving.faults import FaultPlan
            fault = FaultPlan(seed=args.chaos, exhaust_rate=0.2,
                              corrupt_rate=0.3, nan_rate=0.005)
        engine = paged_engine(
            sparams, cfg, serve, block_size=args.block_size, fault=fault,
            prefill_chunk=args.prefill_chunk, max_seq=max_seq,
            step_mode=args.step_mode, max_prefills=args.max_prefills,
            max_waiting=args.max_waiting, shed_policy=args.shed_policy,
            preempt_watermark=args.watermark,
            prefix_caching=args.prefix_cache)
    else:
        engine = BucketedEngine(sparams, cfg, serve,
                                EngineConfig(max_batch=8, bucket=128,
                                             max_seq=max_seq))
    print_eligibility(engine)
    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        engine.submit(rng.integers(0, cfg.vocab_size, args.prompt_len),
                      max_new_tokens=args.max_new,
                      deadline_s=args.deadline_s,
                      ttft_deadline_s=args.ttft_deadline_s)
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    total_new = sum(len(r.out_tokens) for r in done)
    ttfts = sorted(r.ttft_s for r in done)
    print(f"[serve:{args.engine}] {len(done)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new / dt:.1f} tok/s on "
          f"{jax.devices()[0].device_kind}, compiles included), "
          f"ttft p50={ttfts[len(ttfts) // 2]:.2f}s")
    print(f"[serve] peak_bytes_in_use={peak_device_bytes()}")
    if args.engine == "paged":
        print_paged_stats(engine)
    for r in done[:3]:
        print(f"  req {r.uid}: {r.out_tokens[:10]}")

    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            f.write(engine.metrics.to_json())
        print(f"[obs] metrics snapshot -> {args.metrics_json}")
    if args.metrics_prom:
        with open(args.metrics_prom, "w") as f:
            f.write(engine.metrics.to_prometheus())
        print(f"[obs] prometheus text -> {args.metrics_prom}")
    if args.trace_out:
        import json
        from repro.obs.trace import export_chrome_trace
        trace = export_chrome_trace(engine.events, engine=args.engine)
        with open(args.trace_out, "w") as f:
            json.dump(trace, f)
        print(f"[obs] {len(trace['traceEvents'])} trace events -> "
              f"{args.trace_out} (open in ui.perfetto.dev)")
    if args.quant_telemetry:
        snap = engine.metrics.snapshot()
        rates = {k: round(v, 4) for k, v in snap["gauges"].items()
                 if k.startswith("quant_clip_rate")}
        if rates:
            print(f"[obs] quant clip rates: {rates}")


if __name__ == "__main__":
    main()
