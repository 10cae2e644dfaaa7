"""Serving benchmark: paged continuous batching (unified ragged step vs the
two-call step pair) vs bucketed lockstep on one workload, emitting
``BENCH_serving.json``.

The paged engine is measured twice: ``step_mode="unified"`` (one ragged
device program per step — prefill chunks + decode batch together) and
``step_mode="two_call"`` (the PR-3 prefill-then-decode jit pair).  The
``device_dispatches_per_step`` column makes the 2 → 1 program win visible
in the committed trajectory (unified is exactly 1.0 by construction —
asserted), ``recompiles`` pins the bounded shape-bucketing, and the two
modes must emit identical tokens (asserted).

The committed rows come from ``JAX_PLATFORMS=cpu`` runs: their wall
clocks are interpret-mode numbers (relative, not TPU latencies); the HBM
bytes/token rows are derived analytically from the two cache layouts and
the *observed* request lengths:

* contiguous bf16 — every decode step streams each slot's full ``max_seq``
  reservation: ``layers · 2(K,V) · max_seq · kv · hd · 2B``;
* paged int4 — a step reads only the pages a request has mapped: int8 sink
  pages for the first ``num_hi`` tokens, int4-packed pages (+ f16 scale/zp)
  for the rest, rounded up to the page size.

The paged/contiguous ratio is the serving-time claim of the mixed-precision
cache (§B.2): ~8× fewer bytes per decoded token at 256-token reservations,
growing with ``max_seq`` since the contiguous cost is length-independent.

The ``hybrid_jamba`` row serves the reduced Jamba config (Mamba +
attention + MoE) through the same engines: paged K/V for the attention
layers plus the slot-dense SSM state pool, with a forced preemption so the
swap traffic (pages + per-slot conv/SSM state) and
``ssm_state_bytes_per_slot`` land in the trajectory; token parity against
the bucketed oracle and one-dispatch-per-unified-step are asserted.

The ``degraded`` row runs the same smoke model deliberately overloaded
(tiny page pool, bounded waiting queue, per-request deadlines on a virtual
clock) and reports goodput, shed rate, and deadline misses — the
graceful-degradation contract from the robustness PR.

The ``prefix_share`` row serves a seeded prefix-heavy mix (75% of
requests share one 96-token system prefix) twice — prefix caching on and
off — and reports the tokens/s speedup, the TTFT drop, and the peak
page-pool footprint of each pass.  Tokens must be bit-identical between
the two passes (the cache changes where prefill *starts*, never what any
chunk computes) and the allocator must be leak-free at exit; both are
asserted, alongside the deterministic signal (fewer prefill chunks, hit
rate) that makes the row meaningful even where wall clocks are noisy.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python benchmarks/serving_bench.py \
        --smoke --out BENCH_serving.json
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import hist_percentiles
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving import kvcache as KV
from repro.serving.engine import (BucketedEngine, EngineConfig,
                                  PagedEngineConfig, PagedServingEngine)


def drive_workload(engine, prompts, max_new: int) -> tuple:
    """One measured engine pass: an untimed warmup over the same request
    mix first (compiles every shape variant — prefill buckets / unified
    n_pf buckets / decode — and is then reset via ``reset_stats`` so the
    timed pass starts from zeroed registries and an empty event ring,
    except the cumulative ``recompiles``), then the timed pass.
    Percentiles come from the engines' own latency histograms — both
    engine classes share the registry surface, so the old hasattr guard
    (which silently skipped the reset on one of them) is gone.  Returns
    ``(done, row)`` — shared by the dense and hybrid workloads so the
    warmup/reset protocol cannot drift between rows of the same JSON."""
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    engine.run()
    engine.reset_stats(clear_events=True)
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    row = {
        "requests": len(done),
        "decode_tokens": toks,
        "wall_s": round(dt, 3),
        "tokens_per_s": round(toks / dt, 2),
        "ttft_s": hist_percentiles(engine.metrics.histogram("ttft_s")),
        "latency_s": hist_percentiles(engine.metrics.histogram("latency_s")),
    }
    return done, row


def _cache_bytes_per_token(cfg: ModelConfig, kv: KV.KVCacheConfig,
                           max_seq: int, block_size: int,
                           lengths: list[int], paged: bool) -> float:
    """Mean HBM bytes the decode attention reads per generated token."""
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    layers = cfg.num_layers

    def per_head_bytes(tokens_hi: float, tokens_lo: float,
                       quantized: bool) -> float:
        """Bytes read for one of K or V, one kv head, given token counts."""
        if not quantized:
            return (tokens_hi + tokens_lo) * hd * 2.0        # bf16 codes
        code = tokens_hi * hd * 1.0 + tokens_lo * hd * 0.5   # int8 / nibbles
        meta = (tokens_hi + tokens_lo) * 2 * 2.0             # f16 scale+zp
        return code + meta

    if not paged:
        # contiguous: the full reservation streams every step regardless of
        # how many tokens a request actually holds
        num_hi = min(kv.num_hi, max_seq) if kv.quantized else 0
        per_head = per_head_bytes(num_hi, max_seq - num_hi, kv.quantized)
        return layers * 2 * per_head * kvh
    # paged: only the pages a request has mapped, rounded up to page size
    total = 0.0
    for ln in lengths:
        num_hi = min(kv.num_hi, ln) if kv.quantized else 0
        hi_pages = -(-num_hi // block_size) if num_hi else 0
        lo_tokens = ln - num_hi
        lo_pages = -(-lo_tokens // block_size) if lo_tokens > 0 else 0
        per_head = per_head_bytes(hi_pages * block_size,
                                  lo_pages * block_size, kv.quantized)
        total += layers * 2 * per_head * kvh
    return total / max(len(lengths), 1)


def run(smoke: bool = True, seed: int = 0, trace_out: str = None,
        metrics_out: str = None) -> dict:
    if smoke:
        cfg = ModelConfig(name="bench-smoke", family="dense", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                          vocab_size=128)
        n_req, max_seq, bucket = 6, 96, 64
        prompt_lens = (20, 33, 47, 12, 28, 40)
        max_new = 8
    else:
        cfg = ModelConfig(name="bench", family="dense", num_layers=4,
                          d_model=256, num_heads=8, num_kv_heads=4,
                          d_ff=512, vocab_size=512)
        n_req, max_seq, bucket = 16, 256, 128
        prompt_lens = tuple(24 + (i * 37) % 100 for i in range(n_req))
        max_new = 16

    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, l) for l in prompt_lens]

    def workload(engine):
        done, row = drive_workload(engine, prompts, max_new)
        return row, done

    results = {"config": {"model": cfg.name, "requests": n_req,
                          "max_new": max_new, "max_seq": max_seq,
                          "prompt_lens": list(map(int, prompt_lens)),
                          # wall_s / tokens_per_s are single-shot CPU
                          # interpret-mode numbers: comparable between rows
                          # of ONE run, not across machines or commits —
                          # the deterministic columns (dispatches/step,
                          # recompiles, HBM bytes, token parity) are the
                          # trajectory signal
                          "wall_clock_comparable_within_run_only": True}}

    # contiguous bf16 cache through the bucketed engine (the baseline the
    # acceptance ratio is defined against)
    serve_bf16 = lm.ServeConfig(stamp=None,
                                kv=KV.KVCacheConfig(quantized=False))
    eng = BucketedEngine(params, cfg, serve_bf16,
                         EngineConfig(max_batch=8, bucket=bucket,
                                      max_seq=max_seq))
    row, done = workload(eng)
    final_lens = [len(p) + len(r.out_tokens)
                  for p, r in zip(prompts, sorted(done, key=lambda r: r.uid))]
    row["hbm_bytes_per_token"] = int(_cache_bytes_per_token(
        cfg, serve_bf16.kv, max_seq, 16, final_lens, paged=False))
    results["bucketed_bf16"] = row

    # paged int4 (64@8b sink) through the continuous-batching engine —
    # once per step mode, so the unified ragged step's 2 → 1
    # dispatches-per-step win (and its token parity with the two-call
    # pair) lands in the committed trajectory
    kv_q = KV.KVCacheConfig(quantized=True, num_hi=16 if smoke else 64)
    serve_q = lm.ServeConfig(stamp=None, kv=kv_q)
    block = 16
    paged_tokens = {}
    for mode, key in (("unified", "paged_int4"),
                      ("two_call", "paged_int4_two_call")):
        eng = PagedServingEngine(params, cfg, serve_q,
                                 PagedEngineConfig(max_slots=8,
                                                   prefill_chunk=bucket,
                                                   max_seq=max_seq,
                                                   block_size=block,
                                                   step_mode=mode))
        row, done_p = workload(eng)
        paged_tokens[mode] = {r.uid: r.out_tokens for r in done_p}
        row["preemptions"] = eng.stats["preemptions"]
        row["scheduler_steps"] = eng.stats["steps"]
        row["device_dispatches_per_step"] = round(
            eng.stats["device_dispatches"] / max(eng.stats["steps"], 1), 3)
        row["recompiles"] = eng.stats["recompiles"] if mode == "unified" \
            else None
        row["hbm_bytes_per_token"] = int(_cache_bytes_per_token(
            cfg, kv_q, max_seq, block, final_lens, paged=True))
        results[key] = row
        if mode == "unified":
            # CI artifacts from the timed unified pass (the headline row):
            # the Perfetto-loadable span timeline and the full registry
            # snapshot the schema check guards
            if trace_out:
                from repro.obs.trace import export_chrome_trace
                with open(trace_out, "w") as f:
                    json.dump(export_chrome_trace(
                        eng.events, engine="paged_unified"), f)
            if metrics_out:
                with open(metrics_out, "w") as f:
                    f.write(eng.metrics.to_json())
    assert results["paged_int4"]["device_dispatches_per_step"] == 1.0, \
        "unified step must dispatch exactly one device program per step"
    assert results["paged_int4_two_call"]["device_dispatches_per_step"] > \
        1.0, "two-call baseline should exceed one dispatch per step"
    # recorded, not asserted: single-shot wall clocks on a shared CI
    # runner are too noisy for a hard gate — the trajectory JSON carries
    # the ratio so a real regression shows up in history (the dispatch
    # and token-parity asserts above are the deterministic guards)
    results["unified_vs_two_call_tokens_ratio"] = round(
        results["paged_int4"]["tokens_per_s"] /
        max(results["paged_int4_two_call"]["tokens_per_s"], 1e-9), 3)
    for uid, toks in paged_tokens["two_call"].items():
        np.testing.assert_array_equal(
            toks, paged_tokens["unified"][uid],
            err_msg=f"unified/two_call token divergence uid={uid}")

    # same quantized cache through the bucketed engine: isolates the
    # continuous-batching scheduling win from the layout win
    eng = BucketedEngine(params, cfg, serve_q,
                         EngineConfig(max_batch=8, bucket=bucket,
                                      max_seq=max_seq))
    row, _ = workload(eng)
    row["hbm_bytes_per_token"] = int(_cache_bytes_per_token(
        cfg, kv_q, max_seq, 16, final_lens, paged=False))
    results["bucketed_int4"] = row

    ratio = results["bucketed_bf16"]["hbm_bytes_per_token"] / \
        max(results["paged_int4"]["hbm_bytes_per_token"], 1)
    results["paged_vs_bf16_hbm_ratio"] = round(ratio, 2)
    results["hybrid_jamba"] = run_hybrid(seed)
    results["moe_arctic"] = run_moe(seed)
    results["degraded"] = run_degraded(seed)
    results["prefix_share"] = run_prefix_share(seed)
    return results


def run_moe(seed: int = 0) -> dict:
    """Expert-scale row: the reduced Arctic config (8 experts, top-2,
    dense residual) served fused end to end — every STaMP site including
    the MoE experts runs the integer kernels (dropless, ragged dispatch),
    so ``reference_fallback_sites`` must be 0 and the unified ragged step
    still dispatches exactly ONE device program per step (both asserted).
    Router health comes from the engine's own registry (the ``moe_router``
    pseudo-site the router records inside the step program): per-expert
    load, the occupancy of the expert rows computed, and the drop rate
    (0: nothing is dropped)."""
    from repro.configs import get_reduced
    from repro.core.stamp import StampConfig
    cfg = get_reduced("arctic-480b")
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompt_lens = (20, 33, 12)
    max_new = 8
    prompts = [rng.integers(0, cfg.vocab_size, l) for l in prompt_lens]
    serve = lm.ServeConfig(
        stamp=StampConfig(num_hi_tokens=8, execution="fused"),
        kv=KV.KVCacheConfig(quantized=True, num_hi=16),
        quant_telemetry=True)
    eng = PagedServingEngine(
        params, cfg, serve,
        PagedEngineConfig(max_slots=4, prefill_chunk=64, max_seq=96,
                          block_size=16, step_mode="unified"))
    assert eng.stats["reference_fallback_sites"] == 0, \
        "expert config must reach full fused coverage (grouped MoE)"
    _, row = drive_workload(eng, prompts, max_new)
    st = eng.stats
    row["model"] = cfg.name
    row["num_experts"] = cfg.num_experts
    row["experts_per_token"] = cfg.experts_per_token
    row["prompt_lens"] = list(map(int, prompt_lens))
    row["max_new"] = max_new
    row["reference_fallback_sites"] = st["reference_fallback_sites"]
    row["device_dispatches_per_step"] = round(
        st["device_dispatches"] / max(st["steps"], 1), 3)
    assert row["device_dispatches_per_step"] == 1.0, \
        "fused MoE unified step must dispatch exactly one program per step"
    m = eng.metrics
    row["router"] = {
        "expert_tokens_last_step": [
            m.gauge("moe_expert_tokens", labels={"expert": str(i)}).value
            for i in range(cfg.num_experts)],
        "dropped_tokens_total": m.counter("moe_dropped_tokens").value,
        "capacity_occupancy": round(
            m.gauge("moe_capacity_occupancy").value, 4),
        "drop_rate": round(m.gauge("moe_drop_rate").value, 4),
    }
    return row


def run_degraded(seed: int = 0) -> dict:
    """Graceful-degradation row: the same smoke model on a deliberately
    under-provisioned engine — tiny page pool (watermark preemption
    active), bounded waiting queue, and per-request deadlines driven by an
    injected virtual clock (2 virtual ms per clock read, so the row is
    machine-independent and deterministic).  Reports **goodput** (tokens
    of *finished* requests per real second), the shed rate, and the
    deadline-miss count alongside raw tokens/s — the load-shedding
    contract: under overload the engine degrades by plan (reject / shed /
    fail-at-deadline), never by exception, and releases every page/slot
    (asserted)."""
    cfg = ModelConfig(name="bench-degraded", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=128)
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompt_lens = tuple(12 + (i * 17) % 40 for i in range(10))
    prompts = [rng.integers(0, cfg.vocab_size, l) for l in prompt_lens]
    max_new = 8
    tick = 0.02                       # virtual seconds per clock read
    deadline_s, ttft_deadline_s = 0.6, 0.35
    max_waiting, shed_policy, watermark = 5, "reject_newest", 0.75
    clk = {"t": 0.0}

    def clock() -> float:
        clk["t"] += tick
        return clk["t"]

    serve = lm.ServeConfig(stamp=None,
                           kv=KV.KVCacheConfig(quantized=True, num_hi=16))
    eng = PagedServingEngine(
        params, cfg, serve,
        PagedEngineConfig(max_slots=3, prefill_chunk=32, max_seq=96,
                          block_size=16, num_lo_blocks=5,
                          max_waiting=max_waiting, shed_policy=shed_policy,
                          preempt_watermark=watermark),
        clock=clock)
    uids = [eng.submit(p, max_new_tokens=max_new, deadline_s=deadline_s,
                       ttft_deadline_s=ttft_deadline_s) for p in prompts]
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    assert sorted(r.uid for r in done) == sorted(uids), \
        "degraded run lost a request"
    assert eng.sched.quiescent(), "degraded run leaked pages/slots"
    st = eng.stats
    assert st["finished"] > 0, "overload must not starve every request"
    good_tokens = sum(len(r.out_tokens) for r in done
                      if r.status == "finished")
    all_tokens = sum(len(r.out_tokens) for r in done)
    return {
        "model": cfg.name, "requests": len(prompts),
        "virtual_s_per_clock_read": tick,
        "virtual_wall_s": round(clk["t"], 3),
        "deadline_s": deadline_s, "ttft_deadline_s": ttft_deadline_s,
        "max_waiting": max_waiting, "shed_policy": shed_policy,
        "preempt_watermark": watermark,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(all_tokens / wall, 2),
        "goodput_tokens_per_s": round(good_tokens / wall, 2),
        "finished": st["finished"], "failed": st["failed"],
        "shed": st["shed"], "rejected": st["rejected"],
        "shed_rate": round(st["shed"] / len(prompts), 3),
        "deadline_misses": st["deadline_misses"],
        "preemptions": st["preemptions"],
        "watchdog_trips": st["watchdog_trips"],
    }


def gen_prefix_workload(seed: int, vocab: int, n_req: int = 8,
                        shared_frac: float = 0.75, prefix_len: int = 96,
                        tail: tuple = (8, 20),
                        unique: tuple = (40, 72)) -> tuple:
    """Seeded prefix-heavy request mix: ``shared_frac`` of the requests are
    the same ``prefix_len``-token system prefix plus a short unique tail
    (``tail`` token range); the rest are fully unique prompts drawn from the
    ``unique`` length range.  Which positions carry the shared prefix is a
    Bresenham spread (``floor((i+1)·f) > floor(i·f)``), so the mix is evenly
    interleaved and a pure function of ``(seed, n_req, shared_frac)`` — the
    arrival *order* is the list order, identical for every engine under
    test.  Returns ``(prompts, shared_flags)``."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, prefix_len)
    prompts, flags = [], []
    for i in range(n_req):
        hit = int((i + 1) * shared_frac) > int(i * shared_frac)
        if hit:
            t = rng.integers(0, vocab,
                             int(rng.integers(tail[0], tail[1] + 1)))
            prompts.append(np.concatenate([prefix, t]))
        else:
            prompts.append(rng.integers(
                0, vocab, int(rng.integers(unique[0], unique[1] + 1))))
        flags.append(hit)
    return prompts, flags


def run_prefix_share(seed: int = 0) -> dict:
    """Prefix-caching row: the same seeded prefix-heavy workload served
    with the hash-addressed prefix cache on and off.  The warmup pass
    populates the cache (and compiles every shape variant); the timed pass
    then admits every shared request at its first uncached token.  Tokens
    must be **bit-identical** between the two passes — the cache only moves
    the prefill start, chunk boundaries coincide by construction — and
    both allocators must be leak-free at exit (``quiescent`` +
    ``all_free``).  Deterministic guards (prefill chunks, hit count) back
    the wall-clock speedup, which is asserted at the acceptance floor."""
    cfg = ModelConfig(name="bench-prefix", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=128)
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    shared_frac, prefix_len, max_new = 0.75, 96, 6
    prompts, flags = gen_prefix_workload(seed, cfg.vocab_size,
                                         shared_frac=shared_frac,
                                         prefix_len=prefix_len)

    def drive(prefix_caching: bool) -> tuple:
        eng = PagedServingEngine(
            params, cfg,
            lm.ServeConfig(stamp=None,
                           kv=KV.KVCacheConfig(quantized=True, num_hi=16)),
            PagedEngineConfig(max_slots=4, prefill_chunk=32, max_seq=128,
                              block_size=16, prefix_caching=prefix_caching))
        for p in prompts:          # warmup: compiles AND registers prefixes
            eng.submit(p, max_new_tokens=max_new)
        eng.run()
        eng.reset_stats(clear_events=True)
        alloc = eng.sched.alloc
        alloc.peak_referenced = 0  # fresh peak for the timed pass
        uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        assert eng.sched.quiescent() and alloc.all_free(), \
            "prefix workload leaked pages/slots"
        by_uid = {r.uid: r.out_tokens for r in done}
        tokens = [by_uid[u] for u in uids]     # submission order
        return eng, tokens, dt

    eng_on, tok_on, dt_on = drive(True)
    eng_off, tok_off, dt_off = drive(False)
    for i, (a, b) in enumerate(zip(tok_on, tok_off)):
        np.testing.assert_array_equal(
            a, b, err_msg=f"prefix cache changed tokens (request {i}, "
                          f"shared={flags[i]})")
    st_on, st_off = eng_on.stats, eng_off.stats
    n_shared = sum(flags)
    assert st_on["prefix_cache_hits"] >= n_shared, \
        "warm cache must hit every shared-prefix request"
    assert st_off["prefix_cache_hits"] == 0, \
        "cache-off engine must never consult the prefix cache"
    assert st_on["prefill_chunks"] < st_off["prefill_chunks"], \
        "cached prefixes must shrink the prefill work"
    toks = sum(len(t) for t in tok_on)
    speedup = (toks / dt_on) / max(toks / dt_off, 1e-9)
    assert speedup >= 1.3, \
        f"prefix cache speedup {speedup:.2f}x below the 1.3x floor"
    ttft_on = hist_percentiles(eng_on.metrics.histogram("ttft_s"))
    ttft_off = hist_percentiles(eng_off.metrics.histogram("ttft_s"))
    assert ttft_on["p50"] < ttft_off["p50"], \
        "cached prefixes must cut time-to-first-token"
    peak_on = eng_on.sched.alloc.peak_referenced
    peak_off = eng_off.sched.alloc.peak_referenced
    assert peak_on <= peak_off, \
        "page sharing must not grow the peak pool footprint"
    return {
        "requests": len(prompts),
        "shared_prefix_fraction": shared_frac,
        "prefix_len": prefix_len,
        "max_new": max_new,
        "decode_tokens": toks,
        "tokens_per_s": round(toks / dt_on, 2),
        "tokens_per_s_cache_off": round(toks / dt_off, 2),
        "speedup": round(speedup, 3),
        "ttft_s": ttft_on,
        "ttft_s_cache_off": ttft_off,
        "prefill_chunks": st_on["prefill_chunks"],
        "prefill_chunks_cache_off": st_off["prefill_chunks"],
        "prefix_cache_hits": st_on["prefix_cache_hits"],
        "prefix_cache_hit_rate": round(st_on["prefix_cache_hit_rate"], 4),
        "prefix_tokens_reused": st_on["prefix_tokens_reused"],
        "cow_copies": st_on["cow_copies"],
        "peak_pages": peak_on,
        "peak_pages_cache_off": peak_off,
    }


def run_hybrid(seed: int = 0) -> dict:
    """Hybrid (Mamba + attention + MoE) workload on the reduced Jamba
    config: continuous batching over paged K/V *plus* the slot-dense SSM
    state pool.  The lo pool is sized to force a preemption, so the row
    also reports the swap traffic a hybrid eviction moves (pages + per-slot
    conv/SSM state) and `ssm_state_bytes_per_slot` — the fixed HBM a slot
    pins across every Mamba layer, the admission-time cost the scheduler
    accounts by its slot gate.  Tokens must be identical to the bucketed
    oracle (single-chunk prompts: chunk width == bucket width) and the
    unified mode must dispatch exactly ONE device program per step —
    both asserted."""
    from repro.configs import get_reduced
    cfg = get_reduced("jamba-1.5-large-398b")
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompt_lens = (20, 33, 12)
    max_new = 8
    prompts = [rng.integers(0, cfg.vocab_size, l) for l in prompt_lens]
    kv_q = KV.KVCacheConfig(quantized=True, num_hi=16)
    serve = lm.ServeConfig(stamp=None, kv=kv_q)

    def drive(engine):
        done, row = drive_workload(engine, prompts, max_new)
        return {r.uid: r.out_tokens for r in done}, row

    buck_tokens, buck_row = drive(BucketedEngine(
        params, cfg, serve, EngineConfig(max_batch=8, bucket=64,
                                         max_seq=96)))
    row = {"model": cfg.name, "requests": len(prompts),
           "prompt_lens": list(map(int, prompt_lens)), "max_new": max_new,
           "bucketed": buck_row}
    for mode in ("unified", "two_call"):
        eng = PagedServingEngine(
            params, cfg, serve,
            PagedEngineConfig(max_slots=3, prefill_chunk=64, max_seq=96,
                              block_size=16, num_lo_blocks=4,
                              step_mode=mode))
        tokens, mode_row = drive(eng)
        st = eng.stats
        mode_row["preemptions"] = st["preemptions"]
        mode_row["swap_bytes"] = st["swap_bytes"]
        mode_row["device_dispatches_per_step"] = round(
            st["device_dispatches"] / max(st["steps"], 1), 3)
        row[mode] = mode_row
        assert st["preemptions"] > 0, \
            f"hybrid {mode} workload did not exercise preemption"
        for uid in buck_tokens:
            np.testing.assert_array_equal(
                tokens[uid], buck_tokens[uid],
                err_msg=f"hybrid {mode} vs bucketed divergence uid={uid}")
    row["ssm_state_bytes_per_slot"] = eng.sched.cfg.state_bytes_per_slot
    assert row["unified"]["device_dispatches_per_step"] == 1.0, \
        "hybrid unified step must dispatch exactly one program per step"
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + short workload (CI)")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the timed unified-mode pass's event ring "
                         "as Chrome trace-event JSON (ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the unified-mode engine's metrics "
                         "registry snapshot as JSON")
    args = ap.parse_args()
    results = run(smoke=args.smoke, seed=args.seed,
                  trace_out=args.trace_out, metrics_out=args.metrics_out)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results, indent=2))
    assert results["paged_int4"]["hbm_bytes_per_token"] < \
        results["bucketed_bf16"]["hbm_bytes_per_token"], \
        "paged int4 must move fewer HBM bytes/token than contiguous bf16"


if __name__ == "__main__":
    main()
