"""Unified ragged-step tests: bit-identical token parity between the
unified engine and the PR-3 two-call step pair on a mixed workload
(staggered admissions, chunked prompts, preemption + resume mid-prefill),
ragged-kernel-vs-oracle parity at odd chunk lengths and ``num_hi >= seq``,
the jit-recompile guard (fixed compile count per engine run), the
segment-aware STaMP transform application, and the scheduler determinism /
transform-window satellites."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.core.stamp import (StampConfig, fold_segments, stamp_fake_quant,
                              stamp_linear, unfold_segments)
from repro.kernels.paged_attention import paged_ragged_attention
from repro.models import lm
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.serving import kvcache as KV
from repro.serving import paged_kvcache as PKV
from repro.serving.engine import (PagedEngineConfig, PagedServingEngine,
                                  _transform_window)
from repro.serving.paged_kvcache import PagedCacheConfig
from repro.serving.scheduler import (PREFILLING, SchedRequest, Scheduler,
                                     SchedulerConfig)

CFG = ModelConfig(name="unified-test", family="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=128)
QUANT = KV.KVCacheConfig(quantized=True, num_hi=16)
# more requests than slots (staggered admission waves), prompts spanning
# one to three 16-token chunks
PROMPT_LENS = (20, 40, 12, 33, 26)
MAX_NEW = (14, 10, 16, 8, 12)


@pytest.fixture(scope="module")
def params():
    return lm.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(2)
    return [rng.integers(0, CFG.vocab_size, l) for l in PROMPT_LENS]


def paged_cfg(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("max_seq", 96)
    kw.setdefault("block_size", 16)
    return PagedEngineConfig(**kw)


def run_engine(engine, prompts, max_new=MAX_NEW):
    for p, m in zip(prompts, max_new):
        engine.submit(p, m)
    done = engine.run()
    lm.set_fused_cache_attention(False)
    return {r.uid: r.out_tokens for r in done}


# ---------------------------------------------------------------------------
# unified vs two-call engine: bit-identical tokens
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def contended_runs(params, prompts):
    """Mixed workload under page pressure: chunked prompts, staggered
    admissions (5 requests, 3 slots) and a lo pool tight enough to preempt
    mid-prefill — one run per step mode, shared by the assertions below."""
    serve = lm.ServeConfig(stamp=None, kv=QUANT)
    out = {}
    for mode in ("two_call", "unified"):
        eng = PagedServingEngine(params, CFG, serve,
                                 paged_cfg(max_slots=5, num_lo_blocks=6,
                                           step_mode=mode))
        out[mode] = (run_engine(eng, prompts), eng)
    return out


class TestUnifiedEngineParity:
    def test_token_identical_under_preemption(self, contended_runs):
        """The unified ragged step must reproduce the two-call engine token
        for token across chunked prefill, join/leave and preempt+resume."""
        two, _ = contended_runs["two_call"]
        uni, eng = contended_runs["unified"]
        assert set(two) == set(uni)
        for uid in two:
            np.testing.assert_array_equal(two[uid], uni[uid],
                                          err_msg=f"uid={uid}")

    def test_workload_actually_contended(self, contended_runs):
        """The parity claim is vacuous unless the workload really exercised
        preemption, resumes and multi-chunk prefill."""
        _, eng = contended_runs["unified"]
        assert eng.stats["preemptions"] > 0
        kinds = [k for _, k, _ in eng.events]
        assert "resume" in kinds
        chunk_counts = {}
        for _, k, p in eng.events:
            if k == "prefill_chunk":
                chunk_counts[p[0]] = chunk_counts.get(p[0], 0) + 1
        assert max(chunk_counts.values()) >= 3   # 40-token prompt, chunk 16

    def test_one_dispatch_per_step(self, contended_runs):
        """The tentpole: every unified step is exactly one device program;
        the two-call pair exceeds one per step on mixed steps."""
        _, uni = contended_runs["unified"]
        _, two = contended_runs["two_call"]
        assert uni.stats["device_dispatches"] == uni.stats["steps"]
        assert two.stats["device_dispatches"] > two.stats["steps"]

    def test_stamp_fused_parity(self, params, prompts):
        """Same parity under the fused STaMP integer path (prepared int8
        weights, fused decode matmul) — the segment rule must hold through
        the Pallas kernels."""
        serve = lm.ServeConfig(
            stamp=StampConfig(num_hi_tokens=8, execution="fused"), kv=QUANT)
        short = prompts[:3]
        new = MAX_NEW[:3]
        two = run_engine(PagedServingEngine(
            params, CFG, serve, paged_cfg(step_mode="two_call")), short, new)
        uni = run_engine(PagedServingEngine(
            params, CFG, serve, paged_cfg()), short, new)
        for uid in two:
            np.testing.assert_array_equal(two[uid], uni[uid],
                                          err_msg=f"uid={uid}")


class TestRecompileGuard:
    def test_fixed_compile_count_per_run(self, params, prompts):
        """Shape bucketing bounds the jit variants: one engine run compiles
        at most |{0, 1, 2, …, max_prefills}| unified programs, and feeding
        more work through the same engine adds none."""
        serve = lm.ServeConfig(stamp=None, kv=QUANT)
        eng = PagedServingEngine(params, CFG, serve, paged_cfg())
        run_engine(eng, prompts)
        first_count = eng.compile_count()
        assert first_count <= len(eng._npf_buckets)
        assert eng.stats["recompiles"] == len(eng._compiled_keys)
        run_engine(eng, prompts)          # same shapes: zero new compiles
        assert eng.compile_count() == first_count

    def test_events_ring_buffer_capped(self, params, prompts):
        serve = lm.ServeConfig(stamp=None, kv=QUANT)
        eng = PagedServingEngine(params, CFG, serve,
                                 paged_cfg(max_events=16))
        run_engine(eng, prompts)
        assert len(eng.events) == 16      # trace clipped to the newest N
        assert eng.events.maxlen == 16


# ---------------------------------------------------------------------------
# ragged kernel vs oracle
# ---------------------------------------------------------------------------


class TestRaggedKernel:
    def _setup(self, c_len=24):
        cfg = PagedCacheConfig(block_size=8, num_lo_blocks=16,
                               num_hi_blocks=8, max_blocks_per_seq=4,
                               quant=QUANT)
        rng = np.random.default_rng(3)
        g, hd, h = 2, 16, 4
        entry = {k: a[0] for k, a in PKV.init_pools(1, g, hd, cfg).items()}
        # span 0: continuation chunk (16 tokens cached, the chunk's 11
        # valid tokens just written — ODD, ending mid-page); span 1: first
        # chunk, num_hi(16) ≥ its early positions; spans 2-3: decode
        # slots, span 3 with num_hi >= seq
        reqs = {0: ([1, 2], [1, 2], 27), 1: ([3, 4], [3], 21),
                2: ([5, 6], [4, 5], 30), 3: ([7, 0], [0, 0], 9)}
        for uid, (hp, lp, ln) in reqs.items():
            k = jnp.asarray(rng.normal(size=(1, ln, g, hd)
                                       ).astype(np.float32))
            v = jnp.asarray(rng.normal(size=(1, ln, g, hd)
                                       ).astype(np.float32))
            pages, offs, ishi = [], [], []
            for pos in range(ln):
                is_hi, pidx, off = PKV.token_page_index(pos, cfg)
                pages.append((hp if is_hi else lp)[pidx])
                offs.append(off)
                ishi.append(is_hi)
            entry = PKV.write_chunk(entry, k, v,
                                    jnp.asarray(pages, jnp.int32),
                                    jnp.asarray(offs, jnp.int32),
                                    jnp.asarray(ishi, bool), cfg)
        q_pf = jnp.asarray(rng.normal(size=(2, c_len, h, hd)
                                      ).astype(np.float32))
        # the chunks' own raw K/V (rows past a chunk's valid length pad)
        k_pf = jnp.asarray(rng.normal(size=(2, c_len, g, hd)
                                      ).astype(np.float32))
        v_pf = jnp.asarray(rng.normal(size=(2, c_len, g, hd)
                                      ).astype(np.float32))
        q_dec = jnp.asarray(rng.normal(size=(2, 1, h, hd)
                                       ).astype(np.float32))
        # positions read through pages: the chunks' cached prefixes, the
        # decode slots' lengths
        cached = jnp.asarray([16, 0, 30, 9], jnp.int32)
        ht = jnp.asarray([reqs[i][0] for i in range(4)], jnp.int32)
        lt = jnp.asarray([reqs[i][1] + [0] * (4 - len(reqs[i][1]))
                          for i in range(4)], jnp.int32)
        return cfg, entry, q_pf, k_pf, v_pf, q_dec, cached, ht, lt

    @staticmethod
    def _fallback(cfg, entry, q_pf, k_pf, v_pf, q_dec, cached, ht, lt):
        """The XLA fallback of `lm.attn_block_unified`: chunk rows attend
        to their gathered cached prefix and causally to their raw chunk,
        decode slots to their gathered pages."""
        n_pf = q_pf.shape[0]
        segs = PKV.gather_segments(entry, ht[n_pf:], lt[n_pf:], cfg,
                                   jnp.float32)
        out_dec = L.decode_attention_segments(q_dec, segs,
                                              length=cached[n_pf:])
        if n_pf == 0:
            return q_pf, out_dec
        segs = PKV.gather_segments(entry, ht[:n_pf], lt[:n_pf], cfg,
                                   jnp.float32)
        return L.chunked_prefill_attention(q_pf, segs, k_pf, v_pf,
                                           cached[:n_pf]), out_dec

    def test_matches_oracle_mixed_spans(self):
        """Prefill spans (a continuation chunk ending mid-page, a
        no-prefix first chunk) and decode spans (one with num_hi ≥ seq) in
        one call, vs the XLA fallback: chunk rows see their cached prefix
        and their raw chunk.  Only valid chunk rows compared — pad rows
        are defined but discarded by the caller."""
        cfg, entry, q_pf, k_pf, v_pf, q_dec, cached, ht, lt = self._setup()
        out_pf, out_dec = paged_ragged_attention(
            entry, q_pf, q_dec, k_pf, v_pf, cached, ht, lt, cfg.block_size,
            interpret=True)
        ref_pf, ref_dec = self._fallback(cfg, entry, q_pf, k_pf, v_pf,
                                         q_dec, cached, ht, lt)
        for i, n in enumerate((11, 21)):
            np.testing.assert_allclose(
                np.asarray(out_pf[i, :n], np.float32),
                np.asarray(ref_pf[i, :n]), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out_dec, np.float32),
                                   np.asarray(ref_dec), atol=1e-5,
                                   rtol=1e-5)

    def test_all_decode_delegates_to_decode_kernel(self):
        """n_pf = 0 (the steady-state fast case) must route through the
        decode kernel alone and agree with the fallback."""
        cfg, entry, q_pf, k_pf, v_pf, q_dec, cached, ht, lt = self._setup()
        out_pf, out_dec = paged_ragged_attention(
            entry, q_pf[:0], q_dec, k_pf[:0], v_pf[:0], cached[2:], ht[2:],
            lt[2:], cfg.block_size, interpret=True)
        assert out_pf.shape[0] == 0
        _, ref_dec = self._fallback(cfg, entry, q_pf[:0], k_pf[:0],
                                    v_pf[:0], q_dec, cached[2:], ht[2:],
                                    lt[2:])
        np.testing.assert_allclose(np.asarray(out_dec, np.float32),
                                   np.asarray(ref_dec), atol=1e-5,
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# segment-aware STaMP application
# ---------------------------------------------------------------------------


class TestSegmentedStamp:
    def test_fold_unfold_roundtrip(self):
        x = jnp.arange(2 * 12 * 3, dtype=jnp.float32).reshape(2, 12, 3)
        f = fold_segments(x, 4)
        assert f.shape == (6, 4, 3)
        np.testing.assert_array_equal(np.asarray(unfold_segments(f, 2)),
                                      np.asarray(x))
        with pytest.raises(ValueError):
            fold_segments(x, 5)

    def test_fake_quant_per_span(self):
        """seg_len round trip == running each span alone: the transform
        never mixes tokens across the flattened batch."""
        cfg = StampConfig(num_hi_tokens=4)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(1, 32, 16)).astype(np.float32))
        seg = stamp_fake_quant(x, cfg, seg_len=8)
        per_span = jnp.concatenate(
            [stamp_fake_quant(x[:, i:i + 8], cfg) for i in range(0, 32, 8)],
            axis=1)
        np.testing.assert_array_equal(np.asarray(seg),
                                      np.asarray(per_span))

    def test_segment_kernel_wrapper_per_span(self):
        """`stamp_quant_segment_matmul_pallas` (the kernel-level entry for
        flattened callers) == one plain kernel call per span."""
        from repro.core.stamp import prepare_linear
        from repro.kernels.stamp_matmul import (
            stamp_quant_matmul_pallas, stamp_quant_segment_matmul_pallas)
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(2, 24, 16)).astype(np.float32))
        prep = prepare_linear(
            jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32)))
        bias = jnp.zeros((1, 32), jnp.float32)
        kw = dict(transform="dwt", levels=1, num_hi=4, interpret=True)
        seg = stamp_quant_segment_matmul_pallas(
            x, prep.qw, prep.sw, prep.zw, bias, seg_len=8, **kw)
        per_span = jnp.concatenate(
            [stamp_quant_matmul_pallas(x[:, i:i + 8], prep.qw, prep.sw,
                                       prep.zw, bias, **kw)
             for i in range(0, 24, 8)], axis=1)
        np.testing.assert_allclose(np.asarray(seg), np.asarray(per_span),
                                   atol=1e-6, rtol=1e-6)
        with pytest.raises(ValueError):
            stamp_quant_segment_matmul_pallas(
                x, prep.qw, prep.sw, prep.zw, bias, seg_len=7, **kw)

    @pytest.mark.parametrize("execution", ["reference", "fused"])
    def test_stamp_linear_per_span(self, execution):
        cfg = StampConfig(num_hi_tokens=4, execution=execution)
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(1, 32, 16)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(16, 24)).astype(np.float32))
        seg = stamp_linear(x, w, None, cfg, seg_len=8)
        per_span = jnp.concatenate(
            [stamp_linear(x[:, i:i + 8], w, None, cfg)
             for i in range(0, 32, 8)], axis=1)
        np.testing.assert_allclose(np.asarray(seg, np.float32),
                                   np.asarray(per_span, np.float32),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# scheduler satellites: determinism + transform-aware boundaries
# ---------------------------------------------------------------------------


def _mk_sched(**kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("prefill_chunk", 16)
    scfg = SchedulerConfig(**kw)
    pcfg = PagedCacheConfig(block_size=8, num_lo_blocks=64, num_hi_blocks=16,
                            max_blocks_per_seq=8, quant=QUANT)
    return Scheduler(scfg, pcfg, swap_out=lambda r: None,
                     swap_in=lambda r: None)


def _req(uid, length, arrival=None):
    return SchedRequest(uid=uid, prompt=np.zeros(length, np.int32),
                        max_new_tokens=4,
                        arrival=uid if arrival is None else arrival)


class TestSchedulerSatellites:
    def test_victim_tie_break_is_uid(self):
        """Equal arrivals: the evicted victim must be the highest (arrival,
        uid) pair, not whichever request happened to be admitted last."""
        sched = _mk_sched(max_prefills=3)   # all three reserve pages
        a, b, c = _req(1, 8, arrival=5), _req(3, 8, arrival=5), \
            _req(2, 8, arrival=5)
        for r in (a, b, c):
            sched.submit(r)
        sched.plan_step()
        victim = sched._pick_victim(exclude=None)
        assert victim.uid == 3

    def test_waiting_order_tie_break(self):
        sched = _mk_sched(max_slots=1)
        for r in (_req(2, 8, arrival=7), _req(1, 8, arrival=7)):
            sched.submit(r)
        assert [r.uid for r in sched.waiting] == [1, 2]

    def test_free_slots_heap_lowest_first(self):
        sched = _mk_sched(max_slots=3)
        reqs = [_req(i, 8) for i in (1, 2, 3)]
        for r in reqs:
            sched.submit(r)
        sched.plan_step()
        slots = {r.uid: r.slot for r in reqs}
        assert slots == {1: 0, 2: 1, 3: 2}
        reqs[1].state = "running"
        sched.finish(reqs[1])             # frees slot 1
        sched.submit(_req(4, 8))
        sched.plan_step()
        assert sched.active[-1].slot == 1  # lowest free slot reused

    def test_transform_window_alignment(self):
        """Non-final chunk ends align down to the window; the final chunk
        keeps the exact prompt end; a window larger than the chunk falls
        back to the unaligned end (per-chunk transform spans the chunk)."""
        sched = _mk_sched(prefill_chunk=12, transform_window=8,
                          max_prefills=2)
        r = _req(1, 40)
        sched.submit(r)
        plan = sched.plan_step()
        (w,) = plan.prefills
        assert (w.start, w.end) == (0, 8)   # 12 aligned down to 8
        r.pos = w.end
        plan = sched.plan_step()
        assert (plan.prefills[0].start, plan.prefills[0].end) == (8, 16)
        r.pos = 36                          # 4 tokens left < window
        plan = sched.plan_step()
        assert plan.prefills[0].end == 40   # final chunk: exact prompt end

    def test_window_larger_than_chunk_falls_back(self):
        sched = _mk_sched(prefill_chunk=8, transform_window=32)
        r = _req(1, 40)
        sched.submit(r)
        plan = sched.plan_step()
        assert plan.prefills[0].end == 8    # unaligned (documented fallback)

    def test_multiple_prefills_fcfs(self):
        """max_prefills > 1: several PREFILLING requests chunk in the same
        step, strictly FCFS-ordered."""
        sched = _mk_sched(max_prefills=3)
        reqs = [_req(i, 40) for i in (1, 2, 3)]
        for r in reqs:
            sched.submit(r)
        plan = sched.plan_step()
        assert [w.sreq.uid for w in plan.prefills] == [1, 2, 3]
        assert all(r.state == PREFILLING for r in reqs)
        spans = plan.spans()
        assert [s[1] for s in spans] == [0, 16, 32]   # ragged offsets
        assert all(s[2] == 16 for s in spans)

    def test_engine_transform_window_helper(self):
        st = StampConfig(num_hi_tokens=8)     # levels auto
        assert _transform_window(st, 64) == 2 ** st.resolved_levels(64)
        assert _transform_window(None, 64) == 1
        assert _transform_window(StampConfig(enabled=False), 64) == 1
        # window > chunk → fallback 1
        deep = StampConfig(num_hi_tokens=1, levels=10)
        assert _transform_window(deep, 64) == 1
