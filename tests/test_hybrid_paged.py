"""Hybrid (Mamba + attention) serving under the paged engine: the
slot-dense SSM state pool next to the paged KV cache.

Pins the PR-5 contracts:

* masked decode — inactive slots (null tokens) leave per-slot conv/SSM
  state bit-for-bit untouched;
* chunked prefill carries conv/SSM state across chunk boundaries (incl.
  window-unaligned final chunks) and matches the one-shot prefill;
* the bucketed engine's right-padding no longer advances the Mamba
  recurrence with pad tokens (the pad-state audit fix);
* preemption + resume swap the SSM slot state with the victim's pages and
  restore bit-identically;
* the reduced Jamba config serves token-identically across
  BucketedEngine / unified / two-call paged modes, including a forced
  preemption, at exactly one device dispatch per unified step;
* pure-SSM stacks serve pageless (slots are the only capacity dimension);
* capability checks fail with actionable errors (enc-dec, missing
  num_slots, the serve CLI).
"""

import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.configs import get_reduced
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving import kvcache as KV
from repro.serving import paged_kvcache as PKV
from repro.serving.engine import (BucketedEngine, EngineConfig,
                                  PagedEngineConfig, PagedServingEngine)

ROOT = pathlib.Path(__file__).resolve().parents[1]

# small fast hybrid: period (mamba, attn), both FFN'd — every state family
# in four layers
HCFG = ModelConfig(name="hybrid-test", family="hybrid", num_layers=4,
                   d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                   vocab_size=128, attn_period=2, ssm_state=16,
                   ssm_head_dim=16)
SCFG = ModelConfig(name="ssm-test", family="ssm", num_layers=3,
                   d_model=64, num_heads=0, num_kv_heads=0, d_ff=0,
                   vocab_size=128, ssm_state=16, ssm_head_dim=16,
                   tie_embeddings=True)
QUANT = KV.KVCacheConfig(quantized=True, num_hi=16)


@pytest.fixture(scope="module")
def hparams():
    return lm.init_params(jax.random.PRNGKey(0), HCFG)


@pytest.fixture(scope="module")
def sparams():
    return lm.init_params(jax.random.PRNGKey(1), SCFG)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(3)
    return [rng.integers(0, 128, l) for l in (20, 40, 12, 33, 26)]


def paged_cfg(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("max_seq", 96)
    kw.setdefault("block_size", 16)
    return PagedEngineConfig(**kw)


def run_engine(engine, prompts, max_new):
    for p, m in zip(prompts, max_new):
        engine.submit(p, m)
    done = engine.run()
    lm.set_fused_cache_attention(False)
    lm.set_fused_decode_matmul(False)
    return {r.uid: r.out_tokens for r in done}


def hybrid_pools(cfg, num_slots=3):
    pcfg = PKV.PagedCacheConfig(block_size=16, num_lo_blocks=8,
                                num_hi_blocks=4, max_blocks_per_seq=5,
                                quant=QUANT)
    return lm.init_paged_cache(cfg, pcfg, num_slots=num_slots), pcfg


# ---------------------------------------------------------------------------
# slot pool plumbing
# ---------------------------------------------------------------------------


class TestSSMStatePool:
    def test_pool_shapes_and_null_slot(self):
        pools, _ = hybrid_pools(HCFG)
        ssm = [v for v in pools.values() if PKV.is_ssm_entry(v)]
        attn = [v for v in pools.values() if not PKV.is_ssm_entry(v)]
        assert len(ssm) == 1 and len(attn) == 1   # period = (mamba, attn)
        entry = ssm[0]
        nper = HCFG.num_layers // 2
        # num_slots + 1: the last row is the null slot (scatter target for
        # unused prefill chunk rows)
        assert entry["state"].shape == (nper, 4, 8, 16, 16)
        assert entry["conv"].shape == (nper, 4, HCFG.conv_width - 1,
                                       HCFG.d_inner + 2 * HCFG.ssm_state)

    def test_state_bytes_per_slot_analytic(self):
        pools, _ = hybrid_pools(HCFG)
        nper = HCFG.num_layers // 2
        di, n = HCFG.d_inner, HCFG.ssm_state
        state = nper * HCFG.ssm_heads * HCFG.ssm_head_dim * n * 4
        conv = nper * (HCFG.conv_width - 1) * (di + 2 * n) * 2
        assert PKV.ssm_state_bytes_per_slot(pools) == state + conv

    def test_swap_roundtrip_with_ssm_state(self):
        """extract -> zero the row -> insert at a DIFFERENT slot restores
        the state bit-identically (the preemption/resume contract)."""
        pools, _ = hybrid_pools(HCFG)
        rng = np.random.default_rng(0)
        key = next(k for k, v in pools.items() if PKV.is_ssm_entry(v))
        entry = dict(pools[key])
        entry["state"] = jnp.asarray(
            rng.normal(size=pools[key]["state"].shape).astype(np.float32))
        entry["conv"] = jnp.asarray(
            rng.normal(size=pools[key]["conv"].shape)).astype(jnp.bfloat16)
        pools[key] = entry
        saved = PKV.extract_pages(pools, [1], [1, 2], slot=1)
        restored = PKV.insert_pages(pools, saved, [2], [3, 4], slot=2)
        for name in ("state", "conv"):
            np.testing.assert_array_equal(
                np.asarray(restored[key][name][:, 2]),
                np.asarray(pools[key][name][:, 1]))

    def test_swap_without_slot_raises(self):
        pools, _ = hybrid_pools(HCFG)
        with pytest.raises(ValueError, match="slot"):
            PKV.extract_pages(pools, [1], [1])
        with pytest.raises(ValueError, match="slot"):
            PKV.insert_pages(pools, {}, [1], [1])


# ---------------------------------------------------------------------------
# masked decode (satellite: null tokens must not advance the recurrence)
# ---------------------------------------------------------------------------


class TestMaskedDecode:
    def _decode(self, params, pools, active):
        s = len(active)
        z = jnp.zeros((s,), jnp.int32)
        ht = jnp.zeros((s, 1), jnp.int32)
        lt = jnp.zeros((s, 2), jnp.int32)
        serve = lm.ServeConfig(stamp=None,
                               kv=KV.KVCacheConfig(quantized=False))
        serve = dataclasses.replace(
            serve, paged=PKV.PagedCacheConfig(
                block_size=16, num_lo_blocks=4, num_hi_blocks=1,
                max_blocks_per_seq=2,
                quant=KV.KVCacheConfig(quantized=False)))
        _, new_pools, _ = lm.paged_decode_step(
            params, pools, z, z, ht, lt, z, z,
            jnp.zeros((s,), bool), SCFG, serve,
            active=jnp.asarray(active))
        return new_pools

    def test_inactive_slots_keep_state_bit_identical(self, sparams):
        """A step where no slot is RUNNING (all tokens are null pads) must
        be a no-op on every conv/SSM state row — previously the recurrence
        advanced with the pad-token garbage."""
        pcfg = PKV.PagedCacheConfig(
            block_size=16, num_lo_blocks=4, num_hi_blocks=1,
            max_blocks_per_seq=2, quant=KV.KVCacheConfig(quantized=False))
        pools = lm.init_paged_cache(SCFG, pcfg, num_slots=3)
        new_pools = self._decode(sparams, pools, [False, False, False])
        for k, entry in pools.items():
            for name in ("state", "conv"):
                np.testing.assert_array_equal(np.asarray(entry[name]),
                                              np.asarray(new_pools[k][name]))

    def test_active_slot_advances_only_its_row(self, sparams):
        pcfg = PKV.PagedCacheConfig(
            block_size=16, num_lo_blocks=4, num_hi_blocks=1,
            max_blocks_per_seq=2, quant=KV.KVCacheConfig(quantized=False))
        pools = lm.init_paged_cache(SCFG, pcfg, num_slots=3)
        new_pools = self._decode(sparams, pools, [False, True, False])
        key = next(iter(pools))
        st_old = np.asarray(pools[key]["state"])
        st_new = np.asarray(new_pools[key]["state"])
        assert not np.array_equal(st_old[:, 1], st_new[:, 1])
        np.testing.assert_array_equal(st_old[:, 0], st_new[:, 0])
        np.testing.assert_array_equal(st_old[:, 2], st_new[:, 2])
        np.testing.assert_array_equal(st_old[:, 3], st_new[:, 3])  # null


# ---------------------------------------------------------------------------
# stateful chunked prefill (satellite: state carry vs one-shot parity)
# ---------------------------------------------------------------------------


class TestChunkedPrefillStateCarry:
    def test_chunked_state_matches_one_shot(self, hparams):
        """Prefill a 33-token prompt in 16-token chunks (the final chunk
        end is window-unaligned) through the two-call path; the slot's
        conv/SSM state must match the one-shot dense prefill of the same
        prompt (the state a decode step continues from)."""
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, HCFG.vocab_size, 33)
        serve_d = lm.ServeConfig(stamp=None, kv=QUANT, cache_capacity=64)
        _, dense_cache = lm.prefill(
            hparams, {"tokens": jnp.asarray(prompt[None])}, HCFG, serve_d)

        # max_new=1: the first token comes from the prefill logits and the
        # request finishes before any decode step, so the slot holds the
        # post-prompt state — the object under test
        eng = PagedServingEngine(
            hparams, HCFG, lm.ServeConfig(stamp=None, kv=QUANT),
            paged_cfg(max_slots=2, step_mode="two_call"))
        eng.submit(prompt, 1)
        eng.run()
        key = next(k for k, v in eng.pools.items() if PKV.is_ssm_entry(v))
        got = np.asarray(eng.pools[key]["state"][:, 0])   # slot 0
        want = np.asarray(dense_cache[key]["state"][:, 0])
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3)
        # the conv tail is the layer-input activation at the last 3 valid
        # positions: later layers see the (tiny) cross-chunk attention/SSD
        # reduction differences of earlier ones, so the comparison is
        # approximate rather than bitwise (bf16 magnitudes ~1, drift <0.1)
        got_c = np.asarray(eng.pools[key]["conv"][:, 0], np.float32)
        want_c = np.asarray(dense_cache[key]["conv"][:, 0], np.float32)
        np.testing.assert_allclose(got_c, want_c, rtol=1e-1, atol=1e-1)

    def test_single_chunk_prompt_is_bit_identical(self, hparams, prompts):
        """Prompts that fit one prefill chunk: paged (unified) and
        bucketed tokens must be EQUAL, not just close — chunk width ==
        bucket width makes every per-row computation identical."""
        serve = lm.ServeConfig(stamp=None, kv=QUANT)
        short = prompts[:3]
        max_new = (8, 8, 8)
        buck = run_engine(
            BucketedEngine(hparams, HCFG, serve,
                           EngineConfig(max_batch=3, bucket=64, max_seq=96)),
            short, max_new)
        uni = run_engine(
            PagedServingEngine(hparams, HCFG, serve,
                               paged_cfg(prefill_chunk=64)),
            short, max_new)
        for uid in buck:
            np.testing.assert_array_equal(buck[uid], uni[uid],
                                          err_msg=f"uid={uid}")


class TestBucketedPadMask:
    def test_padded_prefill_state_matches_unpadded(self, hparams):
        """The pad-state audit fix: right-padding a hybrid prompt must not
        advance the Mamba recurrence past the prompt's last token —
        prefill(last_pos=) now masks dt and slices the conv tail at the
        valid boundary, so the padded state equals the unpadded one."""
        rng = np.random.default_rng(9)
        prompt = rng.integers(0, HCFG.vocab_size, 21)
        serve = lm.ServeConfig(stamp=None, kv=QUANT, cache_capacity=64)
        padded = np.zeros((1, 32), np.int32)
        padded[0, :21] = prompt
        lg_p, cache_p = lm.prefill(hparams,
                                   {"tokens": jnp.asarray(padded)}, HCFG,
                                   serve, last_pos=jnp.asarray([20]))
        lg_u, cache_u = lm.prefill(hparams,
                                   {"tokens": jnp.asarray(prompt[None])},
                                   HCFG, serve)
        key = next(k for k in cache_p if "state" in cache_p[k])
        np.testing.assert_allclose(np.asarray(cache_p[key]["state"]),
                                   np.asarray(cache_u[key]["state"]),
                                   rtol=2e-2, atol=2e-3)
        np.testing.assert_allclose(
            np.asarray(cache_p[key]["conv"], np.float32),
            np.asarray(cache_u[key]["conv"], np.float32),
            rtol=2e-2, atol=2e-3)
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_u),
                                   rtol=2e-2, atol=2e-3)


# ---------------------------------------------------------------------------
# engine-level parity (acceptance: reduced Jamba, forced preemption)
# ---------------------------------------------------------------------------


class TestHybridUnifiedParity:
    def test_unified_vs_two_call_under_contention(self, hparams, prompts):
        """Multi-chunk prompts, staggered admission (5 requests, 3 slots)
        and a lo pool tight enough to preempt: the unified hybrid step must
        reproduce the two-call engine token for token — SSM state carry,
        masked decode and the state swap all inside one device program."""
        serve = lm.ServeConfig(stamp=None, kv=QUANT)
        max_new = (14, 10, 16, 8, 12)
        out = {}
        for mode in ("two_call", "unified"):
            eng = PagedServingEngine(
                hparams, HCFG, serve,
                paged_cfg(max_slots=5, num_lo_blocks=6, step_mode=mode))
            out[mode] = (run_engine(eng, prompts, max_new), eng)
        two, _ = out["two_call"]
        uni, eng = out["unified"]
        assert eng.stats["preemptions"] > 0
        assert eng.stats["swap_bytes"] > 0
        assert eng.stats["device_dispatches"] == eng.stats["steps"]
        for uid in two:
            np.testing.assert_array_equal(two[uid], uni[uid],
                                          err_msg=f"uid={uid}")


class TestReducedJambaAcceptance:
    @pytest.fixture(scope="class")
    def jamba(self):
        cfg = get_reduced("jamba-1.5-large-398b")
        return cfg, lm.init_params(jax.random.PRNGKey(0), cfg)

    def test_paged_matches_bucketed_with_forced_preemption(self, jamba):
        """The acceptance workload: the reduced Jamba hybrid config (MoE +
        Mamba + attention) serves bit-identical tokens through
        BucketedEngine and both paged step modes, the paged runs include a
        forced preemption + resume, and the unified run dispatches exactly
        one device program per step."""
        cfg, params = jamba
        serve = lm.ServeConfig(stamp=None, kv=QUANT)
        rng = np.random.default_rng(5)
        reqs = [rng.integers(0, cfg.vocab_size, l) for l in (20, 33, 12)]
        max_new = (8, 8, 8)
        buck = run_engine(
            BucketedEngine(params, cfg, serve,
                           EngineConfig(max_batch=3, bucket=64, max_seq=96)),
            reqs, max_new)
        outs = {}
        for mode in ("unified", "two_call"):
            eng = PagedServingEngine(
                params, cfg, serve,
                paged_cfg(prefill_chunk=64, num_lo_blocks=4,
                          step_mode=mode))
            outs[mode] = run_engine(eng, reqs, max_new)
            assert eng.stats["preemptions"] > 0, mode
            kinds = [k for _, k, _ in eng.events]
            assert "preempt" in kinds and "resume" in kinds
            if mode == "unified":
                assert eng.stats["device_dispatches"] == eng.stats["steps"]
        for uid in buck:
            np.testing.assert_array_equal(buck[uid], outs["unified"][uid],
                                          err_msg=f"uid={uid}")
            np.testing.assert_array_equal(buck[uid], outs["two_call"][uid],
                                          err_msg=f"uid={uid}")


class TestPureSSM:
    def test_pageless_serving_matches_bucketed(self, sparams, prompts):
        """A stack with no attention layers allocates no pages at all
        (needs_kv_pages=False): slots are the only capacity dimension, and
        tokens match the bucketed oracle."""
        serve = lm.ServeConfig(stamp=None,
                               kv=KV.KVCacheConfig(quantized=False))
        short = prompts[:3]
        max_new = (6, 6, 6)
        buck = run_engine(
            BucketedEngine(sparams, SCFG, serve,
                           EngineConfig(max_batch=3, bucket=64, max_seq=96)),
            short, max_new)
        eng = PagedServingEngine(sparams, SCFG, serve,
                                 paged_cfg(prefill_chunk=64))
        paged = run_engine(eng, short, max_new)
        for uid in buck:
            np.testing.assert_array_equal(buck[uid], paged[uid],
                                          err_msg=f"uid={uid}")
        active = [r for r in eng.sched.active]
        assert eng.sched.cfg.needs_kv_pages is False
        assert eng.sched.cfg.state_bytes_per_slot > 0
        assert not active or all(
            not (r.hi_pages or r.lo_pages) for r in active)

    def test_more_requests_than_slots(self, sparams, prompts):
        """Slot turnover without pages: admission waves drain the queue."""
        serve = lm.ServeConfig(stamp=None,
                               kv=KV.KVCacheConfig(quantized=False))
        eng = PagedServingEngine(sparams, SCFG, serve,
                                 paged_cfg(max_slots=2, prefill_chunk=64))
        out = run_engine(eng, prompts, (6, 6, 6, 6, 6))
        assert len(out) == 5
        assert all(len(v) == 6 for v in out.values())


# ---------------------------------------------------------------------------
# capability checks (satellite: actionable errors + CLI smoke)
# ---------------------------------------------------------------------------


class TestCapability:
    def test_hybrid_without_num_slots_raises(self):
        pcfg = PKV.PagedCacheConfig(quant=QUANT)
        with pytest.raises(ValueError, match="num_slots"):
            lm.init_paged_cache(HCFG, pcfg)

    def test_encdec_raises_actionable(self):
        cfg = ModelConfig(name="encdec", family="audio", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=128, vocab_size=128, encoder_layers=2,
                          frontend="frames")
        with pytest.raises(NotImplementedError, match="BucketedEngine"):
            lm.init_paged_cache(cfg, PKV.PagedCacheConfig(quant=QUANT),
                                num_slots=2)

    def test_engine_rejects_encdec_before_allocation(self):
        cfg = ModelConfig(name="encdec", family="audio", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=128, vocab_size=128, encoder_layers=2,
                          frontend="frames")
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(NotImplementedError, match="bucketed"):
            PagedServingEngine(params, cfg,
                               lm.ServeConfig(stamp=None, kv=QUANT),
                               paged_cfg())


class TestServeCLI:
    def _env(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + \
            env.get("PYTHONPATH", "")
        env.setdefault("JAX_PLATFORMS", "cpu")
        return env

    def test_paged_encdec_fails_fast_with_fix(self):
        """The CLI must reject --engine paged on an enc-dec arch at the
        argument boundary (not five frames deep in cache init), naming the
        working alternative."""
        p = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch",
             "seamless-m4t-large-v2", "--reduced", "--engine", "paged",
             "--requests", "1", "--max-new", "1"],
            env=self._env(), capture_output=True, text=True, timeout=120)
        assert p.returncode != 0
        assert "bucketed" in p.stderr

    def test_paged_serves_pure_ssm_end_to_end(self):
        """PR-5 smoke: `--engine paged` on the mamba2 reduced config used
        to die inside init_paged_cache; now it serves."""
        p = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch",
             "mamba2-1.3b", "--reduced", "--engine", "paged",
             "--requests", "2", "--prompt-len", "24", "--max-new", "4",
             "--prefill-chunk", "32"],
            env=self._env(), capture_output=True, text=True, timeout=900)
        assert p.returncode == 0, p.stderr[-2000:]
        assert "[serve:paged" in p.stdout
