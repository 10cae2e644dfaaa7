"""End-to-end behaviour tests for the full system: training converges,
the PTQ pipeline improves matched-budget quantization, the serving engine
drains batched requests, and STaMP serving stays close to bf16 serving."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.core.ptq import calibrate, calibrate_and_quantize
from repro.core.stamp import StampConfig
from repro.data.pipeline import DataConfig, calibration_batches
from repro.launch.train import TrainConfig, train
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.kvcache import KVCacheConfig

CFG = ModelConfig(name="sys-test", family="dense", num_layers=2,
                  d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                  vocab_size=256, tie_embeddings=True)


@pytest.fixture(scope="module")
def trained():
    out = train(CFG, TrainConfig(steps=100, global_batch=8, seq=64,
                                 lr=3e-3, warmup=10),
                ckpt_dir=None, verbose=False)
    return out


class TestTraining:
    def test_loss_decreases(self, trained):
        losses = trained["losses"]
        assert losses[-1] < losses[0] * 0.9, \
            f"no learning: {losses[0]:.3f} -> {losses[-1]:.3f}"

    def test_wsd_schedule_used_for_minicpm(self):
        from repro.configs import get_config
        assert get_config("minicpm-2b").schedule == "wsd"

    def test_compressed_grads_still_learn(self):
        out = train(CFG, TrainConfig(steps=60, global_batch=8, seq=64,
                                     lr=3e-3, warmup=10,
                                     compress_grads=True),
                    ckpt_dir=None, verbose=False)
        assert out["losses"][-1] < out["losses"][0]


class TestPTQPipeline:
    def test_calibration_finds_structure(self, trained):
        dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=64,
                          global_batch=4)
        _, serve, report = calibrate_and_quantize(
            trained["params"], calibration_batches(dcfg, 2), CFG)
        assert report.toeplitz_fraction > 0.3
        assert report.num_hi >= 1
        assert serve.stamp is not None and serve.kv.quantized

    def test_quantized_weights_close(self, trained):
        dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=64,
                          global_batch=4)
        sparams, _, _ = calibrate_and_quantize(
            trained["params"], calibration_batches(dcfg, 1), CFG)
        p0 = jax.tree.map(lambda a: a[0], trained["params"]["period"])[0]
        w_ref = np.asarray(p0["wq"], np.float32)
        packed = sparams["period"][0]["wq"]
        deq = np.asarray(lm._dequant_packed(
            jax.tree.map(lambda a: a[0], packed), jnp.float32))
        rel = np.linalg.norm(deq - w_ref) / np.linalg.norm(w_ref)
        assert rel < 0.15

    def test_packed_init_matches_packing_float_init(self):
        """Initialising straight into the served form (one period at a
        time) gives the tree of packing a float init: the same unpacked
        leaves, scales within float rounding, and int4 codes that differ
        only where a value sits at a rounding tie, which the compiled
        per-period pass may break the other way (one code step, in under
        1% of weights)."""
        key = jax.random.PRNGKey(3)
        packed = lm.init_params(key, CFG, weight_bits=4)
        ref = lm.quantize_weights_for_serving(jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
            else a, lm.init_params(key, CFG)), 4)
        is_packed = lambda t: isinstance(t, dict) and "q" in t
        a_leaves = jax.tree.leaves(packed, is_leaf=is_packed)
        b_leaves = jax.tree.leaves(ref, is_leaf=is_packed)
        assert jax.tree.structure(packed, is_leaf=is_packed) == \
            jax.tree.structure(ref, is_leaf=is_packed)
        n_packed = 0
        for a, b in zip(a_leaves, b_leaves):
            if not is_packed(a):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
                continue
            n_packed += 1
            np.testing.assert_allclose(np.asarray(a["scale"]),
                                       np.asarray(b["scale"]), rtol=1e-6)
            wa = np.asarray(lm._dequant_packed(a, jnp.float32))
            wb = np.asarray(lm._dequant_packed(b, jnp.float32))
            step = np.asarray(a["scale"], np.float32)
            assert (np.abs(wa - wb) <= step * 1.001 + 1e-6).all()
            assert (np.abs(wa - wb) > 0.5 * step).mean() < 0.01
        assert n_packed >= 4

    def test_calibrate_through_packed_weights(self):
        dcfg = DataConfig(vocab_size=CFG.vocab_size, seq_len=64,
                          global_batch=4)
        packed = lm.init_params(jax.random.PRNGKey(0), CFG, weight_bits=4)
        serve, report = calibrate(packed, calibration_batches(dcfg, 2), CFG)
        assert report.num_hi >= 1
        assert serve.stamp.num_hi_tokens == serve.kv.num_hi == report.num_hi
        assert serve.weight_bits == 4


class TestServingEngine:
    def test_batched_requests_complete(self, trained):
        serve = lm.ServeConfig(stamp=StampConfig(num_hi_tokens=8),
                               kv=KVCacheConfig(num_hi=8))
        eng = ServingEngine(trained["params"], CFG, serve,
                            EngineConfig(max_batch=4, bucket=32, max_seq=64))
        rng = np.random.default_rng(0)
        for _ in range(6):
            eng.submit(rng.integers(0, CFG.vocab_size, 20),
                       max_new_tokens=8)
        done = eng.run()
        assert len(done) == 6
        assert all(len(r.out_tokens) == 8 for r in done)
        assert not eng.queue

    def test_stamp_serving_tracks_bf16(self, trained):
        params = trained["params"]
        rng = np.random.default_rng(1)
        prompts = rng.integers(0, CFG.vocab_size, (4, 32)).astype(np.int32)

        def first_tokens(serve, p):
            logits, cache = lm.prefill(p, {"tokens": jnp.asarray(prompts)},
                                       CFG, serve)
            return np.asarray(jnp.argmax(logits, -1))

        bf16 = first_tokens(lm.ServeConfig(
            stamp=None, kv=KVCacheConfig(quantized=False),
            weight_bits=None), params)
        stamp = first_tokens(lm.ServeConfig(
            stamp=StampConfig(num_hi_tokens=8),
            kv=KVCacheConfig(num_hi=8), weight_bits=None), params)
        agree = (bf16 == stamp).mean()
        assert agree >= 0.5, f"STaMP serving diverged: {agree:.0%} agreement"
