"""Distribution-layer tests: sharding rules, small-mesh lower+compile,
checkpoint/restart (incl. injected crash), elastic re-shard, gradient
compression, data determinism, HLO analyzer correctness."""

import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from jax.sharding import PartitionSpec as P

from repro.analysis import hlo as H
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, DataIterator, markov_batch
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.optim.compression import (compress_gradients,
                                     error_feedback_update,
                                     init_error_state)
from repro.optim.schedules import wsd_schedule, cosine_schedule

ROOT = pathlib.Path(__file__).resolve().parents[1]


class TestShardingRules:
    def test_param_specs(self):
        from repro.launch.mesh import make_local_mesh
        from repro.sharding import ShardingPolicy
        policy = ShardingPolicy(mesh=make_local_mesh())
        assert policy.param_spec("period/0/wq", 3) == P(None, "data", "model")
        assert policy.param_spec("period/0/wo", 3) == P(None, "model", "data")
        assert policy.param_spec("embed", 2) == P("model", "data")
        assert policy.param_spec("period/0/we_gate", 4) == \
            P(None, "model", "data", None)
        assert policy.param_spec("period/0/ln1", 2) == P(None, None)
        # packed-int4 leaves inherit the parent rule
        assert policy.param_spec("period/0/wq/q", 3) == \
            P(None, "data", "model")
        assert policy.param_spec("period/0/wq/scale", 3) == \
            P(None, None, "model")
        # fused-path prepared int8 leaves inherit it too
        assert policy.param_spec("period/0/wq/iq", 3) == \
            P(None, "data", "model")
        assert policy.param_spec("period/0/wqkv/iq", 3) == \
            P(None, "data", "model")
        assert policy.param_spec("period/0/wqkv/isw", 3) == \
            P(None, None, "model")
        assert policy.param_spec("period/0/wo_mlp/iq", 3) == \
            P(None, "model", "data")
        assert policy.param_spec("period/0/wq/isw", 3) == \
            P(None, None, "model")
        assert policy.param_spec("period/0/wq/izw", 3) == \
            P(None, None, "model")

    def test_seq_sharded_acts(self):
        from repro.launch.mesh import make_local_mesh
        from repro.sharding import ShardingPolicy
        p = ShardingPolicy(mesh=make_local_mesh(), seq_sharded=True)
        assert p.acts() == P(("data",), "model", None)


class TestSchedules:
    def test_wsd_shape(self):
        s = wsd_schedule(1e-3, warmup=10, total=100)
        assert float(s(jnp.asarray(0))) == 0.0
        assert abs(float(s(jnp.asarray(50))) - 1e-3) < 1e-9   # stable
        assert float(s(jnp.asarray(99))) < 2e-4               # decayed

    def test_cosine(self):
        s = cosine_schedule(1e-3, warmup=10, total=100)
        assert float(s(jnp.asarray(100))) < float(s(jnp.asarray(50)))


class TestOptimizer:
    def test_adamw_matches_reference(self):
        cfg = AdamWConfig(lr=1e-2, weight_decay=0.0, grad_clip=1e9)
        params = {"w": jnp.ones((4,)) * 2.0}
        grads = {"w": jnp.ones((4,)) * 0.5}
        state = adamw_init(params, cfg)
        new_p, state, _ = adamw_update(grads, state, params, cfg)
        # step 1: mhat = g, vhat = g², delta = 1 → p - lr
        np.testing.assert_allclose(np.asarray(new_p["w"]),
                                   2.0 - 1e-2 * (0.5 / (0.5 + 1e-8)),
                                   rtol=1e-5)

    def test_grad_clipping(self):
        cfg = AdamWConfig(lr=1e-2, grad_clip=1.0)
        params = {"w": jnp.zeros((100,))}
        grads = {"w": jnp.ones((100,)) * 10.0}  # norm = 100
        state = adamw_init(params, cfg)
        _, _, metrics = adamw_update(grads, state, params, cfg)
        assert float(metrics["grad_norm"]) > 99.0


class TestGradCompression:
    def test_error_feedback_reduces_bias(self):
        rng = np.random.default_rng(0)
        g = {"w": jnp.asarray(rng.normal(size=(256,)).astype(np.float32))}
        err = init_error_state(g)
        acc_plain = np.zeros(256)
        acc_ef = np.zeros(256)
        err_state = err
        for _ in range(50):
            q, scales, _ = compress_gradients(g, init_error_state(g))
            acc_plain += np.asarray(q["w"], np.float32) * float(scales["w"])
            deq, err_state = error_feedback_update(g, err_state)
            acc_ef += np.asarray(deq["w"])
        target = np.asarray(g["w"]) * 50
        assert np.abs(acc_ef - target).max() <= \
            np.abs(acc_plain - target).max() + 1e-5
        # EF accumulation must track the true sum closely
        assert np.abs(acc_ef - target).max() / np.abs(target).max() < 0.01

    def test_compression_ratio(self):
        g = {"w": jnp.ones((1024,), jnp.float32)}
        q, scales, _ = compress_gradients(g, init_error_state(g))
        assert q["w"].dtype == jnp.int8   # 4× fewer bytes over the wire


class TestData:
    def test_deterministic_restart(self):
        cfg = DataConfig(vocab_size=100, seq_len=32, global_batch=4, seed=7)
        it = DataIterator(cfg)
        batches = [next(it) for _ in range(5)]
        it2 = DataIterator(cfg)
        it2.restore({"step": 3})
        b3 = next(it2)
        np.testing.assert_array_equal(batches[3]["tokens"], b3["tokens"])

    def test_local_correlation(self):
        cfg = DataConfig(vocab_size=1000, seq_len=256, global_batch=8)
        b = markov_batch(cfg, 0)
        diffs = np.abs(np.diff(b["tokens"].astype(np.int64), axis=1))
        diffs = np.minimum(diffs, 1000 - diffs)
        # most steps stay within the band
        assert (diffs <= cfg.bandwidth).mean() > 0.8

    def test_labels_are_shifted_tokens(self):
        cfg = DataConfig(vocab_size=50, seq_len=16, global_batch=2)
        b = markov_batch(cfg, 1)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


class TestCheckpoint:
    def test_atomic_roundtrip(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        tree = {"a": jnp.arange(10, dtype=jnp.float32),
                "nested": {"b": jnp.ones((3, 4))}}
        mgr.save(5, tree, extra={"step": 5})
        restored, extra = mgr.restore(tree)
        assert extra["step"] == 5
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.arange(10, dtype=np.float32))

    def test_corruption_falls_back(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        tree = {"a": jnp.arange(4, dtype=jnp.float32)}
        mgr.save(1, tree)
        mgr.save(2, jax.tree.map(lambda x: x + 1, tree))
        # corrupt step 2
        victim = next((tmp_path / "step_00000002").glob("*.npy"))
        data = np.load(victim)
        np.save(victim, data + 99)
        restored, _ = mgr.restore(tree)
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.arange(4, dtype=np.float32))

    def test_gc_keeps_recent(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        tree = {"a": jnp.zeros(2)}
        for s in (1, 2, 3, 4):
            mgr.save(s, tree)
        assert mgr.all_steps() == [3, 4]

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        tree = {"a": jnp.arange(6, dtype=jnp.float32)}
        mgr.save_async(7, tree, extra={"step": 7})
        mgr.wait()
        assert mgr.latest_step() == 7


class TestFaultTolerance:
    def test_crash_and_restart_resumes(self, tmp_path):
        """Inject a hard crash mid-training; the restarted run must resume
        from the checkpoint and converge to the same final state as an
        uninterrupted run (bit-exact data resume)."""
        env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
        base = [sys.executable, "-m", "repro.launch.train",
                "--arch", "minicpm-2b", "--reduced", "--steps", "12",
                "--global-batch", "2", "--seq", "64", "--ckpt-every", "4"]
        crash_dir = tmp_path / "crash"
        p = subprocess.run(base + ["--ckpt-dir", str(crash_dir),
                                   "--fail-at-step", "6"],
                           env=env, capture_output=True, text=True,
                           timeout=600)
        assert p.returncode == 17, p.stderr[-800:]
        p2 = subprocess.run(base + ["--ckpt-dir", str(crash_dir)],
                            env=env, capture_output=True, text=True,
                            timeout=600)
        assert p2.returncode == 0, p2.stderr[-800:]
        assert "[restore] resumed from step 4" in p2.stdout

        clean_dir = tmp_path / "clean"
        p3 = subprocess.run(base + ["--ckpt-dir", str(clean_dir)],
                            env=env, capture_output=True, text=True,
                            timeout=600)
        assert p3.returncode == 0, p3.stderr[-800:]

        final_resumed = p2.stdout.strip().splitlines()[-1]
        final_clean = p3.stdout.strip().splitlines()[-1]
        # "final loss: X (first: Y)" → compare X (bit-exact resume)
        assert final_resumed.split()[2] == final_clean.split()[2], \
            (final_resumed, final_clean)


class TestHLOAnalyzer:
    def test_scan_trip_count_scaling(self):
        """The analyzer must multiply while-body FLOPs by the trip count."""
        def step(w, x):
            def body(h, wi):
                return jnp.tanh(h @ wi), ()
            h, _ = jax.lax.scan(body, x, w)
            return h.sum()
        n_layers, dim = 6, 64
        w = jnp.ones((n_layers, dim, dim))
        x = jnp.ones((8, dim))
        compiled = jax.jit(step).lower(w, x).compile()
        stats = H.analyze_hlo_text(compiled.as_text())
        expected = 2 * 8 * dim * dim * n_layers
        assert abs(stats["dot_flops_per_device"] - expected) / expected < 0.01

    def test_collective_detection(self):
        txt = """
ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  %ag = f32[32,16]{1,0} all-gather(%p), replica_groups={{0,1}}, dimensions={0}
  %ar = f32[16,16]{1,0} all-reduce(%p), to_apply=%add
  ROOT %r = f32[16,16]{1,0} add(%p, %p)
}
"""
        stats = H.analyze_hlo_text(txt)
        assert stats["collective_counts"].get("all-gather") == 1
        assert stats["collective_counts"].get("all-reduce") == 1
        ag = 32 * 16 * 4
        ar = 16 * 16 * 4 * 2   # ring all-reduce ≈ 2× payload
        assert stats["collective_bytes_by_kind"]["all-gather"] == ag
        assert stats["collective_bytes_by_kind"]["all-reduce"] == ar


@pytest.mark.slow
class TestSmallMeshCompile:
    """Lower + compile representative archs on an 8-device forced-host mesh —
    the fast CI version of the 512-chip dry run (subprocess because device
    count is locked at first jax init)."""

    @pytest.mark.parametrize("arch,shape", [
        ("minicpm-2b", "train_4k"),
        ("mamba2-1.3b", "decode_32k"),
    ])
    def test_cell_compiles_on_8_devices(self, arch, shape, tmp_path):
        code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import repro.launch.mesh as M
def small(*, multi_pod=False):
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
M.make_production_mesh = small
import repro.launch.dryrun as D
import dataclasses, repro.configs as C
from repro.models.config import SHAPES
cfg = C.get_reduced("{arch}")
import repro.configs
repro.configs.get_config = lambda a: cfg
SHAPES["{shape}"] = dataclasses.replace(
    SHAPES["{shape}"], seq_len=256, global_batch=4)
r = D.lower_cell("{arch}", "{shape}", multi_pod=False)
assert r["status"] == "ok", r
print("COMPILED", r["chips"])
"""
        env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        assert "COMPILED 8" in p.stdout
