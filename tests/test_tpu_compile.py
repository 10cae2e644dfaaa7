"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described TPU v5e, at Llama-3-8B widths.

Nothing runs: each test lowers one kernel for a chip that is described, not
attached, and compiles it with the TPU compiler, which refuses what the
chip would refuse (tiling-misaligned slices, unsupported vector layouts,
more scoped VMEM than the kernel may use).  Interpret-mode tests cannot see
those faults.  The topology is described inside a module fixture, never at
import: only one process may load the TPU compiler's library at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_matmul as DM, stamp_matmul as SM

# Llama-3-8B (configs/llama3_8b.py) with the serving engine's prefill chunk
D_MODEL, D_FF, QKV_N, HEADS, HEAD_DIM = 4096, 14336, 6144, 32, 128
CHUNK = 128
DECODE_ROWS = 8
STAMP = dict(levels=3, skip_first=True, num_hi=4, hi_bits=8, lo_bits=4,
             interpret=False)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")     # else the compiler logs to /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        mp.undo()


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _weights(k, n):
    return [((k, n), jnp.int8)] + [((1, n), jnp.float32)] * 3


@pytest.mark.parametrize("transform", ["dwt", "wht"])
def test_merged_qkv(one_chip, transform):
    txt = _compile(
        one_chip,
        lambda x, *w: SM.stamp_quant_matmul_pallas(
            x, *w, transform=transform, **STAMP),
        ((2, CHUNK, D_MODEL), jnp.bfloat16), *_weights(D_MODEL, QKV_N))
    assert "tpu_custom_call" in txt


def test_head_split_out_proj(one_chip):
    txt = _compile(
        one_chip,
        lambda x, *w: SM.stamp_quant_matmul_pallas(x, *w, **STAMP),
        ((2, CHUNK, HEADS, HEAD_DIM), jnp.bfloat16),
        *_weights(D_MODEL, D_MODEL))
    assert "tpu_custom_call" in txt


def test_dual_gate_up(one_chip):
    txt = _compile(
        one_chip,
        lambda x, *w: SM.stamp_quant_dual_matmul_pallas(x, *w, **STAMP),
        ((2, CHUNK, D_MODEL), jnp.bfloat16),
        *_weights(D_MODEL, D_FF), *_weights(D_MODEL, D_FF))
    assert "tpu_custom_call" in txt


def test_down_proj(one_chip):
    txt = _compile(
        one_chip,
        lambda x, *w: SM.stamp_quant_matmul_pallas(x, *w, **STAMP),
        ((2, CHUNK, D_FF), jnp.bfloat16), *_weights(D_FF, D_MODEL))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("k,n", [(D_MODEL, QKV_N), (D_FF, D_MODEL)])
def test_decode_matmul(one_chip, k, n):
    txt = _compile(
        one_chip,
        lambda x, *w: DM.stamp_decode_matmul_pallas(x, *w, interpret=False),
        ((DECODE_ROWS, k), jnp.bfloat16), *_weights(k, n))
    assert "tpu_custom_call" in txt

