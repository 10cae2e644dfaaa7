"""The layer scan's prepared weights are read in place: every integer
kernel called with a layer stack and an index returns bit for bit what it
returns for that layer's own arrays, and the serving engine counts the
prepared-weight operands its steps read from stacks (all of them) and from
per-layer slices (none)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import granite_4_0_h_small
from repro.core.stamp import StampConfig, prepare_linear, token_quantize
from repro.kernels import ops
from repro.kernels import stamp_matmul as SM
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving import kvcache as KV
from repro.serving.engine import PagedEngineConfig, PagedServingEngine

jax.config.update("jax_platform_name", "cpu")

LAYERS = 3
STAMP = dict(num_hi=4, interpret=True)


def _stack(shape, seed):
    """A prepared (LAYERS, *shape) weight stack: codes, scale, zero point."""
    w = np.random.default_rng(seed).normal(size=(LAYERS, *shape)) * 0.05
    p = prepare_linear(jnp.asarray(w, jnp.float32), bits=8)
    return p.qw, p.sw, p.zw


def _act(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _single(stack, layer):
    return ops.stamp_quant_matmul(_act((2, 16, 64)), *stack, layer=layer,
                                  **STAMP)


def _headsplit(stack, layer):
    return ops.stamp_quant_matmul(_act((2, 16, 4, 16)), *stack, layer=layer,
                                  **STAMP)


def _dual(stacks, layer):
    gate, up = stacks
    return ops.stamp_quant_dual_matmul(_act((2, 16, 64)), *gate, *up,
                                       layer=layer, **STAMP)


def _decode(stack, layer):
    return ops.stamp_decode_matmul(_act((4, 64)), *stack, layer=layer,
                                   interpret=True)


def _grouped(stacks, layer):
    # experts 0, 1 and 3 hold 8, 5 and 8 rows, then an empty tail tile
    rows = jnp.asarray([8, 5, 8, 0], jnp.int32)
    table = SM.tile_table(rows, jnp.asarray([0, 1, 3, 3], jnp.int32))
    qx, sx, zx = token_quantize(_act((32, 64)))
    return ops.stamp_quant_ragged_matmul(
        table, qx, sx, zx, *(a for s in stacks for a in s), layer=layer,
        block_m=8, block_f=64, interpret=True)


# kernel, its prepared weight stacks (per layer: (K, N), or (E, K, N))
KERNELS = {
    "single": (_single, lambda: _stack((64, 256), 1)),
    "head_split": (_headsplit, lambda: _stack((64, 256), 2)),
    "dual": (_dual, lambda: (_stack((64, 256), 3), _stack((64, 256), 4))),
    "decode": (_decode, lambda: _stack((64, 512), 5)),
    "grouped": (_grouped, lambda: (_stack((4, 64, 128), 6),
                                   _stack((4, 64, 128), 7),
                                   _stack((4, 128, 64), 8))),
}


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_stack_read_in_place_equals_layer(kernel, layer):
    call, make = KERNELS[kernel]
    stacks = make()
    own = jax.tree.map(lambda a: a[layer], stacks)
    got = call(stacks, jnp.int32(layer))
    want = call(own, None)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_stack_without_layer_refused():
    stack = _stack((64, 256), 1)
    with pytest.raises(ValueError, match="layer index"):
        _single(stack, None)
    with pytest.raises(ValueError, match="layer index"):
        _single(jax.tree.map(lambda a: a[0], stack), jnp.int32(0))


# ---------------------------------------------------------------------------
# the engine's weight-read counters
# ---------------------------------------------------------------------------

DENSE = ModelConfig(name="in-place-test", family="dense", num_layers=2,
                    d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                    vocab_size=128)
FUSED = StampConfig(num_hi_tokens=8, execution="fused")
QUANT = KV.KVCacheConfig(quantized=True, num_hi=16)


def _serve(cfg, prompt_lens, max_new, seed=0):
    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    eng = PagedServingEngine(
        params, cfg, lm.ServeConfig(stamp=FUSED, kv=QUANT),
        PagedEngineConfig(max_slots=2, prefill_chunk=16, max_seq=64,
                          block_size=16))
    rng = np.random.default_rng(seed)
    for n in prompt_lens:
        eng.submit(rng.integers(0, cfg.vocab_size, n), max_new)
    eng.run()
    return eng


def _prefill_steps(eng) -> int:
    return len({e.step for e in eng.events if e.kind == "prefill_chunk"})


# operands per layer of a unified step with chunk rows (prefill kernels +
# decode kernels) and of an all-decode step
@pytest.mark.parametrize("cfg,prefill,decode", [
    # qkv, out-proj, gate/up (two), down
    (DENSE, 5 + 5, 5),
    # a layer of either mixer: two projections (in/out, or qkv/out), the
    # grouped experts (three), the shared expert's gate/up (two) and down
    (granite_4_0_h_small.reduced(), 8 + 8, 8),
], ids=["dense", "granite"])
def test_engine_counts_weight_reads(cfg, prefill, decode):
    eng = _serve(cfg, (20, 40), max_new=6)
    st = eng.stats
    steps = st["device_dispatches"]
    n_pf = _prefill_steps(eng)
    assert 0 < n_pf < steps
    assert st["weight_reads_sliced"] == 0
    assert st["weight_reads_in_place"] == cfg.num_layers * (
        n_pf * prefill + (steps - n_pf) * decode)
