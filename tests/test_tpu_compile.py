"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described TPU v5e: the STaMP linears at Llama-3-8B widths and at
Granite-4.0-H-Small's Mamba projections, its grouped expert kernel, the
paged attention at the benchmark cell's cache (Mistral-NeMo-12B) and at
DeepSeek-7B's multi-head widths.

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
from repro.kernels import paged_attention as PA
from repro.serving import paged_kvcache as PKV
from repro.serving.kvcache import KVCacheConfig

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


# Granite-4.0-H-Small (configs/granite_4_0_h_small.py): its Mamba-2
# in_proj (4096 -> z | x | B | C | dt = 16768) and out_proj (8192 -> 4096)
# through the single STaMP kernel, and the grouped expert kernel over the
# ragged row tiles of its 18 held SwiGLU experts (4096 -> 768 -> 4096): 98
# tiles of 32 rows for a prefill region of two 128-token chunk rows, 21
# for the 8 decode slots, with an expert's whole width in one f tile
G_D, G_IN, G_DI, G_F, G_HELD, G_BM = 4096, 16768, 8192, 768, 18, 32


@pytest.mark.parametrize("k,n", [(G_D, G_IN), (G_DI, G_D)],
                         ids=["in_proj", "out_proj"])
def test_granite_mamba_projection(one_chip, k, n):
    txt = _compile(
        one_chip,
        lambda x, *w: SM.stamp_quant_matmul_pallas(x, *w, **STAMP),
        ((2, CHUNK, k), jnp.bfloat16), *_weights(k, n))
    assert "tpu_custom_call" in txt


def _expert_stack(k, n):
    return [((G_HELD, k, n), jnp.int8)] + [((G_HELD, 1, n), jnp.float32)] * 2


@pytest.mark.parametrize("tiles", [98, 21], ids=["prefill", "decode"])
def test_grouped_expert_kernel(one_chip, tiles):
    rows = tiles * G_BM
    txt = _compile(
        one_chip,
        lambda t, x, sx, zx, *w: SM.stamp_quant_ragged_matmul_pallas(
            t, x, sx, zx, *w, block_m=G_BM, block_f=G_F, interpret=False),
        ((3 * tiles,), jnp.int32), ((rows, G_D), jnp.int8),
        ((rows, 1), jnp.float32), ((rows, 1), jnp.float32),
        *_expert_stack(G_D, G_F), *_expert_stack(G_D, G_F),
        *_expert_stack(G_F, G_D))
    assert "moe_grouped_matmul" in txt


# the paged KV cache of mistral-nemo-12b-l20.long_prompt: pages of 4 tokens,
# a 4-token int8 sink, 8 slots of max_seq 3136 (tables 1 + 783), chunk
# rows of 128 tokens; the other geometries the serving path can hand the
# kernel: the page sizes a calibrated sink gives (serve.py's 16, or the
# sink's own size), and head_dim 64 (four kv heads to a 128-lane tile)
PAGE, SINK, SLOTS, MAX_SEQ = 4, 4, 8, 3136


def _compile_paged(one_chip, kv_heads, head_dim, rep, page, sink, max_seq,
                   chunk_rows):
    lo_per = -(-(max_seq - sink) // page)
    pcfg = PKV.PagedCacheConfig(
        block_size=page, num_lo_blocks=SLOTS * lo_per + 1,
        num_hi_blocks=SLOTS * (sink // page) + 1, max_blocks_per_seq=lo_per,
        quant=KVCacheConfig(quantized=True, num_hi=sink))
    entry = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda: PKV.init_pools(1, kv_heads, head_dim, pcfg)))
    spans = chunk_rows + SLOTS
    h = kv_heads * rep

    def attend(e, q_pf, q_dec, k_pf, v_pf, cached, ht, lt):
        if chunk_rows:
            return PA.paged_ragged_attention(e, q_pf, q_dec, k_pf, v_pf,
                                             cached, ht, lt, page,
                                             interpret=False)
        return PA.paged_decode_attention(e, q_dec, cached, ht, lt, page,
                                         interpret=False)

    args = [jax.ShapeDtypeStruct(sh, d, sharding=one_chip) for sh, d in (
        ((chunk_rows, CHUNK, h, head_dim), jnp.bfloat16),
        ((SLOTS, 1, h, head_dim), jnp.bfloat16),
        ((chunk_rows, CHUNK, kv_heads, head_dim), jnp.bfloat16),
        ((chunk_rows, CHUNK, kv_heads, head_dim), jnp.bfloat16),
        ((spans,), jnp.int32),
        ((spans, sink // page), jnp.int32),
        ((spans, lo_per), jnp.int32))]
    return jax.jit(attend).lower(entry, *args).compile().as_text()


@pytest.mark.parametrize("kv_heads,head_dim,rep,page,sink,max_seq,rows", [
    # Mistral-NeMo-12B: GQA, 2 chunk rows + the decode slots
    (8, HEAD_DIM, 4, PAGE, SINK, MAX_SEQ, 2),
    (8, HEAD_DIM, 4, PAGE, SINK, MAX_SEQ, 0),   # decode slots alone
    (32, HEAD_DIM, 1, PAGE, SINK, MAX_SEQ, 2),  # DeepSeek-7B: MHA (rep 1)
    # Llama-3-8B (and every kv-8 GQA model) at pages of 8 and 16 tokens
    (8, HEAD_DIM, 4, 8, 8, MAX_SEQ, 2),
    (8, HEAD_DIM, 4, 16, 64, MAX_SEQ, 2),
    (8, HEAD_DIM, 4, 16, 16, MAX_SEQ, 0),
    (32, HEAD_DIM, 1, 16, 16, MAX_SEQ, 2),      # DeepSeek-7B, pages of 16
    # MiniCPM-2B: head_dim 64, 36 kv heads (MHA), pages of 4 and 16
    (36, 64, 1, PAGE, SINK, 2048, 2),
    (36, 64, 1, 16, 16, 2048, 2),
], ids=["nemo_ragged", "nemo_decode", "deepseek_mha_ragged",
        "llama3_page8_ragged", "llama3_page16_ragged",
        "llama3_page16_decode", "deepseek_page16_ragged",
        "minicpm_hd64_ragged", "minicpm_hd64_page16_ragged"])
def test_paged_attention(one_chip, kv_heads, head_dim, rep, page, sink,
                         max_seq, rows):
    assert PA.compiles_for(page, kv_heads, head_dim)
    txt = _compile_paged(one_chip, kv_heads, head_dim, rep, page, sink,
                         max_seq, rows)
    assert "tpu_custom_call" in txt


def test_paged_attention_under_highest_default_precision(one_chip):
    """Served bf16 queries compile under a process-wide
    ``default_matmul_precision("highest")``: the kernel states its
    matmuls' precision (bf16 at f32 contract precision is refused)."""
    with jax.default_matmul_precision("highest"):
        txt = _compile_paged(one_chip, 8, HEAD_DIM, 4, PAGE, SINK, MAX_SEQ, 0)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("page", [12, 24])
def test_paged_attention_refused_page_sizes(one_chip, monkeypatch, page):
    """Pages whose code rows (12 tokens) or params rows (24 tokens: 12)
    are neither whole 8-row tiles nor fewer than 8 are what the TPU
    compiler refuses, so `compiles_for` sends them to the XLA fallback
    (and the kernel raises before it reaches the compiler)."""
    assert not PA.compiles_for(page, 8, HEAD_DIM)
    with pytest.raises(ValueError, match="block_size"):
        _compile_paged(one_chip, 8, HEAD_DIM, 4, page, page, MAX_SEQ, 0)
    monkeypatch.setattr(PA, "unsupported", lambda *a, **k: None)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile_paged(one_chip, 8, HEAD_DIM, 4, page, page, MAX_SEQ, 0)
