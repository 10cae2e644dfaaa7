"""The Pallas paged-attention kernel (`kernels/paged_attention.py`) in
interpret mode: its in-kernel f16 decode is exact, and the unified step's
attention through it equals the XLA fallback's (`gather_segments` +
`decode_attention_segments` / `chunked_prefill_attention`) at small
widths — page sizes 4 and 16, first chunks, prefixes ending mid-page and on
a page boundary, idle slots, and spans longer than one step of the page
walk."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

jax.config.update("jax_platform_name", "cpu")

from repro.kernels import paged_attention as PA
from repro.models import layers as L
from repro.serving import kvcache as KV
from repro.serving import paged_kvcache as PKV


def test_in_kernel_f16_decode_is_exact():
    """Every finite float16 bit pattern, subnormals and both zeros
    included, decodes inside a kernel to exactly its float32 value."""
    bits = np.arange(1 << 16, dtype=np.uint32).astype(np.uint16)
    finite = np.isfinite(bits.view(np.float16))
    words = jnp.asarray(bits.astype(np.int32).reshape(512, 128))

    def kernel(h_ref, o_ref):
        o_ref[...] = PA.f16_bits_to_f32(h_ref[...])

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((512, 128), jnp.float32),
        interpret=True)(words)
    got = np.asarray(got).reshape(-1)[finite]
    want = bits.view(np.float16)[finite].astype(np.float32)
    # bit for bit: -0.0 keeps its sign, subnormals their last bit
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    assert (want[want != 0] != 0).all() and (np.abs(want) < 6.2e-5).sum() \
        > 2000                                  # subnormals were covered


@pytest.mark.parametrize("bs,kv,hd,tpu,ok", [
    (4, 8, 128, True, True), (8, 8, 128, True, True),
    (16, 8, 128, True, True), (48, 8, 128, True, True),
    (16, 36, 64, True, True), (16, 8, 32, True, True),
    (12, 8, 128, True, False),      # code rows: 12 of an 8-row tile
    (24, 8, 128, True, False),      # params rows: 12
    (2, 8, 128, False, False),      # fewer than four tokens a word
    (16, 1, 128, True, False),      # rows of 64 bytes: stored page-minor
    (16, 1, 128, False, True),      # ... which interpret mode takes
    (16, 8, 112, False, False),     # 56-byte heads straddle lane tiles
])
def test_one_geometry_rule(bs, kv, hd, tpu, ok):
    """`compiles_for` (routing) and the kernel's own refusal are the one
    rule `unsupported`; the TPU side matches what the compiler takes
    (tests/test_tpu_compile.py)."""
    why = PA.unsupported(bs, hd // 2, kv * hd // 2, tpu=tpu)
    assert (why is None) == ok
    if tpu:
        assert PA.compiles_for(bs, kv, hd) == ok


def _pools(bs, g, hd, lengths, num_hi, width, seed):
    """One layer's pools with every span's tokens written through its own
    (shuffled) pages: hi tables and lo tables, span-ordered."""
    cfg = PKV.PagedCacheConfig(
        block_size=bs, num_lo_blocks=1 + len(lengths) * width,
        num_hi_blocks=1 + len(lengths) * (num_hi // bs),
        max_blocks_per_seq=width,
        quant=KV.KVCacheConfig(quantized=True, num_hi=num_hi))
    rng = np.random.default_rng(seed)
    entry = {k: a[0] for k, a in PKV.init_pools(1, g, hd, cfg).items()}
    lo_free = list(rng.permutation(np.arange(1, cfg.num_lo_blocks)))
    nh = num_hi // bs
    hts, lts = [], []
    for i, ln in enumerate(lengths):
        hp = list(range(1 + i * nh, 1 + (i + 1) * nh))
        lp = [int(lo_free.pop()) for _ in range(-(-(ln - num_hi) // bs))] \
            if ln > num_hi else []
        hts.append(hp)
        lts.append(lp + [0] * (width - len(lp)))
        if ln == 0:
            continue
        k = jnp.asarray(rng.normal(size=(1, ln, g, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, ln, g, hd)), jnp.float32)
        where = [PKV.token_page_index(pos, cfg) for pos in range(ln)]
        pages = [(hp if hi else lp)[i] for hi, i, _ in where]
        entry = PKV.write_chunk(
            entry, k, v, jnp.asarray(pages, jnp.int32),
            jnp.asarray([o for _, _, o in where], jnp.int32),
            jnp.asarray([hi for hi, _, _ in where], bool), cfg)
    return cfg, entry, jnp.asarray(hts, jnp.int32), jnp.asarray(lts,
                                                                 jnp.int32)


# (block size, chunk rows' cached prefixes, decode slots' lengths):
# idle decode slots read the null page at length 1, and with the page walk
# cut to 32 tokens a step at page size 4 (128 at 16) the long spans take
# several steps
CASES = {
    "bs4_first_chunk_and_idle_slots": (4, [0, 0], [1, 1, 23, 1]),
    "bs4_prefix_mid_page": (4, [21, 46], [1, 37, 1, 18]),
    "bs4_prefix_on_page_boundary": (4, [24, 44], [17, 1, 40, 1]),
    "bs4_spans_over_several_steps": (4, [131, 0], [97, 1, 150, 66]),
    "bs16_first_chunk_and_idle_slots": (16, [0, 0], [1, 1, 33, 1]),
    "bs16_prefix_mid_page": (16, [21, 53], [1, 37, 1, 18]),
    "bs16_prefix_on_page_boundary": (16, [32, 64], [17, 1, 48, 1]),
    "bs16_spans_over_several_steps": (16, [300, 16], [290, 1, 161, 33]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_fallback(case, monkeypatch):
    bs, pf_cached, dec_lengths = CASES[case]
    monkeypatch.setattr(PA, "TILE_TOKENS", 32)
    g, rep, hd, c_len, num_hi = 8, 2, 32, 8, 16
    cached = pf_cached + dec_lengths
    width = max(-(-(max(cached) - num_hi) // bs), 1)
    cfg, entry, ht, lt = _pools(bs, g, hd, cached, num_hi, width,
                                seed=len(case))
    rng = np.random.default_rng(7)
    n_pf, s_slots, h = len(pf_cached), len(dec_lengths), g * rep
    q_pf = jnp.asarray(rng.normal(size=(n_pf, c_len, h, hd)), jnp.float32)
    k_pf = jnp.asarray(rng.normal(size=(n_pf, c_len, g, hd)), jnp.float32)
    v_pf = jnp.asarray(rng.normal(size=(n_pf, c_len, g, hd)), jnp.float32)
    q_dec = jnp.asarray(rng.normal(size=(s_slots, 1, h, hd)), jnp.float32)
    cached = jnp.asarray(cached, jnp.int32)
    out_pf, out_dec = PA.paged_ragged_attention(
        entry, q_pf, q_dec, k_pf, v_pf, cached, ht, lt, bs, interpret=True)
    segs = PKV.gather_segments(entry, ht[n_pf:], lt[n_pf:], cfg,
                               jnp.float32)
    ref_dec = L.decode_attention_segments(q_dec, segs,
                                          length=cached[n_pf:])
    segs = PKV.gather_segments(entry, ht[:n_pf], lt[:n_pf], cfg,
                               jnp.float32)
    ref_pf = L.chunked_prefill_attention(q_pf, segs, k_pf, v_pf,
                                         cached[:n_pf])
    np.testing.assert_allclose(np.asarray(out_pf), np.asarray(ref_pf),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out_dec), np.asarray(ref_dec),
                               atol=2e-5, rtol=2e-5)


def test_bf16_queries_stay_within_the_fallbacks_rounding():
    """In the served dtype both paths round queries and probabilities to
    bf16; the fallback also rounds every dequantised K/V to bf16, which
    the kernel does not.  Their outputs differ by that rounding alone."""
    bs, pf_cached, dec_lengths = CASES["bs4_prefix_mid_page"]
    g, rep, hd, c_len, num_hi = 8, 2, 32, 8, 16
    cached = pf_cached + dec_lengths
    cfg, entry, ht, lt = _pools(bs, g, hd, cached, num_hi, 16, seed=3)
    rng = np.random.default_rng(8)
    h = g * rep
    q_dec = jnp.asarray(rng.normal(size=(4, 1, h, hd)), jnp.bfloat16)
    cached = jnp.asarray(cached, jnp.int32)
    out = PA.paged_decode_attention(entry, q_dec, cached[2:], ht[2:],
                                    lt[2:], bs, interpret=True)
    segs = PKV.gather_segments(entry, ht[2:], lt[2:], cfg, jnp.bfloat16)
    ref = L.decode_attention_segments(q_dec, segs, length=cached[2:])
    exact = L.decode_attention_segments(
        q_dec.astype(jnp.float32),
        PKV.gather_segments(entry, ht[2:], lt[2:], cfg, jnp.float32),
        length=cached[2:])
    err_kernel = np.abs(np.asarray(out, np.float32) - np.asarray(exact))
    err_fallback = np.abs(np.asarray(ref, np.float32) - np.asarray(exact))
    # no coarser than the fallback: its error bounds the kernel's, to
    # within one bf16 rounding of the output (2⁻⁸ relative)
    scale = np.abs(np.asarray(exact)).max()
    assert err_kernel.max() <= err_fallback.max() + scale * 2.0 ** -8


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["fallback", "kernel"])
def test_engine_counts_the_pages_attention_reads(kernel):
    """The engine's ``attn_pages_walked`` counts what one attention layer
    reads a step: the whole span tables through the XLA fallback, only the
    spans' sink and own int4 pages through the kernel."""
    from repro.models import lm
    from repro.models.config import ModelConfig
    from repro.serving.engine import PagedEngineConfig, PagedServingEngine
    cfg = ModelConfig(name="pages-test", family="dense", num_layers=1,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=64)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    serve = lm.ServeConfig(kv=KV.KVCacheConfig(quantized=True, num_hi=8),
                           fused_cache_attention=kernel)
    eng = PagedServingEngine(params, cfg, serve, PagedEngineConfig(
        max_slots=2, prefill_chunk=16, max_seq=64, block_size=8))
    rng = np.random.default_rng(0)
    for n in (20, 9):
        eng.submit(rng.integers(0, cfg.vocab_size, n), 3)
    eng.run()
    lm.set_fused_cache_attention(False)
    st = eng.stats
    # every step: 2 decode slots + (bucketed) chunk rows, each reserving
    # 1 sink page and 7 int4 pages
    assert st["attn_pages_reserved"] > 0
    assert st["attn_pages_reserved"] % 8 == 0
    if kernel:
        assert 0 < st["attn_pages_walked"] < st["attn_pages_reserved"] / 2
    else:
        assert st["attn_pages_walked"] == st["attn_pages_reserved"]
