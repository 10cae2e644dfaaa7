"""Granite-4.0-H's MoE layer and attention scale at the `reduced()` size
on the CPU (`configs/granite_4_0_h_small.py`):

* expert shares: four chips' shares of the experts, with the shared
  expert counted once, add up to the uncut layer (float and fused);
* dropless: a router rigged to send every token to one expert loses
  none, and the grouped int8 kernel matches the dense loop;
* the paged-attention query scale is the model's (1/128 at Granite's
  widths); where the model states none it is ``1/sqrt(head_dim)``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.configs import granite_4_0_h_small as G
from repro.models import layers as L
from repro.models import lm
from repro.models.config import LayerSpec

CHUNK = 16


def _stamp():
    from repro.core.stamp import StampConfig
    return StampConfig(seq_transform="dwt", levels=3, num_hi_tokens=4,
                       hi_bits=8, lo_bits=4, skip_first_token=True,
                       execution="fused")


def _layer(cfg, fused: bool):
    p = lm.init_layer_params(jax.random.PRNGKey(3), LayerSpec("mamba",
                                                               "moe_dense"),
                             cfg)
    if fused:
        p = lm.prepare_fused_weights(p, _stamp())
    return p


@pytest.mark.parametrize("fused", [False, True], ids=["float", "fused"])
def test_expert_shares_add_up_to_the_uncut_layer(fused):
    """Four chips' shares of 2 experts each (offsets 0, 2, 4, 6): each
    share's experts are the uncut layer's (drawn by global index), and the
    shares' outputs, with the shared expert counted once, add up to the
    uncut layer's to float32 rounding."""
    whole = dataclasses.replace(G.reduced(), num_local_experts=0,
                                expert_offset=0)
    shares = [dataclasses.replace(whole, num_local_experts=2,
                                  expert_offset=o) for o in (0, 2, 4, 6)]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, CHUNK, whole.d_model),
                          jnp.float32)
    spec = LayerSpec("mamba", "moe_dense")
    stamp = _stamp() if fused else None
    pw = _layer(whole, fused)

    def ffn(p, cfg):
        return lm.ffn_block(p, x, spec, cfg, stamp=stamp) - x

    parts = []
    for cfg in shares:
        p = _layer(cfg, fused)
        o = cfg.expert_offset
        for k in ("we_gate", "we_up", "we_down"):
            for a, b in zip(jax.tree.leaves(p[k]), jax.tree.leaves(pw[k])):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b[o:o + 2]))
        parts.append(ffn(p, cfg))
    shared_only = {"ln2": pw["ln2"], "wi_gate": pw["dwi_gate"],
                   "wi_up": pw["dwi_up"], "wo_mlp": pw["dwo_mlp"]}
    shared = lm.ffn_block(shared_only, x, LayerSpec("mamba", "mlp"), whole,
                          stamp=stamp) - x
    got = sum(parts) - (len(parts) - 1) * shared
    want = ffn(pw, whole)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.abs(want).max()))


def test_dropless_routing_keeps_every_row_of_one_expert():
    """A router rigged to send every token's first choice to expert 3:
    all 96 tokens land on one held expert, three tiles of rows — a
    capacity-routed layer would drop most of them.  None is dropped, and
    the grouped int8 kernel matches the dense loop over the same float
    experts within the int8 codes' rounding."""
    cfg = G.reduced()                      # holds experts 2-5 of 8, top 3
    p = _layer(cfg, fused=False)
    t, d = 96, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(5), (t, d), jnp.float32)
    gate_w = p["gate_w"].at[:, 3].set(0.0).at[0, 3].set(1e3)
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 1.0)    # every token picks 3
    valid = jnp.ones((t,), bool)
    disp = L.moe_route_dropless(x, gate_w, cfg.experts_per_token, valid,
                                offset=cfg.expert_offset,
                                held=cfg.held_experts, block_m=32)
    routed, held, groups, dropped = np.asarray(disp.counts).tolist()
    assert routed == t * cfg.experts_per_token and dropped == 0
    idx = np.asarray(L.moe_topk(x, gate_w, cfg.experts_per_token)[1])
    assert (idx[:, 0] == 3).all()
    assert held == int(((idx >= 2) & (idx < 6)).sum()) >= t
    pf = lm.prepare_fused_weights(p, _stamp())
    ws = [pf[k] for k in ("we_gate", "we_up", "we_down")]
    y, counts = L.moe_ffn_dropless_fused(
        x, gate_w, *ws, cfg.experts_per_token, valid,
        offset=cfg.expert_offset)
    want, c2 = L.moe_ffn_dropless(
        x, gate_w, p["we_gate"], p["we_up"], p["we_down"],
        cfg.experts_per_token, valid, offset=cfg.expert_offset)
    np.testing.assert_array_equal(np.asarray(counts)[:2],
                                  np.asarray(c2)[:2])
    assert int(counts[2]) == groups and int(counts[3]) == 0
    err = float(jnp.linalg.norm(y - want) / jnp.linalg.norm(want))
    assert err < 0.03          # 8-bit rows and SwiGLU codes, int8 weights
    # a token whose choices all lie elsewhere gets exactly nothing here
    far = jnp.zeros((1, d)).at[0, 0].set(1.0)
    gw = jnp.zeros_like(gate_w).at[0, 6:].set(1.0)
    y0, _ = L.moe_ffn_dropless_fused(far, gw, *ws, cfg.experts_per_token,
                                     jnp.ones((1,), bool),
                                     offset=cfg.expert_offset)
    assert not np.asarray(y0).any()


def test_paged_attention_takes_the_models_query_scale():
    """Granite's score scale is 1/head_dim (1/128 at its widths), not
    1/sqrt(head_dim); the kernel takes it, and a model that states none
    (NeMo) keeps 1/sqrt(head_dim) bit for bit."""
    from repro.kernels import paged_attention as PA
    from repro.serving import kvcache as KV
    from repro.serving import paged_kvcache as PKV
    assert G.CONFIG.attention_multiplier == 1.0 / G.CONFIG.head_dim == 1 / 128
    bs, g, rep, hd, num_hi = 4, 2, 2, 32, 4
    lengths = [9, 17, 6]
    width = 4
    cfg = PKV.PagedCacheConfig(
        block_size=bs, num_lo_blocks=1 + len(lengths) * width,
        num_hi_blocks=1 + len(lengths), max_blocks_per_seq=width,
        quant=KV.KVCacheConfig(quantized=True, num_hi=num_hi))
    rng = np.random.default_rng(0)
    entry = {k: a[0] for k, a in PKV.init_pools(1, g, hd, cfg).items()}
    hts, lts = [], []
    for i, ln in enumerate(lengths):
        hp = [1 + i]
        lp = list(range(1 + i * width, 1 + i * width
                        + -(-(ln - num_hi) // bs)))
        hts.append(hp)
        lts.append(lp + [0] * (width - len(lp)))
        k = jnp.asarray(rng.normal(size=(1, ln, g, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, ln, g, hd)), jnp.float32)
        where = [PKV.token_page_index(pos, cfg) for pos in range(ln)]
        pages = [(hp if hi else lp)[j] for hi, j, _ in where]
        entry = PKV.write_chunk(
            entry, k, v, jnp.asarray(pages, jnp.int32),
            jnp.asarray([o for _, _, o in where], jnp.int32),
            jnp.asarray([hi for hi, _, _ in where], bool), cfg)
    ht, lt = jnp.asarray(hts, jnp.int32), jnp.asarray(lts, jnp.int32)
    n = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, g * rep, hd)), jnp.float32)
    segs = PKV.gather_segments(entry, ht, lt, cfg, jnp.float32)
    out = {}
    for scale in (None, 1.0 / hd):
        got = PA.paged_decode_attention(entry, q, n, ht, lt, bs,
                                        scale=scale, interpret=True)
        want = L.decode_attention_segments(q, segs, length=n, scale=scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        out[scale] = np.asarray(got)
    assert np.abs(out[None] - out[1.0 / hd]).max() > 1e-2
    # no stated scale is 1/sqrt(head_dim), bit for bit, on both paths
    np.testing.assert_array_equal(
        np.asarray(PA._scaled(q, None)),
        np.asarray(PA._scaled(q, 1.0 / np.sqrt(hd))))
    np.testing.assert_array_equal(
        np.asarray(L.decode_attention_segments(q, segs, length=n)),
        np.asarray(L.decode_attention_segments(
            q, segs, length=n, scale=1.0 / np.sqrt(hd))))
