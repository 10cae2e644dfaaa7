"""Plain reference of a Granite-4.0-H hybrid (model type
``granitemoehybrid``) as the benchmark's configurations state it is
served: W4 weights, STaMP activations in prefill chunks, 8-bit
activations in decode, an int8/int4 KV cache for the attention layers, a
float32 SSM state, and bfloat16 activations between the operations.  It
imports nothing of the program under test and takes nothing the program
made; the quantizers, the sequence transform, the KV codes and the
packing of requests are `dense_llama.py`'s (the same statement of them),
loaded from beside this file.

The layer equations (d the hidden size, m_r the residual multiplier):

* embedding ``x₀ = E[tokens] · embedding_multiplier``; the head is tied:
  ``logits = rms(x_L) · Eᵀ / logits_scaling``;
* every layer ``h = x + m_r · mixer(rms(x))``, then
  ``x' = h + m_r · (moe(rms(h)) + shared(rms(h)))``;
* attention (``layer_types`` "attention"): GQA without positions (NoPE),
  scores times ``attention_multiplier``;
* Mamba-2 (one group): ``[z | x | B | C | dt] = in_proj(h)``, a causal
  depthwise conv of width ``mamba_d_conv`` with bias over ``[x | B | C]``
  then SiLU, ``dt = softplus(dt + dt_bias)``, ``A = −exp(A_log)`` per
  head, the recurrence ``s_t = exp(dt·A)·s_{t−1} + dt·x_t B_tᵀ`` run
  token by token (not chunked), ``y = C_t s_t + D ⊙ x_t``, then
  ``out_proj(rms(y ⊙ silu(z)))``;
* MoE: the router's logits over all ``published.num_local_experts``
  experts, the top ``num_experts_per_tok``, gates the softmax over those;
  each held expert ``[expert_offset, expert_offset + num_local_experts)``
  is a SwiGLU of width ``intermediate_size``, run on every token in a
  plain loop and weighted by the token's gate for it (zero where it chose
  another); choices held on other chips add nothing;
* the shared expert: a SwiGLU of width ``shared_intermediate_size``.

Weights follow the served model's seed layout: ``PRNGKey(seed)`` splits
into (embedding, -, -, periods, -), the period key into one per period of
ten, that into one per layer, and the layer key into 24 (``keys``).  A
Mamba layer draws in_proj, conv weight and out_proj from ``keys[0:3]``;
an attention layer wq, wk, wv, wo from ``keys[0:4]``; then each layer's
shared gate, up, down, the router and the experts' gate, up, down from the
next seven.  Expert ``e`` draws from ``fold_in(key, e)`` of its stack's
key; the conv bias, ``A_log`` and ``dt_bias`` from ``fold_in(layer key,
102 / 100 / 101)``.  Every linear is ``normal / sqrt(fan_in)`` in float32,
rounded to bfloat16, then int4 per output channel; the embedding is
``embedding_init_std · normal`` (0.02 where the file gives none), the
router ``normal / sqrt(d)``, the conv weight and bias
``U(±1/sqrt(mamba_d_conv))`` (Mamba-2's published init), all rounded to
bfloat16 as served, as are ``A_log`` and ``dt_bias``.

Where a served activation is quantized (``dense_llama``'s statement): the
prefill linears (qkv, out-proj, in_proj, out_proj, the shared expert's
gate/up and down) are STaMP over each chunk; the routed experts read the
chunk's STaMP round trip (transform, quantize, inverse), and route on it,
then its 8-bit per-token codes; their SwiGLU output is re-coded at 8 bits
per token before the down projection.  Decode quantizes every linear's
input, the experts' included, at 8 bits per token.

Departures, each within the rounding of the stated precisions: the
program re-codes the int4 weights as int8 for its kernels (an anchored
min-max that can move a weight by half an int8 step), computes the conv's
products and sums in bfloat16, and in chunked prefill rounds ``C·B`` to
bfloat16 inside the SSD; here those are float32.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _load_dense():
    name = "granite_reference_dense_llama"
    if name not in sys.modules:
        path = pathlib.Path(__file__).with_name("dense_llama.py")
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


D = _load_dense()
HIGHEST = D.HIGHEST
BLOCK = 4                     # requests a layer runs at once, so it fits
bf, _kv, _rms = D.bf, D._kv, D._rms
pack, Batch = D.pack, D.Batch


def _fq(x, bits):
    """``dense_llama``'s per-row min-max fake quantization; ``None``
    bits leave ``x`` as it is (the unquantized forward)."""
    return x if bits is None else D._fq(x, bits)


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    period: int
    nh: int
    kvh: int
    hd: int
    ff: int                   # shared expert width
    ef: int                   # routed expert width
    experts: int              # router outputs
    k: int
    held: int
    offset: int
    vocab: int
    eps: float
    weight_bits: int
    di: int
    n: int
    sh: int                   # SSM heads
    conv: int
    emb_mult: float
    res_mult: float
    attn_scale: float
    logits_scaling: float
    embed_std: float

    @classmethod
    def of(cls, c: dict) -> "Dims":
        nh, d = c["num_attention_heads"], c["hidden_size"]
        return cls(d=d, layers=c["num_hidden_layers"], period=10, nh=nh,
                   kvh=c["num_key_value_heads"],
                   hd=c.get("head_dim") or d // nh,
                   ff=c["shared_intermediate_size"],
                   ef=c["intermediate_size"],
                   experts=c["published"]["num_local_experts"],
                   k=c["num_experts_per_tok"],
                   held=c["num_local_experts"], offset=c["expert_offset"],
                   vocab=-(-c["vocab_size"] // 128) * 128,
                   eps=float(c["rms_norm_eps"]),
                   weight_bits=c["serving"]["weight_bits"],
                   di=c["mamba_expand"] * d, n=c["mamba_d_state"],
                   sh=c["mamba_n_heads"], conv=c["mamba_d_conv"],
                   emb_mult=float(c["embedding_multiplier"]),
                   res_mult=float(c["residual_multiplier"]),
                   attn_scale=float(c["attention_multiplier"]),
                   logits_scaling=float(c["logits_scaling"]),
                   embed_std=float(c.get("embedding_init_std", 0.02)))

    def is_attn(self, layer: int) -> bool:
        return layer % self.period == self.period // 2


@dataclasses.dataclass(frozen=True)
class Precision:
    """``dense_llama.Precision``'s fields, any of which ``None`` (no
    quantization), plus the weights' bits."""

    levels: int
    skip_first: bool
    num_hi: int
    hi_bits: object
    lo_bits: object
    decode_bits: object
    kv_num_hi: int
    kv_hi_bits: object
    kv_lo_bits: object
    weight_bits: object
    expert_bits: object       # the routed experts' input and SwiGLU codes

    @classmethod
    def of(cls, c: dict) -> "Precision":
        p = D.Precision.of(c)
        return cls(**dataclasses.asdict(p),
                   weight_bits=c["serving"]["weight_bits"], expert_bits=8)

    def lowered(self) -> "Precision":
        """The next precision down: every 8-bit activation and cache code
        at 4 bits (int4 for int8).  The control of the check."""
        def low(b):
            return 4 if b == 8 else b
        return dataclasses.replace(
            self, hi_bits=low(self.hi_bits),
            decode_bits=low(self.decode_bits),
            kv_hi_bits=low(self.kv_hi_bits),
            expert_bits=low(self.expert_bits))

    def unquantized(self) -> "Precision":
        """The same forward with no quantizer at all: float weights,
        activations and cache (bfloat16 where served)."""
        return dataclasses.replace(
            self, hi_bits=None, lo_bits=None, decode_bits=None,
            kv_hi_bits=None, kv_lo_bits=None, weight_bits=None,
            expert_bits=None)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _w(key, din: int, dout: int, bits, lead=()):
    """``normal / sqrt(din)`` rounded to bfloat16, then int4 codes per
    output channel (``bits`` None: as drawn)."""
    w = bf(jax.random.normal(key, lead + (din, dout), jnp.float32)
           * (1.0 / np.sqrt(din)))
    if bits is None:
        return w
    n = float(2 ** bits - 1)
    mn = jnp.min(w, axis=-2, keepdims=True)
    mx = jnp.max(w, axis=-2, keepdims=True)
    s = jnp.maximum((mx - mn) / n, 1e-8)
    z = jnp.round(-mn / s)
    q = jnp.clip(jnp.round(w / s) + z, 0.0, n)
    return (q - z) * s


def _experts(key, ids, din: int, dout: int, bits):
    return jax.vmap(lambda i: _w(jax.random.fold_in(key, i), din, dout,
                                 bits))(ids)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def layer_weights(layer_key, dims: Dims, attn: bool, bits) -> dict:
    keys = jax.random.split(layer_key, 24)
    d, b = dims.d, bits
    w: dict = {}
    if attn:
        qd, kd = dims.nh * dims.hd, dims.kvh * dims.hd
        w["wqkv"] = jnp.concatenate([_w(keys[0], d, qd, b),
                                     _w(keys[1], d, kd, b),
                                     _w(keys[2], d, kd, b)], axis=1)
        w["wo"] = _w(keys[3], qd, d, b)
        i = 4
    else:
        cd = dims.di + 2 * dims.n
        w["in"] = _w(keys[0], d, 2 * dims.di + 2 * dims.n + dims.sh, b)
        bound = 1.0 / np.sqrt(dims.conv)
        w["conv_w"] = bf(jax.random.uniform(keys[1], (dims.conv, cd),
                                            jnp.float32, -bound, bound))
        w["out"] = _w(keys[2], dims.di, d, b)
        w["conv_b"] = bf(jax.random.uniform(
            jax.random.fold_in(layer_key, 102), (cd,), jnp.float32, -bound,
            bound))
        a = jax.random.uniform(jax.random.fold_in(layer_key, 100),
                               (dims.sh,), jnp.float32, 1.0, 16.0)
        u = jax.random.uniform(jax.random.fold_in(layer_key, 101),
                               (dims.sh,), jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (np.log(0.1) - np.log(0.001))
                                 + np.log(0.001)), 1e-4)
        w["a"] = -jnp.exp(bf(jnp.log(a)))
        w["dt_bias"] = bf(dt + jnp.log(-jnp.expm1(-dt)))
        i = 3
    w["sg"] = _w(keys[i], d, dims.ff, b)
    w["su"] = _w(keys[i + 1], d, dims.ff, b)
    w["sd"] = _w(keys[i + 2], dims.ff, d, b)
    w["router"] = bf(jax.random.normal(keys[i + 3], (d, dims.experts),
                                       jnp.float32) * (1.0 / np.sqrt(d)))
    ids = dims.offset + jnp.arange(dims.held)
    w["eg"] = _experts(keys[i + 4], ids, d, dims.ef, b)
    w["eu"] = _experts(keys[i + 5], ids, d, dims.ef, b)
    w["ed"] = _experts(keys[i + 6], ids, dims.ef, d, b)
    return w


@functools.partial(jax.jit, static_argnums=1)
def _table(key, dims: Dims):
    return bf(jax.random.normal(key, (dims.vocab, dims.d), jnp.float32)
              * dims.embed_std)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _stamp_t(x, lm, bits_rows):
    """Transformed and quantized chunk rows ``(n, C, K)``."""
    t = jnp.einsum("ts,nsk->ntk", lm, x, precision=HIGHEST)
    return t if bits_rows is None else D._fq(t, bits_rows)


def _stamp_lin(x, w, lm, rows):
    """A STaMP linear over chunk rows ``(n, C, K)``, rounded to bfloat16."""
    return bf(D._stamp_out(_stamp_t(x, lm, rows), w, lm))


def _dec_lin(x, w, bits):
    return bf(jnp.einsum("...k,kd->...d", _fq(x, bits), w, precision=HIGHEST))


def _moe(hq, w, dims: Dims, bits):
    """Dropless top-k MoE over the held experts as a plain masked loop;
    ``hq`` (..., d) is what the router and the experts read."""
    lead = hq.shape[:-1]
    x = hq.reshape(-1, dims.d)
    logits = jnp.einsum("td,de->te", x, w["router"], precision=HIGHEST)
    top, idx = jax.lax.top_k(logits, dims.k)
    gates = jax.nn.softmax(top, axis=-1)
    onehot = jax.nn.one_hot(idx - dims.offset, dims.held, dtype=jnp.float32)
    g_e = jnp.einsum("tk,tke->et", gates, onehot)          # 0 where unheld
    xq = _fq(x, bits)

    def one(acc, e):
        wg, wu, wd, g = e
        a = jax.nn.silu(jnp.dot(xq, wg, precision=HIGHEST)) \
            * jnp.dot(xq, wu, precision=HIGHEST)
        return acc + g[:, None] * jnp.dot(_fq(a, bits), wd,
                                          precision=HIGHEST), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["eg"], w["eu"], w["ed"], g_e))
    return bf(y).reshape(*lead, dims.d)


def _residual(x, y, dims: Dims):
    return bf(x + bf(y * dims.res_mult))


def _ffn(w, x_pf, x_dec, lm, rows, dims: Dims, p: Precision):
    b, nc, c, d = x_pf.shape
    h = _rms(x_pf, dims.eps).reshape(b * nc, c, d)
    t = _stamp_t(h, lm, rows)
    # the routed experts read the chunk's STaMP round trip
    hq = bf(jnp.einsum("ts,ntk->nsk", lm, t, precision=HIGHEST))
    a = bf(jax.nn.silu(D._stamp_out(t, w["sg"], lm))
           * D._stamp_out(t, w["su"], lm))
    out = bf(_moe(hq, w, dims, p.expert_bits)
             + _stamp_lin(a, w["sd"], lm, rows))
    x_pf = _residual(x_pf, out.reshape(b, nc, c, d), dims)

    h = _rms(x_dec, dims.eps)
    g = _dec_lin(h, w["sg"], p.decode_bits)
    u = _dec_lin(h, w["su"], p.decode_bits)
    a = bf(bf(jax.nn.silu(g)) * u)
    out = bf(_moe(h, w, dims, p.expert_bits)
             + _dec_lin(a, w["sd"], p.decode_bits))
    return x_pf, _residual(x_dec, out, dims)


def _attention(w, h_pf, h_dec, plen, lm, rows, dims: Dims, p: Precision):
    """The attention mixer's outputs (before the residual) of the prefill
    chunk rows and the decode rows: ``dense_llama``'s attention without
    positions and at the model's score scale."""
    b, nc, c, d = h_pf.shape
    nd = h_dec.shape[1]
    nh, kvh, hd, rep = dims.nh, dims.kvh, dims.hd, dims.nh // dims.kvh
    qd, kd = nh * hd, kvh * hd
    scale = dims.attn_scale
    pos_pf = jnp.arange(nc * c).reshape(nc, c)
    kv = _kv_codes(p)

    qkv = _stamp_lin(h_pf.reshape(b * nc, c, d), w["wqkv"], lm, rows)
    qkv = qkv.reshape(b, nc, c, -1)
    q = qkv[..., :qd].reshape(b, nc, c, nh, hd)
    k = qkv[..., qd:qd + kd].reshape(b, nc, c, kvh, hd)
    v = qkv[..., qd + kd:].reshape(b, nc, c, kvh, hd)
    kc = kv(k.reshape(b, nc * c, kvh, hd), pos_pf.reshape(-1))
    vc = kv(v.reshape(b, nc * c, kvh, hd), pos_pf.reshape(-1))
    kpos = jnp.arange(nc * c)

    def chunk(ci):
        qc = jax.lax.dynamic_index_in_dim(q, ci, 1, keepdims=False)
        qc = qc.reshape(b, c, kvh, rep, hd).transpose(0, 2, 3, 1, 4) * scale
        kr = jax.lax.dynamic_index_in_dim(k, ci, 1, keepdims=False)
        vr = jax.lax.dynamic_index_in_dim(v, ci, 1, keepdims=False)
        s_cache = jnp.einsum("bgrqd,bkgd->bgrqk", qc, kc, precision=HIGHEST)
        seen = (kpos[None] < jnp.minimum(ci * c, plen)[:, None])
        s_cache = jnp.where(seen[:, None, None, None], s_cache, -1e30)
        s_self = jnp.einsum("bgrqd,bkgd->bgrqk", qc, kr, precision=HIGHEST)
        causal = jnp.arange(c)[None, :] <= jnp.arange(c)[:, None]
        s_self = jnp.where(causal, s_self, -1e30)
        o = D._softmax_av([s_cache, s_self],
                          [vc.transpose(0, 2, 1, 3)[:, :, None],
                           vr.transpose(0, 2, 1, 3)[:, :, None]],
                          round_p=False)
        return bf(o.transpose(0, 3, 1, 2, 4).reshape(b, c, qd))

    attn = jax.lax.map(chunk, jnp.arange(nc)).transpose(1, 0, 2, 3)
    o_pf = _stamp_lin(attn.reshape(b * nc, c, qd), w["wo"], lm, rows)

    pos_dec = plen[:, None] + jnp.arange(nd)[None, :]
    qkv = _dec_lin(h_dec, w["wqkv"], p.decode_bits)
    qq = qkv[..., :qd].reshape(b, nd, nh, hd)
    kk = qkv[..., qd:qd + kd].reshape(b, nd, kvh, hd)
    vv = qkv[..., qd + kd:].reshape(b, nd, kvh, hd)
    kdc, vdc = kv(kk, pos_dec), kv(vv, pos_dec)
    qg = bf(qq.reshape(b, nd, kvh, rep, hd).transpose(0, 2, 3, 1, 4) * scale)
    s_pr = jnp.einsum("bgrqd,bkgd->bgrqk", qg, kc, precision=HIGHEST)
    s_pr = jnp.where((kpos[None] < plen[:, None])[:, None, None, None], s_pr,
                     -1e30)
    s_dc = jnp.einsum("bgrqd,bkgd->bgrqk", qg, kdc, precision=HIGHEST)
    s_dc = jnp.where(jnp.arange(nd)[None, :] <= jnp.arange(nd)[:, None],
                     s_dc, -1e30)
    o = D._softmax_av([s_pr, s_dc], [vc.transpose(0, 2, 1, 3)[:, :, None],
                                     vdc.transpose(0, 2, 1, 3)[:, :, None]],
                      round_p=True)
    att = bf(o.transpose(0, 3, 1, 2, 4).reshape(b, nd, qd))
    return o_pf.reshape(b, nc, c, d), _dec_lin(att, w["wo"], p.decode_bits)


def _kv_codes(p: Precision):
    if p.kv_hi_bits is None:
        return lambda x, pos: bf(x)
    return lambda x, pos: _kv(x, pos, p)


def _conv(xp, w, b, width: int, s: int):
    """Depthwise causal conv of ``s`` outputs over ``xp`` (width − 1
    inputs of history first), with bias, then SiLU."""
    y = sum(xp[:, i:i + s] * w[i] for i in range(width))
    return bf(jax.nn.silu(y + b))


def _recur(xbc, dt, mask, state, w, dims: Dims):
    """The SSM over ``xbc`` (b, t, conv_dim) token by token from
    ``state`` (b, heads, head_dim, n); positions where ``mask`` is false
    leave the state as it is.  Returns (y (b, t, heads, head_dim) with
    the D skip, final state)."""
    di, n, sh = dims.di, dims.n, dims.sh
    pd = di // sh
    x = xbc[..., :di].reshape(*xbc.shape[:2], sh, pd)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = dt * mask[..., None]

    def step(s, inp):
        xt, bt, ct, dtt = inp
        s = s * jnp.exp(dtt * w["a"])[..., None, None] \
            + jnp.einsum("bh,bhp,bn->bhpn", dtt, xt, bt, precision=HIGHEST)
        return s, jnp.einsum("bn,bhpn->bhp", ct, s, precision=HIGHEST)

    state, y = jax.lax.scan(step, state, (x.swapaxes(0, 1), bm.swapaxes(0, 1),
                                          cm.swapaxes(0, 1), dt.swapaxes(0, 1)))
    return y.swapaxes(0, 1) + x, state          # D = 1


def _mamba(w, h_pf, h_dec, plen, lm, rows, dims: Dims, p: Precision):
    """The Mamba-2 mixer's outputs (before the residual): the prompt
    through its chunks' STaMP projections and the recurrence over every
    prompt position, then the decode rows continuing from the prompt's
    last state and conv inputs."""
    b, nc, c, d = h_pf.shape
    nd = h_dec.shape[1]
    di, n, width = dims.di, dims.n, dims.conv
    cd = di + 2 * n
    plen_mask = jnp.arange(nc * c)[None, :] < plen[:, None]

    def split(proj):
        z, xbc, dt = proj[..., :di], proj[..., di:di + cd], proj[..., di + cd:]
        return z, xbc, jax.nn.softplus(dt + w["dt_bias"])

    def out(y, z, lin):
        y = bf(bf(y.reshape(*y.shape[:-2], di)) * bf(jax.nn.silu(z)))
        return lin(_rms(y, dims.eps), w["out"])

    proj = _stamp_lin(h_pf.reshape(b * nc, c, d), w["in"], lm, rows)
    z, xbc, dt = split(proj.reshape(b, nc * c, -1))
    hist = jnp.zeros((b, width - 1, cd), jnp.float32)
    xbc_c = _conv(jnp.concatenate([hist, xbc], axis=1), w["conv_w"],
                  w["conv_b"], width, nc * c)
    state0 = jnp.zeros((b, dims.sh, di // dims.sh, n), jnp.float32)
    y, state = _recur(xbc_c, dt, plen_mask, state0, w, dims)
    o_pf = out(y.reshape(b * nc, c, dims.sh, -1), z.reshape(b * nc, c, di),
               lambda a, wt: _stamp_lin(a, wt, lm, rows))

    proj = _dec_lin(h_dec, w["in"], p.decode_bits)
    z, xbc_d, dt = split(proj)
    back = plen[:, None] - (width - 1) + jnp.arange(width - 1)[None, :]
    hist = jnp.take_along_axis(xbc, jnp.maximum(back, 0)[..., None], axis=1)
    hist = jnp.where((back >= 0)[..., None], hist, 0.0)
    xbc_c = _conv(jnp.concatenate([hist, xbc_d], axis=1), w["conv_w"],
                  w["conv_b"], width, nd)
    y, _ = _recur(xbc_c, dt, jnp.ones((b, nd), bool), state, w, dims)
    o_dec = out(y, z, lambda a, wt: _dec_lin(a, wt, p.decode_bits))
    return o_pf.reshape(b, nc, c, d), o_dec


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _layer(w, x_pf, x_dec, plen, lm, dims: Dims, p: Precision, attn: bool):
    b, nc, c, d = x_pf.shape
    rows = None
    if p.hi_bits is not None:
        rows = jnp.where(jnp.arange(c) < p.num_hi, p.hi_bits, p.lo_bits)
        rows = rows.astype(jnp.float32)[:, None]
    h_pf, h_dec = _rms(x_pf, dims.eps), _rms(x_dec, dims.eps)
    mixer = _attention if attn else _mamba
    o_pf, o_dec = mixer(w, h_pf, h_dec, plen, lm, rows, dims, p)
    x_pf, x_dec = _residual(x_pf, o_pf, dims), _residual(x_dec, o_dec, dims)
    return _ffn(w, x_pf, x_dec, lm, rows, dims, p)


@functools.partial(jax.jit, static_argnums=4)
def _logits(x_pf, x_dec, plen, table, dims: Dims):
    b, nc, c, d = x_pf.shape
    last = x_pf.reshape(b, nc * c, d)[jnp.arange(b), plen - 1]
    rows = jnp.concatenate([last[:, None], x_dec], axis=1)
    return jnp.einsum("brd,vd->brv", _rms(rows, dims.eps), table,
                      precision=HIGHEST) / dims.logits_scaling


@functools.partial(jax.jit, static_argnums=1)
def _embed(table, dims: Dims, tokens):
    return bf(table[tokens] * dims.emb_mult)


def logits(c: dict, seed: int, chunk: int, batch: Batch,
           passes: dict) -> dict:
    """Teacher-force the reference over each packed prompt and its served
    tokens, layer by layer and ``BLOCK`` requests at a time, once per
    precision in ``passes`` (name → `Precision`).  Returns name → logits
    ``(B, decode_rows + 1, vocab)``: row 0 at the prompt's last token,
    row ``j`` at served token ``j − 1``."""
    dims = Dims.of(c)
    b, width = batch.tokens_pf.shape
    nc = width // chunk
    k_embed, _, _, k_per, _ = jax.random.split(jax.random.PRNGKey(seed), 5)
    per_keys = jax.random.split(k_per, dims.layers // dims.period)
    first = next(iter(passes.values()))
    lm = jnp.asarray(D.dwt_matrix(chunk, first.levels, first.skip_first))
    plen = jnp.asarray(batch.plen)
    table = _table(k_embed, dims)
    x_pf = _embed(table, dims, jnp.asarray(batch.tokens_pf))
    x_pf = x_pf.reshape(b, nc, chunk, dims.d)
    x_dec = _embed(table, dims, jnp.asarray(batch.tokens_dec))
    blocks = [slice(i, i + BLOCK) for i in range(0, b, BLOCK)]
    states = {(k, j): (x_pf[s], x_dec[s]) for k in passes
              for j, s in enumerate(blocks)}
    del x_pf, x_dec
    for layer in range(dims.layers):
        key = jax.random.split(per_keys[layer // dims.period],
                               dims.period)[layer % dims.period]
        attn = dims.is_attn(layer)
        weights = {}
        for k, p in passes.items():
            if p.weight_bits not in weights:
                weights[p.weight_bits] = layer_weights(key, dims, attn,
                                                       p.weight_bits)
            states.update({(k, j): _layer(weights[p.weight_bits],
                                          *states[k, j], plen[blocks[j]],
                                          lm, dims, p, attn)
                           for j in range(len(blocks))})
        del weights
    return {k: jnp.concatenate([_logits(*states[k, j], plen[s], table, dims)
                                for j, s in enumerate(blocks)])
            for k in passes}


def logit_gaps(c: dict, seed: int, chunk: int, batch: Batch,
               control: bool = False) -> dict:
    """The gap by which each served token's logit lies below the
    reference's best: its largest (``max_gap``), its mean (``mean_gap``)
    and the share of tokens that are not the reference's first
    (``top1_miss``), under ``served``.  With ``control``, the same under
    ``control`` for the token that the reference computed one precision
    lower puts first at each position."""
    prec = Precision.of(c)
    passes = {"served": prec}
    if control:
        passes["control"] = prec.lowered()
    out_logits = logits(c, seed, chunk, batch, passes)
    valid = np.arange(batch.served.shape[1])[None, :] < batch.nserved[:, None]
    ref = out_logits["served"]
    tokens = {"served": jnp.asarray(batch.served)}
    if control:
        tokens["control"] = jnp.argmax(out_logits["control"],
                                       axis=-1).astype(jnp.int32)
    out = {}
    for k, ids in tokens.items():
        g = np.asarray(D._gaps(ref, ids))[valid]
        out[k] = {"max_gap": float(g.max()), "mean_gap": float(g.mean()),
                  "top1_miss": float((g > 0).mean())}
    return out
