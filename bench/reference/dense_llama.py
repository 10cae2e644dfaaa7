"""Plain reference of a dense llama-architecture model as the benchmark's
configurations state it is served: W4 weights, STaMP activations in
prefill chunks, 8-bit activations in decode, an int8/int4 KV cache, and
bfloat16 activations between the operations.  It imports nothing of the
program under test and takes nothing the program made.

Everything is computed in float32 with ``Precision.HIGHEST`` and rounded
to bfloat16 where the served model stores an activation; each quantizer is
written out below from the configuration's statement of it:

* weights: ``W = normal(key, (din, dout)) / sqrt(din)`` in float32,
  rounded to bfloat16, then int4 codes with an asymmetric min-max range
  per output channel.  The keys follow the served model's seed layout:
  ``PRNGKey(seed)`` splits into (embedding, head, -, layers, -), the layer
  key into one per layer, and that one into a single key that splits into
  24, of which wq, wk, wv, wo, gate, up, down take the first seven.  The
  embedding is ``0.02 * normal`` and the head ``normal / sqrt(d)``, both
  in bfloat16.
* prefill: the prompt runs in chunks of ``chunk`` tokens; the last chunk
  is padded with token 0.  Every linear of a chunk is STaMP: a Haar DWT
  along the chunk's tokens (the first token left out), per-token min-max
  quantization with the first ``num_hi`` transformed tokens at ``hi_bits``
  and the rest at ``lo_bits``, the matmul, and the inverse transform.  A
  chunk attends to the cached (quantized) keys and values of earlier
  chunks and causally to its own unquantized ones.
* decode: every token after the first served one runs alone; its linears
  quantize it per token at ``decode_activation_bits``, and it attends to
  the cached keys and values of every earlier position and its own.
* KV cache: per token and head min-max codes, ``hi_bits`` for the first
  ``num_hi`` positions and ``lo_bits`` after, scale and zero point stored
  in float16.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_SQRT2 = math.sqrt(2.0)
BLOCK = 4                     # requests a layer runs at once, so it fits


def bf(x):
    """Round to bfloat16, keep computing in float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    nh: int
    kvh: int
    hd: int
    ff: int
    vocab: int
    theta: float
    eps: float
    weight_bits: int

    @classmethod
    def of(cls, c: dict) -> "Dims":
        nh = c["num_attention_heads"]
        return cls(d=c["hidden_size"], layers=c["num_hidden_layers"], nh=nh,
                   kvh=c["num_key_value_heads"],
                   hd=c.get("head_dim") or c["hidden_size"] // nh,
                   ff=c["intermediate_size"],
                   vocab=-(-c["vocab_size"] // 128) * 128,
                   theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
                   weight_bits=c["serving"]["weight_bits"])


@dataclasses.dataclass(frozen=True)
class Precision:
    """The precisions a configuration states for its activations and
    cache."""

    levels: int
    skip_first: bool
    num_hi: int
    hi_bits: int
    lo_bits: int
    decode_bits: int
    kv_num_hi: int
    kv_hi_bits: int
    kv_lo_bits: int

    @classmethod
    def of(cls, c: dict) -> "Precision":
        s, kv = c["serving"]["stamp"], c["serving"]["kv_cache"]
        if s["transform"] != "dwt":
            raise ValueError(f"no reference for transform {s['transform']!r}")
        return cls(levels=s["levels"], skip_first=s["skip_first_token"],
                   num_hi=s["num_hi"], hi_bits=s["hi_bits"],
                   lo_bits=s["lo_bits"],
                   decode_bits=c["serving"]["decode_activation_bits"],
                   kv_num_hi=kv["num_hi"], kv_hi_bits=kv["hi_bits"],
                   kv_lo_bits=kv["lo_bits"])

    def lowered(self) -> "Precision":
        """The next precision down: every 8-bit activation and cache code
        at 4 bits (int4 for int8).  The control of the check."""
        def low(b):
            return 4 if b == 8 else b
        return dataclasses.replace(
            self, hi_bits=low(self.hi_bits), decode_bits=low(self.decode_bits),
            kv_hi_bits=low(self.kv_hi_bits))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _w_quant(key, din: int, dout: int, bits: int):
    w = bf(jax.random.normal(key, (din, dout), jnp.float32)
           * (1.0 / np.sqrt(din)))
    n = float(2 ** bits - 1)
    mn = jnp.min(w, axis=0, keepdims=True)
    mx = jnp.max(w, axis=0, keepdims=True)
    s = jnp.maximum((mx - mn) / n, 1e-8)
    z = jnp.round(-mn / s)
    q = jnp.clip(jnp.round(w / s) + z, 0.0, n)
    return (q - z) * s


@functools.partial(jax.jit, static_argnums=1)
def layer_weights(layer_key, dims: Dims) -> dict:
    ks = jax.random.split(jax.random.split(layer_key, 1)[0], 24)
    d, qd, kd, b = dims.d, dims.nh * dims.hd, dims.kvh * dims.hd, \
        dims.weight_bits
    return {"wqkv": jnp.concatenate([_w_quant(ks[0], d, qd, b),
                                     _w_quant(ks[1], d, kd, b),
                                     _w_quant(ks[2], d, kd, b)], axis=1),
            "wo": _w_quant(ks[3], qd, d, b),
            "wg": _w_quant(ks[4], d, dims.ff, b),
            "wu": _w_quant(ks[5], d, dims.ff, b),
            "wd": _w_quant(ks[6], dims.ff, d, b)}


@functools.partial(jax.jit, static_argnums=1)
def _embed_rows(key, dims: Dims, tokens):
    table = bf(jax.random.normal(key, (dims.vocab, dims.d), jnp.float32)
               * 0.02)
    return table[tokens]


@functools.partial(jax.jit, static_argnums=1)
def _head(key, dims: Dims):
    return bf(jax.random.normal(key, (dims.d, dims.vocab), jnp.float32)
              * (1.0 / np.sqrt(dims.d)))


# ---------------------------------------------------------------------------
# the sequence transform
# ---------------------------------------------------------------------------


def _haar_pass(x: np.ndarray) -> np.ndarray:
    """One orthonormal Haar pass along the last axis; an odd last element
    passes through."""
    n = x.shape[-1]
    pairs = n // 2
    even, odd = x[..., 0:2 * pairs:2], x[..., 1:2 * pairs:2]
    parts = [(even + odd) / _SQRT2, (even - odd) / _SQRT2]
    if n % 2:
        parts.append(x[..., -1:])
    return np.concatenate(parts, axis=-1)


def dwt_matrix(n: int, levels: int, skip_first: bool) -> np.ndarray:
    """``L`` with ``L @ x`` the transform of the tokens of ``x``: each
    level transforms the low band again; with ``skip_first`` the first
    token stays out of it."""
    body = n - 1 if skip_first else n
    out = np.eye(body)                 # row i: the transform of token i
    lo = body
    for _ in range(levels):
        if lo < 2:
            break
        out = np.concatenate([_haar_pass(out[:, :lo]), out[:, lo:]], axis=1)
        lo = (lo + 1) // 2
    m = out.T
    if skip_first:
        m = np.block([[np.ones((1, 1)), np.zeros((1, body))],
                      [np.zeros((body, 1)), m]])
    return m.astype(np.float32)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------


def _fq(x, bits):
    """Per-row asymmetric min-max fake quantization over the last axis;
    ``bits`` broadcasts against the kept axis."""
    n = 2.0 ** bits - 1.0
    mn = jnp.min(x, axis=-1, keepdims=True)
    mx = jnp.max(x, axis=-1, keepdims=True)
    s = jnp.maximum((mx - mn) / n, 1e-8)
    z = jnp.round(-mn / s)
    return (jnp.clip(jnp.round(x / s) + z, 0.0, n) - z) * s


def _kv(x, pos, p: Precision):
    """Cached keys or values as attention reads them back: codes per token
    and head, scale and zero point in float16, values in bfloat16."""
    def one(bits):
        n = 2.0 ** bits - 1.0
        mn = jnp.min(x, axis=-1, keepdims=True)
        mx = jnp.max(x, axis=-1, keepdims=True)
        s = jnp.maximum((mx - mn) / n, 1e-8)
        z = jnp.round(-mn / s)
        q = jnp.clip(jnp.round(x / s) + z, 0.0, n)
        f16 = jnp.float16
        return bf((q - z.astype(f16).astype(jnp.float32))
                  * s.astype(f16).astype(jnp.float32))
    hi = (pos < p.kv_num_hi)[..., None, None]
    return jnp.where(hi, one(p.kv_hi_bits), one(p.kv_lo_bits))


def _rms(x, eps):
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return bf(x * bf(r))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = (1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
             ).astype(np.float32)
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return bf(jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1))


def _stamp_t(x, lm, bits_rows):
    """Transformed and quantized chunk rows ``(n, C, K)``."""
    return _fq(jnp.einsum("ts,nsk->ntk", lm, x, precision=HIGHEST),
               bits_rows)


def _stamp_out(tq, w, lm):
    y = jnp.einsum("ntk,kd->ntd", tq, w, precision=HIGHEST)
    return jnp.einsum("ts,ntd->nsd", lm, y, precision=HIGHEST)


def _dec_lin(x, w, bits):
    return jnp.einsum("...k,kd->...d", _fq(x, bits), w, precision=HIGHEST)


def _softmax_av(scores, vals, round_p):
    """Softmax over the concatenated key axis, then the weighted values."""
    m = jnp.max(jnp.concatenate([s.max(-1, keepdims=True) for s in scores],
                                axis=-1), axis=-1, keepdims=True)
    ps = [jnp.exp(s - m) for s in scores]
    den = sum(p.sum(-1, keepdims=True) for p in ps)
    num = sum(jnp.einsum("...qk,...kd->...qd", bf(p) if round_p else p, v,
                         precision=HIGHEST) for p, v in zip(ps, vals))
    return num / den


@functools.partial(jax.jit, static_argnums=(5, 6))
def _layer(w, x_pf, x_dec, plen, lm, dims: Dims, p: Precision):
    b, nc, c, d = x_pf.shape
    nd = x_dec.shape[1]
    nh, kvh, hd, rep = dims.nh, dims.kvh, dims.hd, dims.nh // dims.kvh
    qd, kd = nh * hd, kvh * hd
    scale = 1.0 / np.sqrt(hd)
    rows = jnp.where(jnp.arange(c) < p.num_hi, p.hi_bits, p.lo_bits)
    rows = rows.astype(jnp.float32)[:, None]
    pos_pf = jnp.arange(nc * c).reshape(nc, c)

    # prefill chunk rows
    h = _rms(x_pf, dims.eps).reshape(b * nc, c, d)
    qkv = bf(_stamp_out(_stamp_t(h, lm, rows), w["wqkv"], lm))
    qkv = qkv.reshape(b, nc, c, -1)
    q = _rope(qkv[..., :qd].reshape(b, nc, c, nh, hd), pos_pf, dims.theta)
    k = _rope(qkv[..., qd:qd + kd].reshape(b, nc, c, kvh, hd), pos_pf,
              dims.theta)
    v = qkv[..., qd + kd:].reshape(b, nc, c, kvh, hd)
    kc = _kv(k.reshape(b, nc * c, kvh, hd), pos_pf.reshape(-1), p)
    vc = _kv(v.reshape(b, nc * c, kvh, hd), pos_pf.reshape(-1), p)
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
        o = _softmax_av([s_cache, s_self],
                        [vc.transpose(0, 2, 1, 3)[:, :, None],
                         vr.transpose(0, 2, 1, 3)[:, :, None]],
                        round_p=False)
        return bf(o.transpose(0, 3, 1, 2, 4).reshape(b, c, qd))

    attn = jax.lax.map(chunk, jnp.arange(nc)).transpose(1, 0, 2, 3)
    o = bf(_stamp_out(_stamp_t(attn.reshape(b * nc, c, qd), lm, rows),
                      w["wo"], lm))
    x_pf = bf(x_pf + o.reshape(b, nc, c, d))
    t = _stamp_t(_rms(x_pf, dims.eps).reshape(b * nc, c, d), lm, rows)
    a = bf(jax.nn.silu(_stamp_out(t, w["wg"], lm)) * _stamp_out(t, w["wu"], lm))
    dn = bf(_stamp_out(_stamp_t(a, lm, rows), w["wd"], lm))
    x_pf = bf(x_pf + dn.reshape(b, nc, c, d))

    # decode rows: position plen + j, each alone
    pos_dec = plen[:, None] + jnp.arange(nd)[None, :]
    h = _rms(x_dec, dims.eps)
    qkv = bf(_dec_lin(h, w["wqkv"], p.decode_bits))
    qq = _rope(qkv[..., :qd].reshape(b, nd, nh, hd), pos_dec, dims.theta)
    kk = _rope(qkv[..., qd:qd + kd].reshape(b, nd, kvh, hd), pos_dec,
               dims.theta)
    vv = qkv[..., qd + kd:].reshape(b, nd, kvh, hd)
    kdc, vdc = _kv(kk, pos_dec, p), _kv(vv, pos_dec, p)
    qg = bf(qq.reshape(b, nd, kvh, rep, hd).transpose(0, 2, 3, 1, 4) * scale)
    s_pr = jnp.einsum("bgrqd,bkgd->bgrqk", qg, kc, precision=HIGHEST)
    s_pr = jnp.where((kpos[None] < plen[:, None])[:, None, None, None], s_pr,
                     -1e30)
    s_dc = jnp.einsum("bgrqd,bkgd->bgrqk", qg, kdc, precision=HIGHEST)
    s_dc = jnp.where(jnp.arange(nd)[None, :] <= jnp.arange(nd)[:, None],
                     s_dc, -1e30)
    o = _softmax_av([s_pr, s_dc], [vc.transpose(0, 2, 1, 3)[:, :, None],
                                   vdc.transpose(0, 2, 1, 3)[:, :, None]],
                    round_p=True)
    att = bf(o.transpose(0, 3, 1, 2, 4).reshape(b, nd, qd))
    x_dec = bf(x_dec + bf(_dec_lin(att, w["wo"], p.decode_bits)))
    h = _rms(x_dec, dims.eps)
    g = bf(_dec_lin(h, w["wg"], p.decode_bits))
    u = bf(_dec_lin(h, w["wu"], p.decode_bits))
    a = bf(bf(jax.nn.silu(g)) * u)
    x_dec = bf(x_dec + bf(_dec_lin(a, w["wd"], p.decode_bits)))
    return x_pf, x_dec


@functools.partial(jax.jit, static_argnums=4)
def _logits(x_pf, x_dec, plen, head, dims: Dims):
    b, nc, c, d = x_pf.shape
    last = x_pf.reshape(b, nc * c, d)[jnp.arange(b), plen - 1]
    rows = jnp.concatenate([last[:, None], x_dec], axis=1)
    return jnp.einsum("brd,dv->brv", _rms(rows, dims.eps), head,
                      precision=HIGHEST)


@jax.jit
def _gaps(logits, ids):
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, ids[..., None], axis=-1)[..., 0]


@dataclasses.dataclass
class Batch:
    """Sampled requests packed to fixed shapes: prompts padded to
    ``chunks`` whole chunks, decode rows to ``decode_rows``."""

    tokens_pf: np.ndarray     # (B, chunks * chunk) int32, padded with 0
    tokens_dec: np.ndarray    # (B, decode_rows) int32: served[:-1]
    served: np.ndarray        # (B, decode_rows + 1) int32
    plen: np.ndarray          # (B,)
    nserved: np.ndarray       # (B,)


def pack(seqs: list, chunk: int, chunks: int, decode_rows: int) -> Batch:
    b = len(seqs)
    tpf = np.zeros((b, chunks * chunk), np.int32)
    tdec = np.zeros((b, decode_rows), np.int32)
    served = np.zeros((b, decode_rows + 1), np.int32)
    plen = np.zeros((b,), np.int32)
    ns = np.zeros((b,), np.int32)
    for i, (prompt, out) in enumerate(seqs):
        out = np.asarray(out, np.int32)
        if len(prompt) > chunks * chunk or len(out) > decode_rows + 1:
            raise ValueError("a sampled request exceeds the packed shape")
        tpf[i, :len(prompt)] = prompt
        tdec[i, :len(out) - 1] = out[:-1]
        served[i, :len(out)] = out
        plen[i], ns[i] = len(prompt), len(out)
    return Batch(tpf, tdec, served, plen, ns)


def logit_gaps(c: dict, seed: int, chunk: int, batch: Batch,
               control: bool = False) -> dict:
    """Teacher-force the reference over each sampled prompt and its served
    tokens, layer by layer and ``BLOCK`` requests at a time, and read the
    gap by which each served token's logit lies below the reference's
    best: its largest (``max_gap``), its mean (``mean_gap``) and the share
    of tokens that are not the reference's first (``top1_miss``), under
    ``served``.  With ``control``, the same under ``control`` for the token
    that the reference computed one precision lower puts first at each
    position."""
    dims, prec = Dims.of(c), Precision.of(c)
    b, width = batch.tokens_pf.shape
    nc = width // chunk
    k_embed, k_head, _, k_layers, _ = jax.random.split(
        jax.random.PRNGKey(seed), 5)
    layer_keys = jax.random.split(k_layers, dims.layers)
    lm = jnp.asarray(dwt_matrix(chunk, prec.levels, prec.skip_first))
    plen = jnp.asarray(batch.plen)
    x_pf = _embed_rows(k_embed, dims, jnp.asarray(batch.tokens_pf))
    x_pf = x_pf.reshape(b, nc, chunk, dims.d)
    x_dec = _embed_rows(k_embed, dims, jnp.asarray(batch.tokens_dec))
    passes = {"served": prec}
    if control:
        passes["control"] = prec.lowered()
    blocks = [slice(i, i + BLOCK) for i in range(0, b, BLOCK)]
    states = {(k, j): (x_pf[s], x_dec[s]) for k in passes
              for j, s in enumerate(blocks)}
    del x_pf, x_dec
    for layer in range(dims.layers):
        w = layer_weights(layer_keys[layer], dims)
        states = {(k, j): _layer(w, *states[k, j], plen[blocks[j]], lm, dims,
                                 passes[k]) for k, j in states}
        del w
    head = _head(k_head, dims)
    logits = {k: jnp.concatenate([_logits(*states[k, j], plen[s], head, dims)
                                  for j, s in enumerate(blocks)])
              for k in passes}
    valid = np.arange(batch.served.shape[1])[None, :] < batch.nserved[:, None]
    ref = logits["served"]
    tokens = {"served": jnp.asarray(batch.served)}
    if control:
        tokens["control"] = jnp.argmax(logits["control"], axis=-1).astype(
            jnp.int32)
    out = {}
    for k, ids in tokens.items():
        g = np.asarray(_gaps(ref, ids))[valid]
        out[k] = {"max_gap": float(g.max()), "mean_gap": float(g.mean()),
                  "top1_miss": float((g > 0).mean())}
    return out
