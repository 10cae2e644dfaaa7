"""The end-to-end PTQ pipeline: calibrate → allocate → quantize → serve.

Mirrors the paper's procedure (§5, B.1–B.2):

1. run calibration batches, capturing block-input activations;
2. estimate sequence autocorrelation / transformed-token energies per site
   and verify the Toeplitz premise (``toeplitz_fraction``);
3. pick the number of high-precision tokens for the bit budget (greedy
   two-level scheme — the paper fixes 64; we derive it and report both);
4. RTN-quantize the weights with min-max range search (B.2);
5. emit a ``ServeConfig`` + packed weights for the serving engine.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitalloc
from repro.core.calibration import SiteStats, toeplitz_fraction
from repro.core.stamp import StampConfig
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving.kvcache import KVCacheConfig


@dataclasses.dataclass
class PTQReport:
    num_hi: int
    avg_bits: float
    toeplitz_fraction: float
    energy_head_fraction: float     # energy in the first num_hi tokens
    sites: int


def capture_block_inputs(params, batch: dict, cfg: ModelConfig,
                         max_blocks: int = 4):
    """Forward pass collecting the residual-stream input of the first
    ``max_blocks`` scan periods (the quantization sites' common input)."""
    taps = []

    x, _, _ = lm.model_hidden(params, batch, cfg, mode="forward",
                              policy=None, remat=False)
    # cheap proxy: tap the embedding output and final hidden — the
    # autocorrelation structure is driven by the data's locality and is
    # stable across depth (paper Fig. 3 shows layer 15/20 look alike).
    emb = lm._embed(params, batch["tokens"])
    taps.append(np.asarray(emb, np.float32))
    taps.append(np.asarray(x, np.float32))
    return taps


def calibrate(
    params,
    calib_batches: list,
    cfg: ModelConfig,
    *,
    avg_budget: float = 4.125,
    hi_bits: int = 8,
    lo_bits: int = 4,
    transform: str = "dwt",
    levels: int = 3,
    weight_bits: Optional[int] = 4,
) -> tuple[lm.ServeConfig, PTQReport]:
    """Steps 1–3 and 5: the serving config and report, weights untouched.

    ``params`` may be the float tree or the packed serving tree
    (`lm._linear` dequantizes packed weights in the forward), so a model
    initialised packed — ``lm.init_params(..., weight_bits=4)`` — is
    calibrated through its served weights, with no float copy."""
    stats: Optional[SiteStats] = None
    for batch in calib_batches:
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        for tap in capture_block_inputs(params, b, cfg):
            if stats is None:
                stats = SiteStats.empty(tap.shape[-2], tap.shape[-1])
            stats.update(tap)
    if stats is None:
        raise ValueError("no calibration data")

    tf = toeplitz_fraction(stats.autocorr)
    energies = stats.energy_profile(transform, levels=levels)
    order = np.sort(energies)[::-1]
    num_hi = bitalloc.greedy_two_level(order, avg_budget, hi=hi_bits,
                                       lo=lo_bits)
    num_hi = max(1, min(num_hi, 64))   # paper uses 64; budget may allow less
    head_frac = float(order[:num_hi].sum() / max(order.sum(), 1e-9))

    stamp = StampConfig(seq_transform=transform, levels=levels,
                        num_hi_tokens=num_hi, hi_bits=hi_bits,
                        lo_bits=lo_bits, skip_first_token=True)
    serve = lm.ServeConfig(
        stamp=stamp,
        kv=KVCacheConfig(quantized=True, num_hi=num_hi,
                         hi_bits=hi_bits, lo_bits=lo_bits),
        weight_bits=weight_bits)
    seq = stats.autocorr.shape[0]
    report = PTQReport(
        num_hi=num_hi,
        avg_bits=float((num_hi * hi_bits + (seq - num_hi) * lo_bits) / seq),
        toeplitz_fraction=tf,
        energy_head_fraction=head_frac,
        sites=2)
    return serve, report


def calibrate_and_quantize(
    params,
    calib_batches: list,
    cfg: ModelConfig,
    *,
    weight_bits: Optional[int] = 4,
    **kw,
) -> tuple[dict, lm.ServeConfig, PTQReport]:
    """The whole pipeline on a float tree: :func:`calibrate` (``kw`` are
    its options), then step 4 — bf16 cast and ``weight_bits`` packing."""
    serve, report = calibrate(params, calib_batches, cfg,
                              weight_bits=weight_bits, **kw)
    sparams = params
    if weight_bits:
        sparams = lm.quantize_weights_for_serving(
            jax.tree.map(lambda a: jnp.asarray(a, jnp.bfloat16)
                         if a.dtype == jnp.float32 else a, params),
            weight_bits)
    return sparams, serve, report
