"""IBM Granite-4.0-H-Small (32B total, 9B active)
[hf:ibm-granite/granite-4.0-h-small, config.json; model type
granitemoehybrid] — 40 layers in periods of ten: Mamba-2 mixers (state
128, head dim 64, expand 2, one group, conv 4 with bias) and one NoPE
GQA attention layer at index 5 of each period (layers 5, 15, 25, 35);
every layer's FFN is a dropless top-10-of-72 MoE (SwiGLU experts of
width 768) plus an ungated shared SwiGLU expert of width 1536.  Granite's
scalars: embeddings times 12, residual branches times 0.22, attention
scores times 1/128, logits divided by 16; the head is tied.

Serves under `PagedServingEngine` (paged K/V for the attention layers,
the slot-dense SSM state pool for the Mamba layers).  The benchmark's
configuration (`bench/configs/granite-4.0-h-small-l20-ep4.json`) runs
20 of the 40 layers with 18 of the 72 experts of each layer: one chip's
share of a 4-way expert-parallel stage."""
import dataclasses
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="hybrid",
    num_layers=40, d_model=4096, num_heads=32, num_kv_heads=8,
    head_dim=128, d_ff=1536, vocab_size=100352,
    num_experts=72, experts_per_token=10, moe_d_ff=768,
    dense_residual=True, moe_period=1,
    attn_period=10, ssm_state=128, ssm_head_dim=64, ssm_expand=2,
    conv_width=4, conv_bias=True, use_rope=False,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0,
    norm_eps=1e-5, tie_embeddings=True, sub_quadratic=True,
    source="hf:ibm-granite/granite-4.0-h-small",
)


def reduced():
    """One period at CPU test widths; 8 routed experts, top 3, of which
    this share holds 4 (experts 2-5); SSM state 64, so the recurrence is
    a visible share of the logits at this width."""
    return dataclasses.replace(
        CONFIG, num_layers=10, d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=16, d_ff=96, vocab_size=512, num_experts=8,
        experts_per_token=3, moe_d_ff=32, num_local_experts=4,
        expert_offset=2, ssm_state=64, ssm_head_dim=16,
        attention_multiplier=1.0 / 16)
