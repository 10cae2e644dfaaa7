"""Program side of a Granite-4.0-H hybrid configuration (model type
``granitemoehybrid``): the `ModelConfig` the system under test runs,
built from the published keys of a configuration file under
``bench/configs``.  The file's ``num_local_experts`` is the experts this
chip holds, from ``expert_offset``; the router's width is the published
count."""

from __future__ import annotations

from repro.models.config import ModelConfig

PERIOD = 10                   # one attention layer per ten, at index 5


def model_config(c: dict) -> ModelConfig:
    layers = c["num_hidden_layers"]
    kinds = c["layer_types"][:layers]
    want = ["attention" if i % PERIOD == PERIOD // 2 else "mamba"
            for i in range(layers)]
    if kinds != want:
        raise ValueError(f"layer_types {kinds} are not whole periods of "
                         f"{PERIOD} with attention at {PERIOD // 2}")
    d, expand = c["hidden_size"], c["mamba_expand"]
    if c["mamba_n_heads"] * c["mamba_d_head"] != expand * d \
            or c["mamba_n_groups"] != 1:
        raise ValueError("the Mamba-2 mixer takes n_heads · d_head = "
                         "expand · hidden and one group")
    if c["position_embedding_type"] != "nope":
        raise ValueError("the family serves NoPE attention")
    return ModelConfig(
        name=c["name"], family="hybrid",
        num_layers=layers, d_model=d,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["shared_intermediate_size"],
        vocab_size=c["vocab_size"],
        num_experts=c["published"]["num_local_experts"],
        num_local_experts=c["num_local_experts"],
        expert_offset=c["expert_offset"],
        experts_per_token=c["num_experts_per_tok"],
        moe_d_ff=c["intermediate_size"],
        dense_residual=True, moe_period=1,
        attn_period=PERIOD, ssm_state=c["mamba_d_state"],
        ssm_head_dim=c["mamba_d_head"], ssm_expand=expand,
        conv_width=c["mamba_d_conv"], conv_bias=c["mamba_conv_bias"],
        use_rope=False,
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], sub_quadratic=True,
        embed_init_std=c.get("embedding_init_std", 0.02),
        source=c["source"])
