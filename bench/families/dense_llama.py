"""Program side of a dense llama-architecture configuration: the
`ModelConfig` the system under test runs, built from the published keys
of a configuration file under ``bench/configs``."""

from __future__ import annotations

from repro.models.config import ModelConfig


def model_config(c: dict) -> ModelConfig:
    return ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim"), d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], source=c["source"])
