"""Assigned architecture registry: ``get_config(arch_id)``.

Each module defines ``CONFIG`` (the full published configuration) and
``reduced()`` (a tiny same-family config for CPU smoke tests).
"""

from __future__ import annotations

import importlib

ARCHS = (
    "minicpm_2b",
    "deepseek_7b",
    "mistral_nemo_12b",
    "qwen2_72b",
    "llava_next_mistral_7b",
    "jamba_1_5_large_398b",
    "seamless_m4t_large_v2",
    "kimi_k2_1t_a32b",
    "arctic_480b",
    "mamba2_1_3b",
    "granite_4_0_h_small",
    # the paper's own evaluation models
    "llama3_8b",
    "pixart_sigma",
)

_ALIASES = {a.replace("_", "-"): a for a in ARCHS}
_ALIASES.update({
    "minicpm-2b": "minicpm_2b",
    "deepseek-7b": "deepseek_7b",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "qwen2-72b": "qwen2_72b",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "arctic-480b": "arctic_480b",
    "mamba2-1.3b": "mamba2_1_3b",
    "granite-4.0-h-small": "granite_4_0_h_small",
    "llama3-8b": "llama3_8b",
    "pixart-sigma": "pixart_sigma",
})


def canonical(arch: str) -> str:
    return _ALIASES.get(arch, arch)


def get_config(arch: str):
    mod = importlib.import_module(f"repro.configs.{canonical(arch)}")
    return mod.CONFIG


def get_reduced(arch: str):
    mod = importlib.import_module(f"repro.configs.{canonical(arch)}")
    return mod.reduced()
