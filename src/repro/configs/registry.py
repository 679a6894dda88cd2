"""Architecture registry: ``--arch <id>`` resolution for every launcher."""
from __future__ import annotations

import importlib
from typing import Dict

from repro.configs.base import ModelConfig

_ARCH_MODULES: Dict[str, str] = {
    "granite-20b": "repro.configs.granite_20b",
    "internvl2-2b": "repro.configs.internvl2_2b",
    "qwen3-1.7b": "repro.configs.qwen3_1_7b",
    "mixtral-8x7b": "repro.configs.mixtral_8x7b",
    "zamba2-2.7b": "repro.configs.zamba2_2_7b",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "phi4-mini-3.8b": "repro.configs.phi4_mini_3_8b",
    "mamba2-2.7b": "repro.configs.mamba2_2_7b",
    "starcoder2-3b": "repro.configs.starcoder2_3b",
    "seamless-m4t-large-v2": "repro.configs.seamless_m4t_large_v2",
    # The paper's own evaluation model (extra, beyond the 10 assigned).
    "qwen3-4b-thinking": "repro.configs.qwen3_4b_thinking",
    # Served on the chip by the benchmark (extra): latent attention,
    # shared and routed experts.
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
}

_EXTRA_ARCHS = ("qwen3-4b-thinking", "deepseek-v2-lite")
ASSIGNED_ARCHS = tuple(k for k in _ARCH_MODULES if k not in _EXTRA_ARCHS)
ALL_ARCHS = tuple(_ARCH_MODULES)


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(_ARCH_MODULES[arch])
    return mod.smoke_config() if smoke else mod.full_config()


def serving_config(arch: str = "qwen3-4b-thinking") -> ModelConfig:
    """Smoke-scale config wired to the synthetic-task tokenizer, used by
    the serving engine benchmarks (the model actually sampled from)."""
    import dataclasses

    from repro.data.tokenizer import get_tokenizer

    cfg = get_config(arch, smoke=True)
    return dataclasses.replace(cfg, vocab_size=get_tokenizer().vocab_size)


__all__ = [
    "ASSIGNED_ARCHS",
    "ALL_ARCHS",
    "get_config",
    "serving_config",
]
