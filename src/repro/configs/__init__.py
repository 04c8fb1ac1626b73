"""Architecture registry: one module per assigned architecture.

Each module exports get_config() (the full assigned spec, citation in
its docstring) and reduced_config() (the CPU smoke-test variant:
<=2-ish layers, d_model<=512, <=4 experts)."""
from __future__ import annotations

import importlib

ARCHITECTURES = (
    "starcoder2_15b",
    "recurrentgemma_9b",
    "llama3_2_vision_90b",
    "xlstm_125m",
    "seamless_m4t_medium",
    "qwen3_4b",
    "arctic_480b",
    "deepseek_v2_236b",
    "qwen2_72b",
    "qwen3_8b",
    "deepseek_v2_lite",
)

# CLI ids (dashes) -> module names
_ALIASES = {a.replace("_", "-"): a for a in ARCHITECTURES}
_ALIASES.update({
    "starcoder2-15b": "starcoder2_15b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "llama-3.2-vision-90b": "llama3_2_vision_90b",
    "xlstm-125m": "xlstm_125m",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "qwen3-4b": "qwen3_4b",
    "arctic-480b": "arctic_480b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "qwen2-72b": "qwen2_72b",
    "qwen3-8b": "qwen3_8b",
})


def _module(name: str):
    key = _ALIASES.get(name, name)
    return importlib.import_module(f"repro.configs.{key}")


def get_config(name: str):
    return _module(name).get_config()


def reduced_config(name: str):
    return _module(name).reduced_config()


def list_architectures() -> tuple:
    return tuple(sorted(set(_ALIASES) - set(ARCHITECTURES)))
