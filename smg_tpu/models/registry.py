"""Model registry: architecture name -> model module.

A model module exposes ``init_params``, ``logical_axes``, ``forward_prefill``,
``forward_decode``, ``forward_train`` with the signatures in
``smg_tpu/models/llama.py`` (the reference implementation of the contract).
"""

from __future__ import annotations

from types import ModuleType

_REGISTRY: dict[str, ModuleType] = {}
# architectures this package brings itself, loaded on first use
_LLAMA_FAMILY = ("llama", "qwen", "mistral", "qwen_moe")
_BUILTIN = (*_LLAMA_FAMILY, "olmo_hybrid")


def register_model(arch: str, module: ModuleType) -> None:
    _REGISTRY[arch] = module


def get_model(arch: str) -> ModuleType:
    if arch not in _REGISTRY:
        if arch in _LLAMA_FAMILY:
            from smg_tpu.models import llama

            # one functional module serves the dense family and the MoE
            # variants (the MLP dispatches on cfg.num_experts)
            _REGISTRY.setdefault("llama", llama)
            _REGISTRY.setdefault("qwen", llama)
            _REGISTRY.setdefault("mistral", llama)
            _REGISTRY.setdefault("qwen_moe", llama)
        elif arch == "olmo_hybrid":
            from smg_tpu.models import olmo_hybrid

            _REGISTRY.setdefault("olmo_hybrid", olmo_hybrid)
        else:
            raise KeyError(
                f"unsupported model architecture: {arch!r} "
                f"(registered: {sorted(set(_REGISTRY) | set(_BUILTIN))})"
            )
    return _REGISTRY[arch]
