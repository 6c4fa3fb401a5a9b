"""Model registry: architecture name -> model module.

What the runners call of a model module (``smg_tpu/models/llama.py`` is the
reference implementation of the contract, ``models/olmo_hybrid.py`` the one
with state beside its pages):

- ``init_params(cfg, key)``, ``logical_axes(cfg)``;
- ``forward_prefill`` (one chunk of one sequence), ``forward_prefill_batched``
  (several sequences' chunks; ``no_ctx`` when every row starts its sequence);
- ``forward_decode_horizon`` (one column of a decode frame: the frozen cache
  is read, the column's K and V go to the frame's side buffers, the caller
  lands them with ``ops.attention.land_side_buffers``).  There is no other
  decode step;
- optional, each a serving limit where absent (``SERVING_LIMITS``):
  ``forward_verify_block`` (speculative verify), ``forward_embed``
  (``/v1/embeddings``), ``forward_train`` (the dense causal forward of
  ``smg_tpu/train`` and of the tests);
- a module whose sequences hold state beside their pages also gives
  ``state_shapes(cfg, slots)`` and ``decode_step(cfg)`` (what its recurrent
  layers' decode step is called, the keyword that picks its form, whether the
  kernel fits) and takes the pools and slots after the page tables
  (``engine/recurrent_runner.py``; ``models/nemotron_h.py``, whose layers are
  state-space mixers, latent experts and attention in any order, has routed
  counts beside its state);
- a module whose cache is one latent buffer (``models/pangu_moe.py``,
  ``models/longcat_flash.py``) takes ``v_cache`` of zero size through its
  prefill forwards untouched, and its decode column takes the one side buffer
  (``cfg.num_cache_layers`` deep, which for ``longcat_flash`` is twice the
  model's layers) and the lanes that hold a sequence
  (``engine/latent_runner.py``); a module with state **and** a latent cache
  (``models/kimi_linear.py``: ``cfg.recurrent`` and ``cfg.latent_cache``) is
  a module with state whose forwards take ``v_cache`` of zero size untouched
  and whose decode column takes the one latent side buffer where the others
  take K and V's two, then the pools, the slots and the lanes that run
  (``engine/recurrent_runner.py`` builds the frame from what the cache's spec
  says); a module with routed experts gives
  ``merge_counts`` and ``ROUTED_COUNTS``, the names of what its frame counts
  (``pangu_moe``'s four; ``longcat_flash`` adds the picks on identity experts);
- a module whose latent attention reads a learned selection of the cache
  (``models/glm_moe_dsa.py``: ``cfg.num_index_layers`` > 0) is a module with one
  latent buffer whose ``v_cache`` is not of zero size: it holds the index keys
  of the layers with an indexer, on the same page tables, the prefill forwards
  write both, and the decode column takes the two caches and the two side
  buffers as pairs and counts the selector's rows beside the experts'
  (``engine/latent_runner.py``);
- a module with window layers beside full ones (``models/mimo.py``) takes the
  window layers' rings and the rows' slots after the page tables, as a module
  with state does, and its decode column the four side buffers as one tuple
  (``engine/window_runner.py``);
- a module with a next-token module of its own (``models/exaone_moe.py``) is a
  module with window layers that also gives ``forward_verify_column``,
  ``forward_mtp_prefill``, ``forward_mtp_column`` and ``mtp_logits``: with
  speculation on its decode column verifies two rows a lane and the module
  drafts the next (``engine/window_runner.SelfDraftingRunner``).
"""

from __future__ import annotations

from types import ModuleType

_REGISTRY: dict[str, ModuleType] = {}
# architectures this package brings itself, loaded on first use
_LLAMA_FAMILY = ("llama", "qwen", "mistral", "qwen_moe")
_BUILTIN = (*_LLAMA_FAMILY, "olmo_hybrid", "pangu_ultra_moe", "mimo_v2_flash", "exaone_moe",
            "longcat_flash", "nemotron_h", "kimi_linear", "glm_moe_dsa")


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
        elif arch == "pangu_ultra_moe":
            from smg_tpu.models import pangu_moe

            _REGISTRY.setdefault("pangu_ultra_moe", pangu_moe)
        elif arch == "mimo_v2_flash":
            from smg_tpu.models import mimo

            _REGISTRY.setdefault("mimo_v2_flash", mimo)
        elif arch == "exaone_moe":
            from smg_tpu.models import exaone_moe

            _REGISTRY.setdefault("exaone_moe", exaone_moe)
        elif arch == "longcat_flash":
            from smg_tpu.models import longcat_flash

            _REGISTRY.setdefault("longcat_flash", longcat_flash)
        elif arch == "nemotron_h":
            from smg_tpu.models import nemotron_h

            _REGISTRY.setdefault("nemotron_h", nemotron_h)
        elif arch == "kimi_linear":
            from smg_tpu.models import kimi_linear

            _REGISTRY.setdefault("kimi_linear", kimi_linear)
        elif arch == "glm_moe_dsa":
            from smg_tpu.models import glm_moe_dsa

            _REGISTRY.setdefault("glm_moe_dsa", glm_moe_dsa)
        else:
            raise KeyError(
                f"unsupported model architecture: {arch!r} "
                f"(registered: {sorted(set(_REGISTRY) | set(_BUILTIN))})"
            )
    return _REGISTRY[arch]
