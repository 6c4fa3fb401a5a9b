"""Model architecture config, loadable from HF ``config.json``.

The reference never loads models itself (engines do); for the in-tree TPU
engine this is first-class.  Presets cover the BASELINE.md staged configs:
Llama-3 1B/8B/70B class and a tiny test config.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ModelConfig:
    arch: str = "llama"
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    eos_token_ids: tuple[int, ...] = (128001, 128009)
    bos_token_id: int = 128000
    dtype: str = "bfloat16"
    # MoE (0 = dense)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    # Qwen3-family: per-head RMSNorm on q/k before rope (q_norm/k_norm)
    qk_norm: bool = False
    # ---- Gemma-2-family knobs (all default to llama semantics) ----
    activation: str = "silu"  # "silu" | "gelu_tanh"
    rms_unit_offset: bool = False  # RMSNorm scales by (1 + weight)
    embed_scale: bool = False  # multiply token embeddings by sqrt(hidden)
    post_norms: bool = False  # post-attention/post-ffn RMSNorms (4/layer)
    attn_logit_softcap: float | None = None  # tanh softcap on attn scores
    final_logit_softcap: float | None = None  # tanh softcap on lm logits
    query_scale: float | None = None  # 1/sqrt(query_pre_attn_scalar) override
    # Sliding-window attention (Gemma-2 / Mistral): window size and the
    # alternation pattern — every ``sliding_window_pattern``-th layer is
    # GLOBAL, the rest attend locally.  Serving applies real per-layer
    # window masks; train/embed support contexts <= window (trace-time
    # check) since their shared layer body has no per-layer index.
    sliding_window: int | None = None
    sliding_window_pattern: int = 2
    # Vision tower (VLM; None = text-only).  ``image_token_id`` is the
    # placeholder the gateway expands per image (Qwen2-VL <|image_pad|>).
    vision: "object | None" = None  # VisionConfig (kept loose: frozen dataclass)
    image_token_id: int | None = None
    # ---- hybrid stacks (``olmo_hybrid``): the kind of every layer in order,
    # and the linear-attention layers' shape.  ``num_layers`` stays the depth;
    # only the ``full_attention`` layers hold pages (``num_cache_layers``).
    # ``rope_theta`` 0 means the full layers apply no rotary embedding.
    layer_types: "tuple[str, ...] | None" = None
    linear_num_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    linear_allow_neg_eigval: bool = False
    # ---- latent attention (``pangu_ultra_moe``): queries through a rank of
    # ``q_lora_rank``, keys and values through one latent of ``kv_lora_rank``
    # and one rotary key of ``qk_rope_head_dim`` a token, which is all a token
    # leaves in the cache.  ``head_dim`` is then the width queries meet keys
    # at (``qk_nope_head_dim + qk_rope_head_dim``).  0 = ordinary attention.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # ---- routed experts beyond the Qwen-MoE settings.  ``num_experts`` is the
    # router's width and is never cut; ``experts_held`` is the contiguous range
    # ``(first, count)`` of routed experts this process holds (None: all).
    first_k_dense_replace: int = 0  # leading layers with a dense MLP
    n_shared_experts: int = 0
    moe_scoring: str = "softmax"  # "softmax" | "sigmoid" over all experts
    norm_topk_prob: bool = True  # renormalise the top-k scores to sum 1
    routed_scaling_factor: float = 1.0
    experts_held: "tuple[int, int] | None" = None
    # random weights only (``init_params``): the scale of the routed experts'
    # output projections beside the shared expert's.  Whoever compares random
    # weights against a reference sets it (``random_routed_out_gain``), not
    # the program: 1 draws every expert alike.
    random_routed_out_gain: float = 1.0
    # ---- window layers beside full ones (``mimo_v2_flash``).  ``layer_types``
    # names every layer ``full_attention`` or ``sliding_attention``; a window
    # layer has ``swa_num_kv_heads`` key/value heads, rotary base
    # ``swa_rope_theta`` and, where ``swa_sink_bias``, a learned logit a head
    # in its softmax's denominator.  Keys are ``head_dim`` wide and values
    # ``v_head_dim``, in both kinds; rotary turns the first
    # ``qk_rope_head_dim`` lanes of a head; values are scaled by
    # ``attention_value_scale``.  ``moe_layer_freq[l]`` is 1 where layer ``l``
    # has routed experts; ``moe_select_bias``: the router picks by score plus
    # a learned bias and weighs by the score.
    swa_num_kv_heads: int = 0
    swa_rope_theta: float = 0.0
    swa_sink_bias: bool = False
    attention_value_scale: float = 1.0
    moe_layer_freq: "tuple[int, ...] | None" = None
    moe_select_bias: bool = False
    # ---- the model's own next-token module (``exaone_moe``): ``mtp_layers``
    # blocks behind the stack that draft the token after the next one.  Each
    # is a full-attention layer whose keys and values take one more layer of
    # the pages (``num_cache_layers`` counts it).
    mtp_layers: int = 0
    # ---- two attention sublayers a layer, identity experts (``longcat_flash``).
    # Every attention sublayer leaves its own entry in the cache, so a layer is
    # ``attention_sublayers`` cache layers (``num_cache_layers``).  The last
    # ``zero_experts`` of the router's ``num_experts`` outputs are experts that
    # compute nothing (``E_i(x) = x``) and that no chip holds.  The latent
    # attention's two normed low-rank vectors are scaled by ``mla_q_scale`` and
    # ``mla_kv_scale`` (1: not scaled, and nothing is multiplied in).
    attention_sublayers: int = 1
    zero_experts: int = 0
    mla_q_scale: float = 1.0
    mla_kv_scale: float = 1.0
    # ---- one mixer or one feed-forward part a layer (``nemotron_h``).
    # ``layer_types`` names every layer ``mamba`` (a Mamba-2 state-space
    # mixer: ``ssm_num_heads`` heads of ``ssm_head_dim``, a state of
    # ``ssm_state_size`` a lane of a head, ``ssm_groups`` groups of heads that
    # share the input and output vectors, a causal convolution of
    # ``ssm_conv_kernel`` taps, prefill in chunks of ``ssm_chunk_size``),
    # ``moe`` (routed experts that work in a latent of ``moe_latent_size``,
    # ungated, ``activation`` "relu2"; one shared expert of
    # ``moe_shared_intermediate_size`` on the model's own width) or
    # ``full_attention`` (``rope_theta`` 0: unrotated).
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 0
    ssm_conv_kernel: int = 0
    ssm_chunk_size: int = 0
    moe_latent_size: int = 0
    moe_shared_intermediate_size: int = 0
    # random weights only (``nemotron_h.init_params``): sizes of the drawing
    # that whoever compares random weights against a reference sets, as
    # ``(name, value)`` pairs (``random_weights`` in a config.json; the names
    # and what each is 1 or the published value without are
    # ``models/nemotron_h.DRAW``'s).  Empty draws every part alike.
    random_init: "tuple[tuple[str, float], ...]" = ()
    # ---- Kimi Delta Attention beside latent attention (``kimi_linear``).
    # ``layer_types`` names every layer ``kda`` (the delta rule whose decay is
    # a number a key channel: ``linear_num_heads`` heads with keys of
    # ``linear_key_head_dim`` and values of ``linear_value_head_dim``, a causal
    # convolution of ``linear_conv_kernel_dim`` taps, the decay's and the
    # output gate's low rank ``linear_key_head_dim``) or ``full_attention``
    # (latent attention, the query projected in one step: ``q_lora_rank`` 0;
    # ``rope_theta`` 0: the key the heads share is not rotated).  Such a model
    # is ``recurrent`` and has a ``latent_cache``: state slots beside latent
    # pages (``engine/recurrent_runner.py``).
    # ---- a learned selector over the latent cache (``glm_moe_dsa``).  A layer
    # of ``indexer_types`` "full" has an indexer: ``index_n_heads`` query heads
    # of ``index_head_dim`` against one cached key of that width a token, whose
    # weighted scores choose the ``index_topk`` cached tokens the layer's
    # attention reads; a "shared" layer reads the set of the nearest "full"
    # layer before it and has neither indexer nor index keys.  The index keys
    # are a second paged buffer on the latent cache's page tables, one layer
    # for every "full" layer (``num_index_layers``).  0 / None: no selector.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_types: "tuple[str, ...] | None" = None

    @property
    def window_cache(self) -> bool:
        """Some layers keep a window of keys and values a sequence, outside
        the pages."""
        return self.layer_types is not None and "sliding_attention" in self.layer_types

    @property
    def latent_cache(self) -> bool:
        """A token leaves one latent entry in the cache and no V."""
        return self.kv_lora_rank > 0

    @property
    def num_index_layers(self) -> int:
        """Layers with an indexer of their own: each keeps one index key a
        token in the second paged buffer."""
        return sum(1 for t in self.indexer_types or () if t == "full")

    @property
    def rope_dim(self) -> int:
        """Lanes the rotary embedding turns."""
        return self.qk_rope_head_dim or self.head_dim

    @property
    def held_experts(self) -> "tuple[int, int]":
        """``(first, count)`` of the routed experts this process holds (the
        identity experts are nobody's to hold)."""
        return self.experts_held or (0, self.num_experts - self.zero_experts)

    @property
    def num_cache_layers(self) -> int:
        """Layers that hold keys and values in the paged cache."""
        if self.layer_types is None:
            return self.num_layers * self.attention_sublayers
        return sum(1 for t in self.layer_types if t == "full_attention") + self.mtp_layers

    @property
    def num_window_layers(self) -> int:
        """Layers that keep a window of keys and values a sequence."""
        return sum(1 for t in self.layer_types or () if t == "sliding_attention")

    def kv_lanes(self, window: bool = False) -> tuple[int, int]:
        """(K lanes, V lanes) a token leaves in a full-attention layer, or in
        a window layer."""
        heads = self.swa_num_kv_heads if window else self.num_kv_heads
        return heads * self.head_dim, heads * (self.v_head_dim or self.head_dim)

    @property
    def recurrent(self) -> bool:
        """Some layers keep per-sequence state outside the pages."""
        return self.layer_types is not None and bool(
            {"linear_attention", "mamba", "kda"} & set(self.layer_types))

    @property
    def mrope_section(self) -> "tuple[int, ...] | None":
        """Qwen2-VL M-RoPE frequency split (t, h, w) from rope_scaling; None
        = standard rope (engine/mrope.py)."""
        if not self.rope_scaling:
            return None
        sec = self.rope_scaling.get("mrope_section")
        return tuple(sec) if sec else None

    @classmethod
    def from_hf_config(cls, cfg: dict, dtype: str = "bfloat16") -> "ModelConfig":
        if cfg.get("model_type") == "olmo_hybrid":
            return cls._from_olmo_hybrid(cfg, dtype)
        if cfg.get("model_type") == "pangu_ultra_moe":
            return cls._from_pangu_ultra_moe(cfg, dtype)
        if cfg.get("model_type") == "mimo_v2_flash":
            return cls._from_mimo_v2_flash(cfg, dtype)
        if cfg.get("model_type") == "exaone_moe":
            return cls._from_exaone_moe(cfg, dtype)
        if cfg.get("model_type") == "longcat_flash":
            return cls._from_longcat_flash(cfg, dtype)
        if cfg.get("model_type") == "nemotron_h":
            return cls._from_nemotron_h(cfg, dtype)
        if cfg.get("model_type") == "kimi_linear":
            return cls._from_kimi_linear(cfg, dtype)
        if cfg.get("model_type") == "glm_moe_dsa":
            return cls._from_glm_moe_dsa(cfg, dtype)
        # keys that change what the layers compute and that this path would
        # drop in silence: routed experts beyond Qwen-MoE's settings, latent
        # attention.  A config that carries one is another model (D6's rule).
        foreign = sorted(k for k in cls._LLAMA_PATH_REFUSED
                         if cfg.get(k) not in (None, 0, False))
        if cfg.get("norm_topk_prob") is False:
            foreign.append("norm_topk_prob")
        if foreign:
            raise ValueError(
                f"config.json (model_type {cfg.get('model_type')!r}) has keys the Llama-family "
                f"loader does not consume: {foreign}; it would be served as another model")
        arch_names = cfg.get("architectures") or ["LlamaForCausalLM"]
        arch = "llama"
        name = arch_names[0].lower()
        # Qwen3 family (dense and MoE) normalizes q/k per head before rope
        qk_norm = "qwen3" in name
        if "qwen3moe" in name or "qwen2moe" in name:
            arch = "qwen_moe"
        elif "qwen" in name:
            arch = "qwen"
        elif "mistral" in name:
            arch = "llama"  # same architecture family
        eos = cfg.get("eos_token_id", 2)
        eos_ids = tuple(eos) if isinstance(eos, list) else (eos,)
        num_heads = cfg["num_attention_heads"]
        # Gemma-2 family: gelu MLP, (1+w) norms, scaled embeddings, post
        # norms, attn/final logit softcaps, query_pre_attn_scalar scale
        gemma = "gemma2" in name or "gemma-2" in name
        extra: dict = {}
        if "mistral" in name and cfg.get("sliding_window"):
            # Mistral v0.1-style: EVERY layer windowed (pattern 0)
            extra = dict(
                sliding_window=cfg["sliding_window"],
                sliding_window_pattern=0,
            )
        if gemma:
            q_scalar = cfg.get("query_pre_attn_scalar") or cfg.get("head_dim", 256)
            extra = dict(
                activation="gelu_tanh",
                rms_unit_offset=True,
                embed_scale=True,
                post_norms=True,
                attn_logit_softcap=cfg.get("attn_logit_softcapping", 50.0),
                final_logit_softcap=cfg.get("final_logit_softcapping", 30.0),
                query_scale=1.0 / (q_scalar ** 0.5),
                sliding_window=cfg.get("sliding_window"),
                tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            )
        vision = None
        vc = cfg.get("vision_config")
        if vc and "vl" in name:
            from smg_tpu.models.vit import VisionConfig

            vh = vc.get("embed_dim") or vc.get("hidden_size", 1280)
            vision = VisionConfig(
                hidden_size=vh,
                intermediate_size=vc.get("intermediate_size") or vh * 4,
                num_layers=vc.get("depth") or vc.get("num_hidden_layers", 32),
                num_heads=vc.get("num_heads") or vc.get("num_attention_heads", 16),
                patch_size=vc.get("patch_size", 14),
                merge_size=vc.get("spatial_merge_size", 2),
                in_channels=vc.get("in_channels", vc.get("in_chans", 3)),
                out_hidden_size=cfg["hidden_size"],
                dtype=dtype,
            )
        return cls(
            arch=arch,
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg.get("intermediate_size", 4 * cfg["hidden_size"]),
            num_layers=cfg["num_hidden_layers"],
            num_heads=num_heads,
            num_kv_heads=cfg.get("num_key_value_heads", num_heads),
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // num_heads,
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_scaling=cfg.get("rope_scaling"),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=extra.pop(
                "tie_word_embeddings", cfg.get("tie_word_embeddings", False)
            ),
            eos_token_ids=eos_ids,
            bos_token_id=cfg.get("bos_token_id", 1),
            dtype=dtype,
            num_experts=cfg.get("num_experts", cfg.get("num_routed_experts", 0)) or 0,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 0) or 0,
            moe_intermediate_size=cfg.get("moe_intermediate_size", 0) or 0,
            vision=vision,
            image_token_id=cfg.get("image_token_id"),
            qk_norm=qk_norm,
            **extra,
        )

    _LLAMA_PATH_REFUSED = ("n_routed_experts", "n_shared_experts", "kv_lora_rank",
                           "q_lora_rank", "scoring_func", "first_k_dense_replace",
                           "routed_scaling_factor", "qk_rope_head_dim")

    # what an ``olmo_hybrid`` config.json may hold: keys this loader turns
    # into the model's shape, and keys that bear on no shape.  Any other key
    # is an error, so that a config this program would serve wrong fails to
    # load (ROADMAP D6's rule, on this path).
    _OLMO_HYBRID_CONSUMED = frozenset({
        "model_type", "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
        "hidden_act", "max_position_embeddings", "attention_bias", "rms_norm_eps",
        "tie_word_embeddings", "layer_types", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "linear_allow_neg_eigval", "rope_parameters",
        "rope_theta", "eos_token_id", "bos_token_id",
    })
    _OLMO_HYBRID_SHAPELESS = frozenset({
        "architectures", "torch_dtype", "dtype", "transformers_version", "use_cache",
        "initializer_range", "pad_token_id", "attention_dropout", "auto_map",
        "_name_or_path",
    })

    @classmethod
    def _from_olmo_hybrid(cls, cfg: dict, dtype: str) -> "ModelConfig":
        unknown = sorted(set(cfg) - cls._OLMO_HYBRID_CONSUMED - cls._OLMO_HYBRID_SHAPELESS)
        if unknown:
            raise ValueError(
                f"olmo_hybrid config.json has keys this loader does not consume: {unknown}; "
                "a key that may bear on the model's shape is not dropped in silence")
        if cfg.get("hidden_act", "silu") != "silu":
            raise ValueError(f"olmo_hybrid: hidden_act {cfg['hidden_act']!r} is not served")
        if cfg.get("attention_bias"):
            raise ValueError("olmo_hybrid: attention_bias is not served")
        heads = cfg["num_attention_heads"]
        lin_heads = cfg["linear_num_value_heads"]
        if cfg["linear_num_key_heads"] != lin_heads:
            raise ValueError(
                "olmo_hybrid: linear_num_key_heads and linear_num_value_heads differ; "
                "grouped value heads are not served")
        layer_types = tuple(cfg["layer_types"])
        if len(layer_types) != cfg["num_hidden_layers"]:
            raise ValueError(
                f"olmo_hybrid: {len(layer_types)} layer_types for "
                f"{cfg['num_hidden_layers']} layers")
        from smg_tpu.models.olmo_hybrid import period_of

        period_of(layer_types)  # one period repeated, or a ValueError that says so
        rope = dict(cfg.get("rope_parameters") or {})
        theta = rope.pop("rope_theta", None)
        if cfg.get("rope_theta", theta) != theta:
            raise ValueError("olmo_hybrid: rope_theta and rope_parameters.rope_theta disagree")
        if rope.pop("rope_type", "default") != "default" or rope:
            raise ValueError(f"olmo_hybrid: rope_parameters {cfg['rope_parameters']} is not served")
        eos = cfg.get("eos_token_id", 2)
        return cls(
            arch="olmo_hybrid",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads") or heads,
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
            rope_theta=float(theta or 0.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
            bos_token_id=cfg.get("bos_token_id", 1),
            dtype=dtype,
            layer_types=layer_types,
            linear_num_heads=lin_heads,
            linear_key_head_dim=cfg["linear_key_head_dim"],
            linear_value_head_dim=cfg["linear_value_head_dim"],
            linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
            linear_allow_neg_eigval=bool(cfg.get("linear_allow_neg_eigval", False)),
        )

    # ``pangu_ultra_moe`` (openPangu-Ultra-MoE): the same rule as above.
    _PANGU_CONSUMED = frozenset({
        "model_type", "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "hidden_act", "max_position_embeddings", "attention_bias",
        "rms_norm_eps", "tie_word_embeddings", "rope_theta", "rope_scaling",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "first_k_dense_replace", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob", "scoring_func",
        "sandwich_norm", "num_nextn_predict_layers", "eos_token_id", "bos_token_id",
        # the chip's share of a deployment (model-configs guide, section 4):
        # ``n_routed_experts`` counts the experts held here, these two say of
        # how many the router chooses and where the held range starts
        "router_num_experts", "routed_expert_offset",
        # random weights only: see ``ModelConfig.random_routed_out_gain``
        "random_routed_out_gain",
    })

    @classmethod
    def _from_pangu_ultra_moe(cls, cfg: dict, dtype: str) -> "ModelConfig":
        unknown = sorted(set(cfg) - cls._PANGU_CONSUMED - cls._OLMO_HYBRID_SHAPELESS)
        if unknown:
            raise ValueError(
                f"pangu_ultra_moe config.json has keys this loader does not consume: {unknown}; "
                "a key that may bear on the model's shape is not dropped in silence")
        if cfg.get("hidden_act", "silu") != "silu":
            raise ValueError(f"pangu_ultra_moe: hidden_act {cfg['hidden_act']!r} is not served")
        if cfg.get("attention_bias"):
            raise ValueError("pangu_ultra_moe: attention_bias is not served")
        if not cfg.get("sandwich_norm", False):
            raise ValueError("pangu_ultra_moe: only sandwich_norm true is served")
        if cfg.get("rope_scaling") not in (None, "none"):
            raise ValueError(f"pangu_ultra_moe: rope_scaling {cfg['rope_scaling']} is not served")
        if cfg.get("scoring_func", "sigmoid") not in ("sigmoid", "softmax"):
            raise ValueError(f"pangu_ultra_moe: scoring_func {cfg['scoring_func']!r} is not served")
        heads = cfg["num_attention_heads"]
        if cfg.get("num_key_value_heads", heads) != heads:
            raise ValueError("pangu_ultra_moe: latent attention has one latent for all heads; "
                             "num_key_value_heads must equal num_attention_heads")
        held = cfg["n_routed_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("routed_expert_offset", 0)
        if not (0 <= first and first + held <= width):
            raise ValueError(
                f"pangu_ultra_moe: experts {first}..{first + held - 1} are not among "
                f"the router's {width}")
        layers, dense = cfg["num_hidden_layers"], cfg.get("first_k_dense_replace", 0)
        if not 0 <= dense <= layers:
            raise ValueError(f"pangu_ultra_moe: first_k_dense_replace {dense} of {layers} layers")
        # ``num_nextn_predict_layers``: the next-token prediction module is a
        # drafter; the model's own logits do not depend on it.  It is consumed
        # here and neither loaded nor served (SERVING_LIMITS["speculative"]).
        eos = cfg.get("eos_token_id", 2)
        return cls(
            arch="pangu_ultra_moe",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=heads,
            head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
            bos_token_id=cfg.get("bos_token_id", 1),
            dtype=dtype,
            post_norms=True,
            num_experts=width,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            first_k_dense_replace=dense,
            n_shared_experts=cfg.get("n_shared_experts", 0),
            moe_scoring=cfg.get("scoring_func", "sigmoid"),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            experts_held=(first, held),
            random_routed_out_gain=float(cfg.get("random_routed_out_gain", 1.0)),
        )

    # ``mimo_v2_flash`` (MiMo-V2-Flash): the same rule as above.
    _MIMO_CONSUMED = frozenset({
        "model_type", "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "swa_num_key_value_heads", "head_dim", "v_head_dim",
        "swa_num_attention_heads", "swa_head_dim", "swa_v_head_dim",
        "hidden_act", "max_position_embeddings", "attention_bias", "layernorm_epsilon",
        "tie_word_embeddings", "rope_theta", "swa_rope_theta", "rope_scaling",
        "partial_rotary_factor", "hybrid_layer_pattern", "sliding_window",
        "sliding_window_size", "attention_chunk_size", "add_swa_attention_sink_bias",
        "add_full_attention_sink_bias", "attention_value_scale", "moe_layer_freq",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "scoring_func",
        "topk_method", "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor",
        "eos_token_id", "bos_token_id",
        # the chip's share of a deployment, as for ``pangu_ultra_moe``
        "router_num_experts", "routed_expert_offset",
        # random weights only: see ``ModelConfig.random_routed_out_gain``
        "random_routed_out_gain",
    })

    @classmethod
    def _from_mimo_v2_flash(cls, cfg: dict, dtype: str) -> "ModelConfig":
        unknown = sorted(set(cfg) - cls._MIMO_CONSUMED - cls._OLMO_HYBRID_SHAPELESS)
        if unknown:
            raise ValueError(
                f"mimo_v2_flash config.json has keys this loader does not consume: {unknown}; "
                "a key that may bear on the model's shape is not dropped in silence")

        def only(key, served, default):
            if cfg.get(key, default) not in served:
                raise ValueError(f"mimo_v2_flash: {key} {cfg[key]!r} is not served")

        only("hidden_act", ("silu",), "silu")
        only("attention_bias", (False, None), False)
        only("rope_scaling", (None, "none"), None)
        only("scoring_func", ("sigmoid",), "sigmoid")
        only("topk_method", ("noaux_tc",), "noaux_tc")
        only("n_group", (1, None), 1)
        only("topk_group", (1, None), 1)
        only("n_shared_experts", (0, None), 0)
        only("add_full_attention_sink_bias", (False, None), False)
        only("routed_scaling_factor", (None, 1, 1.0), None)
        window = cfg["sliding_window"]
        for key in ("sliding_window_size", "attention_chunk_size"):
            if cfg.get(key, window) != window:
                raise ValueError(
                    f"mimo_v2_flash: {key} {cfg[key]} differs from sliding_window {window}; "
                    "what a second window would mean is not served")
        for key, same in (("swa_num_attention_heads", "num_attention_heads"),
                          ("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim")):
            if cfg.get(key, cfg[same]) != cfg[same]:
                raise ValueError(
                    f"mimo_v2_flash: {key} {cfg[key]} differs from {same} {cfg[same]}; window "
                    "layers with query heads of their own shape are not served")
        layers = cfg["num_hidden_layers"]
        pattern, moe = tuple(cfg["hybrid_layer_pattern"]), tuple(cfg["moe_layer_freq"])
        if len(pattern) != layers or len(moe) != layers \
                or not set(pattern) <= {0, 1} or not set(moe) <= {0, 1}:
            raise ValueError(
                f"mimo_v2_flash: hybrid_layer_pattern ({len(pattern)}) and moe_layer_freq "
                f"({len(moe)}) must give a 0 or a 1 for each of {layers} layers")
        if 1 not in pattern:
            raise ValueError("mimo_v2_flash: no sliding-window layer in hybrid_layer_pattern")
        D = cfg["head_dim"]
        rope_dim = int(D * cfg.get("partial_rotary_factor", 1.0))
        if rope_dim % 2 or not 0 < rope_dim <= D:
            raise ValueError(f"mimo_v2_flash: rotary over {rope_dim} of {D} lanes")
        held = cfg["n_routed_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("routed_expert_offset", 0)
        if not (0 <= first and first + held <= width):
            raise ValueError(
                f"mimo_v2_flash: experts {first}..{first + held - 1} are not among "
                f"the router's {width}")
        heads = cfg["num_attention_heads"]
        eos = cfg.get("eos_token_id", 2)
        return cls(
            arch="mimo_v2_flash",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=D,
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("layernorm_epsilon", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
            bos_token_id=cfg.get("bos_token_id", 1),
            dtype=dtype,
            num_experts=width,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            qk_rope_head_dim=rope_dim,
            v_head_dim=cfg["v_head_dim"],
            moe_scoring="sigmoid",
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            experts_held=(first, held),
            sliding_window=window,
            layer_types=tuple("sliding_attention" if p else "full_attention" for p in pattern),
            swa_num_kv_heads=cfg["swa_num_key_value_heads"],
            swa_rope_theta=float(cfg.get("swa_rope_theta", 10000.0)),
            swa_sink_bias=bool(cfg.get("add_swa_attention_sink_bias", False)),
            attention_value_scale=float(cfg.get("attention_value_scale") or 1.0),
            moe_layer_freq=moe,
            moe_select_bias=True,
            random_routed_out_gain=float(cfg.get("random_routed_out_gain", 1.0)),
        )

    # ``exaone_moe`` (K-EXAONE): the same rule as above.
    _EXAONE_CONSUMED = frozenset({
        "model_type", "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "hidden_act", "max_position_embeddings",
        "rms_norm_eps", "tie_word_embeddings", "rope_parameters", "layer_types",
        "mlp_layer_types", "sliding_window", "sliding_window_pattern", "sliding_windows",
        "first_k_dense_replace", "num_experts", "num_experts_per_tok", "num_shared_experts",
        "scoring_func", "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor",
        "num_nextn_predict_layers", "mtp_layer_types", "mtp_sliding_windows",
        "eos_token_id", "bos_token_id",
        # the chip's share of a deployment, as for ``pangu_ultra_moe``
        "router_num_experts", "routed_expert_offset",
    })

    @classmethod
    def _from_exaone_moe(cls, cfg: dict, dtype: str) -> "ModelConfig":
        unknown = sorted(set(cfg) - cls._EXAONE_CONSUMED - cls._OLMO_HYBRID_SHAPELESS)
        if unknown:
            raise ValueError(
                f"exaone_moe config.json has keys this loader does not consume: {unknown}; "
                "a key that may bear on the model's shape is not dropped in silence")

        def only(key, served, default):
            if cfg.get(key, default) not in served:
                raise ValueError(f"exaone_moe: {key} {cfg[key]!r} is not served")

        only("hidden_act", ("silu",), "silu")
        only("scoring_func", ("sigmoid",), "sigmoid")
        only("n_group", (1, None), 1)
        only("topk_group", (1, None), 1)
        only("num_shared_experts", (1,), 1)
        only("num_nextn_predict_layers", (0, 1, None), 0)
        rope = cfg.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"exaone_moe: rope_parameters {rope} is not served")
        layers, window = cfg["num_hidden_layers"], cfg["sliding_window"]
        kinds, mlps = tuple(cfg["layer_types"]), tuple(cfg["mlp_layer_types"])
        if len(kinds) != layers or len(mlps) != layers \
                or not set(kinds) <= {"sliding_attention", "full_attention"} \
                or not set(mlps) <= {"dense", "sparse"}:
            raise ValueError(
                f"exaone_moe: layer_types ({len(kinds)}) and mlp_layer_types ({len(mlps)}) must "
                f"name each of {layers} layers sliding_attention or full_attention, dense or "
                "sparse")
        if "sliding_attention" not in kinds:
            raise ValueError("exaone_moe: no sliding_attention layer in layer_types")
        windows = tuple(cfg.get("sliding_windows") or ())
        if windows and windows != tuple(window if k == "sliding_attention" else 0 for k in kinds):
            raise ValueError(
                f"exaone_moe: sliding_windows {list(windows)} is not sliding_window {window} in "
                "the sliding layers and 0 in the full ones; a window a layer is not served")
        dense = cfg.get("first_k_dense_replace", 0)
        if mlps != ("dense",) * dense + ("sparse",) * (layers - dense):
            raise ValueError(
                f"exaone_moe: mlp_layer_types is not first_k_dense_replace {dense} dense layers "
                "and sparse ones behind them")
        mtp = cfg.get("num_nextn_predict_layers") or 0
        if mtp and (tuple(cfg.get("mtp_layer_types") or ("full_attention",)) != ("full_attention",)
                    or tuple(cfg.get("mtp_sliding_windows") or (0,)) != (0,)):
            raise ValueError("exaone_moe: a next-token module that is not one full_attention "
                             "layer is not served")
        held = cfg["num_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("routed_expert_offset", 0)
        if not (0 <= first and first + held <= width):
            raise ValueError(
                f"exaone_moe: experts {first}..{first + held - 1} are not among "
                f"the router's {width}")
        eos = cfg.get("eos_token_id", 2)
        theta = float(rope.get("rope_theta", 1000000.0))
        return cls(
            arch="exaone_moe",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            rope_theta=0.0,  # the full layers apply no rotary embedding
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
            bos_token_id=cfg.get("bos_token_id", 1),
            dtype=dtype,
            num_experts=width,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            qk_norm=True,
            v_head_dim=cfg["head_dim"],
            first_k_dense_replace=dense,
            n_shared_experts=1,
            moe_scoring="sigmoid",
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor") or 1.0),
            experts_held=(first, held),
            sliding_window=window,
            layer_types=kinds,
            swa_num_kv_heads=cfg["num_key_value_heads"],
            swa_rope_theta=theta,
            moe_layer_freq=tuple(int(m == "sparse") for m in mlps),
            moe_select_bias=True,
            mtp_layers=mtp,
        )

    # ``longcat_flash`` (LongCat-Flash): the same rule as above, over the
    # published config's own key names (``num_layers``, ``ffn_hidden_size``,
    # ``expert_ffn_hidden_size``, ``moe_topk``, ``zero_expert_num``).
    _LONGCAT_CONSUMED = frozenset({
        "model_type", "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
        "num_layers", "num_attention_heads", "hidden_act", "max_position_embeddings",
        "attention_bias", "attention_method", "rms_norm_eps", "tie_word_embeddings",
        "rope_theta", "rope_scaling", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
        "n_routed_experts", "zero_expert_num", "zero_expert_type", "moe_topk",
        "routed_scaling_factor", "norm_topk_prob", "router_bias", "eos_token_id",
        "bos_token_id",
        # the chip's share of a deployment, as for ``pangu_ultra_moe``: the
        # real experts the router chooses among (its outputs are these and the
        # ``zero_expert_num`` identity experts behind them) and where the held
        # range starts
        "router_num_experts", "routed_expert_offset",
    })

    @classmethod
    def _from_longcat_flash(cls, cfg: dict, dtype: str) -> "ModelConfig":
        unknown = sorted(set(cfg) - cls._LONGCAT_CONSUMED - cls._OLMO_HYBRID_SHAPELESS)
        if unknown:
            raise ValueError(
                f"longcat_flash config.json has keys this loader does not consume: {unknown}; "
                "a key that may bear on the model's shape is not dropped in silence")

        def only(key, served, default):
            if cfg.get(key, default) not in served:
                raise ValueError(f"longcat_flash: {key} {cfg[key]!r} is not served")

        only("hidden_act", ("silu",), "silu")
        only("attention_bias", (False, None), False)
        only("attention_method", ("MLA",), "MLA")
        only("rope_scaling", (None, "none"), None)
        only("zero_expert_type", ("identity",), "identity")
        only("norm_topk_prob", (False, None), False)
        only("router_bias", (False, None), False)
        held = cfg["n_routed_experts"]
        real = cfg.get("router_num_experts", held)
        first = cfg.get("routed_expert_offset", 0)
        if not (0 <= first and first + held <= real):
            raise ValueError(
                f"longcat_flash: experts {first}..{first + held - 1} are not among "
                f"the router's {real} real experts")
        zero = cfg.get("zero_expert_num", 0)
        E, eos = cfg["hidden_size"], cfg.get("eos_token_id", 2)
        return cls(
            arch="longcat_flash",
            vocab_size=cfg["vocab_size"],
            hidden_size=E,
            intermediate_size=cfg["ffn_hidden_size"],
            num_layers=cfg["num_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_attention_heads"],
            head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
            bos_token_id=cfg.get("bos_token_id", 1),
            dtype=dtype,
            num_experts=real + zero,
            num_experts_per_tok=cfg["moe_topk"],
            moe_intermediate_size=cfg["expert_ffn_hidden_size"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            moe_scoring="softmax",
            norm_topk_prob=False,
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            experts_held=(first, held),
            moe_select_bias=True,
            attention_sublayers=2,
            zero_experts=zero,
            mla_q_scale=(E / cfg["q_lora_rank"]) ** 0.5 if cfg.get("mla_scale_q_lora") else 1.0,
            mla_kv_scale=(E / cfg["kv_lora_rank"]) ** 0.5 if cfg.get("mla_scale_kv_lora") else 1.0,
        )

    # ``nemotron_h`` (Nemotron-H, Nemotron 3): the same rule as above.
    _NEMOTRON_H_CONSUMED = frozenset({
        "model_type", "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "hybrid_override_pattern", "num_attention_heads", "num_key_value_heads", "head_dim",
        "attention_bias", "mlp_bias", "use_bias", "mlp_hidden_act", "max_position_embeddings",
        "norm_eps", "layer_norm_epsilon", "tie_word_embeddings", "sliding_window",
        "mamba_num_heads", "mamba_head_dim", "mamba_hidden_act", "mamba_proj_bias",
        "ssm_state_size", "n_groups", "conv_kernel", "chunk_size", "expand", "use_conv_bias",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "moe_intermediate_size",
        "moe_latent_size", "moe_shared_expert_intermediate_size", "moe_shared_expert_overlap",
        "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor",
        "num_nextn_predict_layers", "mtp_hybrid_override_pattern", "eos_token_id",
        "bos_token_id",
        # read by no layer of the published forward: the attention layers
        # carry no rotary embedding (the state-space layers carry order), the
        # time-step range shapes a checkpoint's initial ``dt_bias`` and clamps
        # nothing at inference, the rest say how the published code runs
        "rope_theta", "partial_rotary_factor", "time_step_min", "time_step_max",
        "time_step_floor", "rescale_prenorm_residual", "residual_in_fp32",
        "use_mamba_kernels", "num_logits_to_keep",
        # the chip's share of a deployment, as for ``pangu_ultra_moe``
        "router_num_experts", "routed_expert_offset",
        # random weights only: see ``ModelConfig.random_init``
        "random_weights",
    })
    #: ``hybrid_override_pattern``'s letters as ``layer_types`` names them
    NEMOTRON_H_LETTERS = {"M": "mamba", "E": "moe", "*": "full_attention"}

    @classmethod
    def _from_nemotron_h(cls, cfg: dict, dtype: str) -> "ModelConfig":
        unknown = sorted(set(cfg) - cls._NEMOTRON_H_CONSUMED - cls._OLMO_HYBRID_SHAPELESS)
        if unknown:
            raise ValueError(
                f"nemotron_h config.json has keys this loader does not consume: {unknown}; "
                "a key that may bear on the model's shape is not dropped in silence")

        def only(key, served, default):
            if cfg.get(key, default) not in served:
                raise ValueError(f"nemotron_h: {key} {cfg[key]!r} is not served")

        only("mlp_hidden_act", ("relu2",), "relu2")
        only("mamba_hidden_act", ("silu",), "silu")
        only("use_conv_bias", (True,), True)
        only("sliding_window", (None,), None)
        only("n_shared_experts", (1,), 1)
        only("moe_shared_expert_overlap", (False, None), False)
        for key in ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias"):
            only(key, (False, None), False)
        if (cfg.get("n_group", 1), cfg.get("topk_group", 1)) != (1, 1):
            raise ValueError("nemotron_h: a group limit on the router's picks "
                             "(n_group, topk_group) is not served")
        eps = cfg.get("norm_eps", 1e-5)
        if cfg.get("layer_norm_epsilon", eps) != eps:
            raise ValueError("nemotron_h: norm_eps and layer_norm_epsilon disagree")
        pattern = cfg["hybrid_override_pattern"]
        letters = cls.NEMOTRON_H_LETTERS
        if "-" in pattern:
            from smg_tpu.models.nemotron_h import SERVING_LIMITS

            raise ValueError(SERVING_LIMITS["dense_mlp_layer"])
        strange = sorted(set(pattern) - set(letters))
        if strange:
            raise ValueError(
                f"nemotron_h: hybrid_override_pattern has letters {strange} that name no "
                f"layer this program knows ({', '.join(sorted(letters))} are served)")
        if len(pattern) != cfg["num_hidden_layers"]:
            raise ValueError(f"nemotron_h: a pattern of {len(pattern)} letters for "
                             f"{cfg['num_hidden_layers']} layers")
        E = cfg["hidden_size"]
        Hm, Pm = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        if Hm * Pm != cfg.get("expand", Hm * Pm // E) * E:
            raise ValueError(f"nemotron_h: {Hm} state-space heads of {Pm} are not "
                             f"expand {cfg['expand']} x hidden_size {E}")
        if Hm % cfg["n_groups"]:
            raise ValueError(f"nemotron_h: n_groups {cfg['n_groups']} does not divide "
                             f"mamba_num_heads {Hm}")
        held = cfg["n_routed_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("routed_expert_offset", 0)
        if not (0 <= first and first + held <= width):
            raise ValueError(f"nemotron_h: experts {first}..{first + held - 1} are not among "
                             f"the router's {width}")
        if cfg.get("intermediate_size", cfg["moe_intermediate_size"]) != cfg["moe_intermediate_size"]:
            # ``intermediate_size`` is the width of a ``-`` layer's MLP, which
            # this row's pattern has none of; the family writes both alike
            raise ValueError("nemotron_h: intermediate_size and moe_intermediate_size differ")
        # ``num_nextn_predict_layers`` and ``mtp_hybrid_override_pattern``: the
        # next-token module is a drafter; it is consumed here and neither
        # loaded nor served (SERVING_LIMITS["speculative"])
        heads = cfg["num_attention_heads"]
        eos = cfg.get("eos_token_id", 2)
        return cls(
            arch="nemotron_h",
            vocab_size=cfg["vocab_size"],
            hidden_size=E,
            intermediate_size=cfg["moe_intermediate_size"],
            num_layers=len(pattern),
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads") or heads,
            head_dim=cfg.get("head_dim") or E // heads,
            rope_theta=0.0,
            rms_norm_eps=eps,
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
            bos_token_id=cfg.get("bos_token_id", 1),
            dtype=dtype,
            activation="relu2",
            layer_types=tuple(letters[c] for c in pattern),
            num_experts=width,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            n_shared_experts=1,
            moe_scoring="sigmoid",
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            experts_held=(first, held),
            random_init=tuple(sorted((k, float(v)) for k, v in
                                     (cfg.get("random_weights") or {}).items())),
            moe_select_bias=True,
            ssm_num_heads=Hm,
            ssm_head_dim=Pm,
            ssm_state_size=cfg["ssm_state_size"],
            ssm_groups=cfg["n_groups"],
            ssm_conv_kernel=cfg["conv_kernel"],
            ssm_chunk_size=cfg.get("chunk_size", 128),
            moe_latent_size=cfg["moe_latent_size"],
            moe_shared_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
        )

    # ``kimi_linear`` (Kimi-Linear): the same rule as above.
    _KIMI_LINEAR_CONSUMED = frozenset({
        "model_type", "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "hidden_act", "rms_norm_eps",
        "tie_word_embeddings", "model_max_length", "max_position_embeddings",
        "linear_attn_config", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "mla_use_nope", "first_k_dense_replace",
        "moe_layer_freq", "moe_intermediate_size", "num_experts", "num_experts_per_token",
        "num_shared_experts", "moe_renormalize", "moe_router_activation_func",
        "use_grouped_topk", "num_expert_group", "topk_group", "routed_scaling_factor",
        "num_nextn_predict_layers", "eos_token_id", "bos_token_id",
        # read by no layer of the published forward: nothing is rotated at any
        # position (``mla_use_nope``: order reaches the latent layers through
        # the KDA layers), and ``head_dim`` (hidden / heads) shapes nothing
        # beside ``qk_nope_head_dim``, ``qk_rope_head_dim`` and ``v_head_dim``
        "rope_theta", "rope_scaling", "head_dim",
        # the chip's share of a deployment, as for ``pangu_ultra_moe``
        "router_num_experts", "routed_expert_offset",
        # random weights only: see ``ModelConfig.random_init``
        "random_weights",
    })

    @classmethod
    def _from_kimi_linear(cls, cfg: dict, dtype: str) -> "ModelConfig":
        unknown = sorted(set(cfg) - cls._KIMI_LINEAR_CONSUMED - cls._OLMO_HYBRID_SHAPELESS)
        if unknown:
            raise ValueError(
                f"kimi_linear config.json has keys this loader does not consume: {unknown}; "
                "a key that may bear on the model's shape is not dropped in silence")
        if cfg.get("hidden_act", "silu") != "silu":
            raise ValueError(f"kimi_linear: hidden_act {cfg['hidden_act']!r} is not served")
        if not cfg.get("mla_use_nope", False):
            raise ValueError("kimi_linear: mla_use_nope false (a rotated shared key) is not "
                             "served: this loader's latent layers rotate nothing")
        if cfg.get("q_lora_rank") is not None:
            raise ValueError(f"kimi_linear: q_lora_rank {cfg['q_lora_rank']} (a low-rank step "
                             "in the query) is not served: the query is projected in one step")
        if cfg.get("rope_scaling") is not None:
            raise ValueError(f"kimi_linear: rope_scaling {cfg['rope_scaling']} is not served")
        if (cfg.get("num_expert_group", 1), cfg.get("topk_group", 1)) != (1, 1):
            raise ValueError("kimi_linear: a group limit on the router's picks "
                             "(num_expert_group, topk_group above 1) is not served")
        if cfg.get("moe_router_activation_func", "sigmoid") not in ("sigmoid", "softmax"):
            raise ValueError(f"kimi_linear: moe_router_activation_func "
                             f"{cfg['moe_router_activation_func']!r} is not served")
        if cfg.get("moe_layer_freq", 1) != 1:
            raise ValueError(f"kimi_linear: moe_layer_freq {cfg['moe_layer_freq']} is not served "
                             "(every layer behind the dense ones is an expert layer)")
        if cfg.get("num_shared_experts", 1) != 1:
            raise ValueError(f"kimi_linear: num_shared_experts {cfg['num_shared_experts']} "
                             "is not served")
        heads = cfg["num_attention_heads"]
        if cfg.get("num_key_value_heads", heads) != heads:
            raise ValueError("kimi_linear: latent attention has one latent for all heads; "
                             "num_key_value_heads must equal num_attention_heads")
        layers = cfg["num_hidden_layers"]
        lin = cfg["linear_attn_config"]
        kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
        # both lists count layers from 1
        both, neither = sorted(kda & full), sorted(set(range(1, layers + 1)) - kda - full)
        if both or neither or (kda | full) - set(range(1, layers + 1)):
            raise ValueError(
                f"kimi_linear: linear_attn_config must name each of the layers 1..{layers} in "
                f"kda_layers or in full_attn_layers, once: in both {both}, in neither {neither}, "
                f"past the last {sorted((kda | full) - set(range(1, layers + 1)))}")
        held = cfg["num_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("routed_expert_offset", 0)
        if not (0 <= first and first + held <= width):
            raise ValueError(f"kimi_linear: experts {first}..{first + held - 1} are not among "
                             f"the router's {width}")
        dense = cfg.get("first_k_dense_replace", 0)
        layer_types = tuple("kda" if l in kda else "full_attention"
                            for l in range(1, layers + 1))
        eos = cfg.get("eos_token_id", 163586)
        out = cls(
            arch="kimi_linear",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=heads,
            head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            rope_theta=0.0,
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("model_max_length")
            or cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
            bos_token_id=cfg.get("bos_token_id", 163584),
            dtype=dtype,
            layer_types=layer_types,
            linear_num_heads=lin["num_heads"],
            linear_key_head_dim=lin["head_dim"],
            linear_value_head_dim=lin["head_dim"],
            linear_conv_kernel_dim=lin["short_conv_kernel_size"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            num_experts=width,
            num_experts_per_tok=cfg["num_experts_per_token"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            first_k_dense_replace=dense,
            n_shared_experts=1,
            moe_scoring=cfg.get("moe_router_activation_func", "sigmoid"),
            norm_topk_prob=bool(cfg.get("moe_renormalize", True)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            experts_held=(first, held),
            moe_select_bias=True,
            random_init=tuple(sorted((k, float(v)) for k, v in
                                     (cfg.get("random_weights") or {}).items())),
        )
        from smg_tpu.models.kimi_linear import layout

        layout(out)  # the stack this program runs: a sentence for any other
        return out

    # ``glm_moe_dsa`` (GLM-5.2): the same rule as above.
    _GLM_DSA_CONSUMED = frozenset({
        "model_type", "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "qk_head_dim", "hidden_act",
        "max_position_embeddings", "attention_bias", "rms_norm_eps", "tie_word_embeddings",
        "rope_parameters", "rope_interleave", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
        "mlp_layer_types", "moe_layer_freq", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob", "scoring_func",
        "topk_method", "n_group", "topk_group", "ep_size", "num_nextn_predict_layers",
        "index_topk", "index_n_heads", "index_head_dim", "indexer_types",
        "indexer_rope_interleave", "index_share_for_mtp_iteration",
        # the rule the published ``indexer_types`` was written from; the list
        # is the truth and these three are read and checked for nothing
        "index_skip_topk_offset", "index_topk_freq", "index_topk_pattern",
        "eos_token_id", "bos_token_id",
        # the chip's share of a deployment, as for ``pangu_ultra_moe``
        "router_num_experts", "routed_expert_offset",
    })

    @classmethod
    def _from_glm_moe_dsa(cls, cfg: dict, dtype: str) -> "ModelConfig":
        unknown = sorted(set(cfg) - cls._GLM_DSA_CONSUMED - cls._OLMO_HYBRID_SHAPELESS)
        if unknown:
            raise ValueError(
                f"glm_moe_dsa config.json has keys this loader does not consume: {unknown}; "
                "a key that may bear on the model's shape is not dropped in silence")

        def only(key, served, default):
            if cfg.get(key, default) not in served:
                raise ValueError(f"glm_moe_dsa: {key} {cfg[key]!r} is not served")

        only("hidden_act", ("silu",), "silu")
        only("attention_bias", (False, None), False)
        only("scoring_func", ("sigmoid",), "sigmoid")
        only("topk_method", ("noaux_tc",), "noaux_tc")
        only("n_group", (1,), 1)
        only("topk_group", (1,), 1)
        only("moe_layer_freq", (1,), 1)
        only("ep_size", (1,), 1)
        only("rope_interleave", (True,), True)
        only("indexer_rope_interleave", (True,), True)
        only("index_topk_pattern", (None,), None)
        rope = cfg.get("rope_parameters")
        if not isinstance(rope, dict) or set(rope) - {"rope_theta", "rope_type"} \
                or rope.get("rope_type", "default") != "default" or "rope_theta" not in rope:
            raise ValueError(f"glm_moe_dsa: rope_parameters {rope!r} is not served (a table "
                             "of rope_theta and rope_type 'default')")
        heads = cfg["num_attention_heads"]
        if cfg.get("num_key_value_heads", heads) != heads:
            raise ValueError("glm_moe_dsa: latent attention has one latent for all heads; "
                             "num_key_value_heads must equal num_attention_heads")
        dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        if cfg.get("qk_head_dim", dn + dr) != dn + dr or cfg.get("head_dim", dn) not in (dn, dn + dr):
            raise ValueError(f"glm_moe_dsa: qk_head_dim {cfg.get('qk_head_dim')} and head_dim "
                             f"{cfg.get('head_dim')} do not follow from qk_nope_head_dim {dn} "
                             f"and qk_rope_head_dim {dr}")
        layers = cfg["num_hidden_layers"]
        mlps = cfg.get("mlp_layer_types")
        dense = cfg.get("first_k_dense_replace", 0)
        if mlps is None:
            mlps = ["dense"] * dense + ["sparse"] * (layers - dense)
        if list(mlps) != ["dense"] * dense + ["sparse"] * (layers - dense) or not 0 <= dense <= layers:
            raise ValueError(f"glm_moe_dsa: mlp_layer_types {list(mlps)} is not first_k_dense_replace "
                             f"{dense} dense layers and then sparse ones, {layers} in all")
        kinds = cfg.get("indexer_types")
        if (not isinstance(kinds, (list, tuple)) or len(kinds) != layers
                or set(kinds) - {"full", "shared"} or kinds[0] != "full"):
            raise ValueError(f"glm_moe_dsa: indexer_types {kinds!r} is not a list of 'full' and "
                             f"'shared', one a layer ({layers}), that starts 'full'")
        topk, ih, idim = cfg["index_topk"], cfg["index_n_heads"], cfg["index_head_dim"]
        if topk < 1 or ih < 1 or idim < dr:
            raise ValueError(f"glm_moe_dsa: index_topk {topk}, index_n_heads {ih}, index_head_dim "
                             f"{idim} (the rotary lanes are its first {dr})")
        held = cfg["n_routed_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("routed_expert_offset", 0)
        if not (0 <= first and first + held <= width):
            raise ValueError(
                f"glm_moe_dsa: experts {first}..{first + held - 1} are not among "
                f"the router's {width}")
        # ``num_nextn_predict_layers`` and ``index_share_for_mtp_iteration``:
        # the next-token module is a drafter the model's own logits do not
        # depend on; consumed here, neither loaded nor served.
        eos = cfg.get("eos_token_id", 2)
        return cls(
            arch="glm_moe_dsa",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_layers=layers,
            num_heads=heads,
            num_kv_heads=heads,
            head_dim=dn + dr,
            rope_theta=float(rope["rope_theta"]),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
            bos_token_id=cfg.get("bos_token_id", 1),
            dtype=dtype,
            num_experts=width,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=dn,
            qk_rope_head_dim=dr,
            v_head_dim=cfg["v_head_dim"],
            first_k_dense_replace=dense,
            n_shared_experts=cfg.get("n_shared_experts", 0),
            moe_scoring="sigmoid",
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            experts_held=(first, held),
            moe_select_bias=True,
            index_topk=topk,
            index_n_heads=ih,
            index_head_dim=idim,
            indexer_types=tuple(kinds),
        )

    @classmethod
    def from_pretrained(cls, path: str, dtype: str = "bfloat16") -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_hf_config(json.load(f), dtype=dtype)


# ---- presets (BASELINE.md staged configs) ----

def tiny_test_config(vocab_size: int = 512) -> ModelConfig:
    """Tiny model for CPU tests: 4 layers, GQA 8q/2kv, head_dim 16."""
    return ModelConfig(
        vocab_size=vocab_size,
        hidden_size=128,
        intermediate_size=256,
        num_layers=4,
        num_heads=8,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        max_position_embeddings=2048,
        eos_token_ids=(0,),
        bos_token_id=1,
        dtype="float32",
    )


def llama32_1b_config() -> ModelConfig:
    return ModelConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        rope_theta=500000.0,
        rope_scaling={"rope_type": "llama3", "factor": 32.0, "low_freq_factor": 1.0,
                      "high_freq_factor": 4.0, "original_max_position_embeddings": 8192},
        tie_word_embeddings=True,
    )


def llama3_8b_config() -> ModelConfig:
    return ModelConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0,
    )


def llama3_70b_config() -> ModelConfig:
    return ModelConfig(
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0,
    )


def tiny_moe_config() -> ModelConfig:
    """Tiny Qwen-MoE-style config for CPU tests: 4 experts, top-2."""
    import dataclasses

    return dataclasses.replace(
        tiny_test_config(),
        arch="qwen_moe",
        num_experts=4,
        num_experts_per_tok=2,
        moe_intermediate_size=128,
    )


def tiny_vlm_config() -> ModelConfig:
    """Tiny Qwen2-VL-style VLM for CPU tests: tiny LLM + tiny vision tower.
    Placeholder token 500 plays <|image_pad|> (reference: the EPD encode leg,
    ``stages/encode.rs``)."""
    import dataclasses

    from smg_tpu.models.vit import tiny_vision_config

    base = tiny_test_config()
    return dataclasses.replace(
        base,
        vision=tiny_vision_config(out_hidden_size=base.hidden_size),
        image_token_id=500,
    )


def tiny_olmo_hybrid_config(vocab_size: int = 512) -> ModelConfig:
    """Tiny Olmo-Hybrid for CPU tests: two periods of two linear-attention
    layers and one full-attention layer, state heads that fill whole 128-lane
    tiles so the decode kernel runs in interpret mode."""
    import dataclasses

    return dataclasses.replace(
        tiny_test_config(vocab_size),
        arch="olmo_hybrid",
        num_layers=6,
        num_kv_heads=8,
        rope_theta=0.0,
        rms_norm_eps=1e-6,
        layer_types=("linear_attention", "linear_attention", "full_attention") * 2,
        linear_num_heads=4,
        linear_key_head_dim=16,
        linear_value_head_dim=32,
        linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True,
    )


def tiny_pangu_moe_config(vocab_size: int = 512, held: "tuple[int, int] | None" = None
                          ) -> ModelConfig:
    """Tiny openPangu-Ultra-MoE for CPU tests: one dense layer and two expert
    layers, latent attention whose cache entry (96 + 32) fills one 128-lane
    tile, 16 routed experts (top 4) of which ``held`` are here (None: all)."""
    import dataclasses

    return dataclasses.replace(
        tiny_test_config(vocab_size),
        arch="pangu_ultra_moe",
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=48,
        post_norms=True,
        num_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=64,
        q_lora_rank=64,
        kv_lora_rank=96,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=32,
        first_k_dense_replace=1,
        n_shared_experts=1,
        moe_scoring="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        experts_held=held,
    )


def tiny_longcat_flash_config(vocab_size: int = 512, held: "tuple[int, int] | None" = None,
                              **changes) -> ModelConfig:
    """Tiny LongCat-Flash for CPU tests: two double layers (four cache
    layers), latent attention whose cache entry (96 + 32) fills one 128-lane
    tile, a router of 24 real experts (of which ``held`` are here; None: all)
    and 8 identity experts behind them, top 6, both scales on."""
    import dataclasses

    return dataclasses.replace(
        tiny_test_config(vocab_size),
        arch="longcat_flash",
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        head_dim=48,
        num_experts=32,
        num_experts_per_tok=6,
        moe_intermediate_size=64,
        q_lora_rank=64,
        kv_lora_rank=96,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=32,
        moe_scoring="softmax",
        norm_topk_prob=False,
        routed_scaling_factor=6.0,
        experts_held=held,
        moe_select_bias=True,
        attention_sublayers=2,
        zero_experts=8,
        mla_q_scale=(128 / 64) ** 0.5,
        mla_kv_scale=(128 / 96) ** 0.5,
        **changes,
    )


def tiny_glm_dsa_config(vocab_size: int = 512, held: "tuple[int, int] | None" = None,
                        **changes) -> ModelConfig:
    """Tiny GLM-5.2 for CPU tests: a dense layer and four expert layers whose
    indexers are full, full, shared, shared, shared; latent attention whose
    cache entry (96 + 32) fills one 128-lane tile; an indexer of 4 heads of 32
    (rotary on the first 16 lanes) that chooses 16 cached tokens; a router of
    16 experts (top 4, of which ``held`` are here; None: all) and one shared."""
    import dataclasses

    return dataclasses.replace(
        tiny_test_config(vocab_size),
        arch="glm_moe_dsa",
        num_layers=5,
        num_heads=4,
        num_kv_heads=4,
        head_dim=48,
        num_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=64,
        q_lora_rank=64,
        kv_lora_rank=96,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=32,
        first_k_dense_replace=1,
        n_shared_experts=1,
        moe_scoring="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        experts_held=held,
        moe_select_bias=True,
        **{"index_topk": 16, "index_n_heads": 4, "index_head_dim": 32,
           "indexer_types": ("full", "full", "shared", "shared", "shared"), **changes},
    )


def tiny_nemotron_h_config(vocab_size: int = 512, held: "tuple[int, int] | None" = None,
                           pattern: str = "MEM*EME", **changes) -> ModelConfig:
    """Tiny Nemotron-H for CPU tests: ``pattern`` of state-space (``M``: 4
    heads of 16 in 2 groups, state 16, chunks of 8), latent-expert (``E``: a
    router of 16, top 4, of which ``held`` are here, None: all; latent 32,
    experts 48 wide, the shared expert 96) and unrotated attention layers
    (``*``: 4 query and 2 key/value heads of 64, a whole 128-lane tile a
    token), one mixer or one feed-forward part a layer."""
    import dataclasses

    return dataclasses.replace(
        tiny_test_config(vocab_size),
        arch="nemotron_h",
        num_layers=len(pattern),
        num_heads=4,
        num_kv_heads=2,
        head_dim=64,
        rope_theta=0.0,
        rms_norm_eps=1e-5,
        activation="relu2",
        layer_types=tuple(ModelConfig.NEMOTRON_H_LETTERS[c] for c in pattern),
        intermediate_size=48,
        num_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=48,
        n_shared_experts=1,
        moe_scoring="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        experts_held=held,
        moe_select_bias=True,
        ssm_num_heads=4,
        ssm_head_dim=16,
        ssm_state_size=16,
        ssm_groups=2,
        ssm_conv_kernel=4,
        ssm_chunk_size=8,
        moe_latent_size=32,
        moe_shared_intermediate_size=96,
        # the sizes of the drawing the benchmark's configuration of this
        # architecture sets, so that the toy's tests hear what its check hears
        random_init=(("attn_out", 4.0), ("bc_gain", 2.0), ("dt_max", 0.5), ("dt_min", 0.02),
                     ("routed_out", 1.5), ("score_std", 1.5), ("shared_out", 0.5)),
        **changes,
    )


def tiny_kimi_linear_config(vocab_size: int = 512, held: "tuple[int, int] | None" = None,
                            layers: int = 8, **changes) -> ModelConfig:
    """Tiny Kimi-Linear for CPU tests: periods of three KDA layers (4 heads
    with keys of 16 and values of 32, 4 taps) and one unrotated latent layer (4
    heads of 32 + 16 and 32 over a latent of 64: one 128-lane entry a token),
    layer 1's feed-forward part a dense MLP and every other a router of 16,
    top 4, of which ``held`` are here (None: all), experts 48 wide."""
    import dataclasses

    changes = {"layer_types": tuple("full_attention" if l % 4 == 3 else "kda"
                                    for l in range(layers)),
               "first_k_dense_replace": 1, **changes}
    return dataclasses.replace(
        tiny_test_config(vocab_size),
        arch="kimi_linear",
        num_layers=layers,
        num_heads=4,
        num_kv_heads=4,
        head_dim=48,
        rope_theta=0.0,
        rms_norm_eps=1e-5,
        linear_num_heads=4,
        linear_key_head_dim=16,
        linear_value_head_dim=32,
        linear_conv_kernel_dim=4,
        kv_lora_rank=64,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=32,
        intermediate_size=96,
        num_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=48,
        n_shared_experts=1,
        moe_scoring="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=2.446,
        experts_held=held,
        moe_select_bias=True,
        **changes,
    )


def kimi_linear_48b_a3b_config() -> ModelConfig:
    """Kimi-Linear-48B-A3B-Instruct as published (27 layers, 256 experts,
    163,840 rows): 98 GB in bfloat16, so no single chip serves it whole;
    ``benchmark/configs/kimi-linear-48b-a3b.json`` is one chip's share."""
    kda = [l for l in range(1, 28) if l % 4 and l != 27]
    return ModelConfig.from_hf_config({
        "model_type": "kimi_linear", "vocab_size": 163840, "hidden_size": 2304,
        "intermediate_size": 9216, "num_hidden_layers": 27, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 72, "hidden_act": "silu", "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False, "model_max_length": 1048576, "rope_theta": 10000,
        "rope_scaling": None, "kv_lora_rank": 512, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "mla_use_nope": True, "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "moe_intermediate_size": 1024, "num_experts": 256, "num_experts_per_token": 8,
        "num_shared_experts": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "use_grouped_topk": True,
        "num_expert_group": 1, "topk_group": 1, "routed_scaling_factor": 2.446,
        "num_nextn_predict_layers": 0,
        "linear_attn_config": {
            "kda_layers": kda, "full_attn_layers": [l for l in range(1, 28) if l not in kda],
            "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4},
    })


def glm_5_2_config() -> ModelConfig:
    """GLM-5.2 as published (78 layers, 256 experts, 154,880 rows): 1.5 TB in
    bfloat16, so no single chip serves it whole;
    ``benchmark/configs/glm-5.2.json`` is one chip's share."""
    kinds = ["full" if l < 3 or (l - 3) % 4 == 3 else "shared" for l in range(78)]
    return ModelConfig.from_hf_config({
        "model_type": "glm_moe_dsa", "vocab_size": 154880, "hidden_size": 6144,
        "intermediate_size": 12288, "moe_intermediate_size": 2048, "num_hidden_layers": 78,
        "num_attention_heads": 64, "num_key_value_heads": 64, "head_dim": 192,
        "qk_head_dim": 256, "hidden_act": "silu", "rms_norm_eps": 1e-5,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "tie_word_embeddings": False, "rope_interleave": True,
        "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
        "q_lora_rank": 2048, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "first_k_dense_replace": 3,
        "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 75, "moe_layer_freq": 1,
        "n_routed_experts": 256, "n_shared_experts": 1, "num_experts_per_tok": 8,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "ep_size": 1,
        "num_nextn_predict_layers": 1, "index_topk": 2048, "index_n_heads": 32,
        "index_head_dim": 128, "indexer_types": kinds, "indexer_rope_interleave": True,
        "index_share_for_mtp_iteration": True, "index_skip_topk_offset": 3,
        "index_topk_freq": 4, "index_topk_pattern": None,
    })


def tiny_mimo_config(vocab_size: int = 512, held: "tuple[int, int] | None" = None,
                     **changes) -> ModelConfig:
    """Tiny MiMo-V2-Flash for CPU tests: a dense full-attention layer, three
    window layers (window 8) and a full one with routed experts (16, top 4, of
    which ``held`` are here; None: all).  16 heads of 64 with values of 32,
    rotary over 16 lanes; 4 key/value heads in the full layers (K lanes 256,
    V 128) and 8 in the window layers (512 and 256): whole 128-lane tiles, so
    that both decode kernels run in interpret mode."""
    import dataclasses

    return dataclasses.replace(
        tiny_test_config(vocab_size),
        arch="mimo_v2_flash",
        num_layers=5,
        num_heads=16,
        num_kv_heads=4,
        head_dim=64,
        v_head_dim=32,
        qk_rope_head_dim=16,
        rope_theta=5000000.0,
        num_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=64,
        moe_scoring="sigmoid",
        norm_topk_prob=True,
        experts_held=held,
        sliding_window=8,
        layer_types=("full_attention", "sliding_attention", "sliding_attention",
                     "sliding_attention", "full_attention"),
        swa_num_kv_heads=8,
        swa_rope_theta=10000.0,
        swa_sink_bias=True,
        attention_value_scale=0.707,
        moe_layer_freq=(0, 1, 1, 1, 1),
        moe_select_bias=True,
        **changes,
    )


def tiny_exaone_moe_config(vocab_size: int = 512, held: "tuple[int, int] | None" = None,
                           **changes) -> ModelConfig:
    """Tiny K-EXAONE for CPU tests: a dense window layer, three window layers
    (window 8) and a full one with routed experts (16, top 4, of which ``held``
    are here; None: all) and a shared expert, and the next-token module behind
    them.  16 query and 4 key/value heads of 64: K and V lanes 256 in both
    kinds, whole 128-lane tiles, so that both decode kernels run in interpret
    mode."""
    import dataclasses

    return dataclasses.replace(
        tiny_test_config(vocab_size),
        arch="exaone_moe",
        num_layers=5,
        num_heads=16,
        num_kv_heads=4,
        swa_num_kv_heads=4,
        head_dim=64,
        v_head_dim=64,
        qk_norm=True,
        rope_theta=0.0,
        swa_rope_theta=1000000.0,
        num_experts=16,
        num_experts_per_tok=4,
        moe_intermediate_size=64,
        first_k_dense_replace=1,
        n_shared_experts=1,
        moe_scoring="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        experts_held=held,
        sliding_window=8,
        layer_types=("sliding_attention", "sliding_attention", "sliding_attention",
                     "sliding_attention", "full_attention"),
        moe_layer_freq=(0, 1, 1, 1, 1),
        moe_select_bias=True,
        mtp_layers=1,
        **changes,
    )


def tiny_gemma2_config(vocab_size: int = 512) -> ModelConfig:
    """Tiny Gemma-2-style model for CPU tests: gelu MLP, (1+w) norms,
    scaled embeddings, post norms, attn/final softcaps, tied unembed."""
    import dataclasses

    return dataclasses.replace(
        tiny_test_config(vocab_size),
        activation="gelu_tanh",
        rms_unit_offset=True,
        embed_scale=True,
        post_norms=True,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        query_scale=1.0 / (32.0 ** 0.5),
        sliding_window=4096,
        tie_word_embeddings=True,
    )


def tiny_vlm_mrope_config() -> ModelConfig:
    """Tiny VLM with Qwen2-VL M-RoPE enabled (head_dim 16 -> D/2 = 8 =
    2+3+3 frequency sections)."""
    import dataclasses

    return dataclasses.replace(
        tiny_vlm_config(),
        rope_scaling={"type": "mrope", "mrope_section": [2, 3, 3]},
    )


PRESETS = {
    "tiny": tiny_test_config,
    "tiny-gemma2": tiny_gemma2_config,
    "tiny-moe": tiny_moe_config,
    "tiny-vlm": tiny_vlm_config,
    "tiny-olmo-hybrid": tiny_olmo_hybrid_config,
    "tiny-pangu-moe": tiny_pangu_moe_config,
    "tiny-mimo": tiny_mimo_config,
    "tiny-exaone-moe": tiny_exaone_moe_config,
    "tiny-longcat-flash": tiny_longcat_flash_config,
    "tiny-nemotron-h": tiny_nemotron_h_config,
    "tiny-kimi-linear": tiny_kimi_linear_config,
    "tiny-glm-dsa": tiny_glm_dsa_config,
    "kimi-linear-48b-a3b": kimi_linear_48b_a3b_config,
    "glm-5.2": glm_5_2_config,
    "llama3.2-1b": llama32_1b_config,
    "llama3-8b": llama3_8b_config,
    "llama3-70b": llama3_70b_config,
}
