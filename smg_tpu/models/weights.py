"""HF safetensors -> smg_tpu param pytree loading, with sharded placement.

Reference analogue: weight loading lives in the external engines; in-tree
here.  Reads ``*.safetensors`` lazily tensor-by-tensor and places each on its
target sharding to avoid host-memory spikes.
"""

from __future__ import annotations

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from smg_tpu.utils import get_logger

logger = get_logger("models.weights")


def _hf_key_map(cfg, n_layers: int) -> dict[str, tuple[str, ...]]:
    """our param tree path -> HF tensor name template."""
    m = {
        ("embed",): "model.embed_tokens.weight",
        ("final_norm",): "model.norm.weight",
        ("layers", "attn_norm"): "model.layers.{i}.input_layernorm.weight",
        ("layers", "wq"): "model.layers.{i}.self_attn.q_proj.weight",
        ("layers", "wk"): "model.layers.{i}.self_attn.k_proj.weight",
        ("layers", "wv"): "model.layers.{i}.self_attn.v_proj.weight",
        ("layers", "wo"): "model.layers.{i}.self_attn.o_proj.weight",
        ("layers", "mlp_norm"): "model.layers.{i}.post_attention_layernorm.weight",
    }
    if cfg.qk_norm:
        m[("layers", "q_norm")] = "model.layers.{i}.self_attn.q_norm.weight"
        m[("layers", "k_norm")] = "model.layers.{i}.self_attn.k_norm.weight"
    if cfg.post_norms:
        # Gemma-2 four-norm layers: HF's post_attention_layernorm is the
        # POST-attention norm there, and the ffn pre-norm is its own key
        m[("layers", "mlp_norm")] = "model.layers.{i}.pre_feedforward_layernorm.weight"
        m[("layers", "post_attn_norm")] = "model.layers.{i}.post_attention_layernorm.weight"
        m[("layers", "post_mlp_norm")] = "model.layers.{i}.post_feedforward_layernorm.weight"
    if cfg.num_experts > 0:
        # Qwen-MoE naming: router = mlp.gate.weight, experts under mlp.experts.{e}
        m[("layers", "router")] = "model.layers.{i}.mlp.gate.weight"
        m[("layers", "w_gate")] = "model.layers.{i}.mlp.experts.{e}.gate_proj.weight"
        m[("layers", "w_up")] = "model.layers.{i}.mlp.experts.{e}.up_proj.weight"
        m[("layers", "w_down")] = "model.layers.{i}.mlp.experts.{e}.down_proj.weight"
    else:
        m[("layers", "w_gate")] = "model.layers.{i}.mlp.gate_proj.weight"
        m[("layers", "w_up")] = "model.layers.{i}.mlp.up_proj.weight"
        m[("layers", "w_down")] = "model.layers.{i}.mlp.down_proj.weight"
    if not cfg.tie_word_embeddings:
        m[("lm_head",)] = "lm_head.weight"
    return m


def _transform(path: tuple[str, ...], w: np.ndarray, cfg) -> np.ndarray:
    """HF [out, in] linear layout -> our einsum layouts."""
    E, H, K, D, F = (
        cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
    )
    del F  # linear transforms below are shape-agnostic transposes
    leaf = path[-1]
    if leaf == "router":
        return w.transpose(1, 0)  # [E, n_experts]
    if leaf == "wq":
        return w.reshape(H, D, E).transpose(2, 0, 1)  # [E, H, D]
    if leaf in ("wk", "wv"):
        return w.reshape(K, D, E).transpose(2, 0, 1)  # [E, K, D]
    if leaf == "wo":
        return w.reshape(E, H, D).transpose(1, 2, 0)  # [H, D, E]
    if leaf in ("w_gate", "w_up"):
        return w.transpose(1, 0)  # [E, F]
    if leaf == "w_down":
        return w.transpose(1, 0)  # [F, E]
    if leaf == "lm_head":
        return w.transpose(1, 0)  # [E, V]
    return w  # embed [V, E], norms [E]


def _open_checkpoint(path: str):
    """(handles, name->handle-index map) over all *.safetensors in ``path``."""
    from safetensors import safe_open

    files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no safetensors under {path}")
    location: dict[str, int] = {}
    handles = [safe_open(f, framework="numpy") for f in files]
    for i, h in enumerate(handles):
        for name in h.keys():
            location[name] = i
    return handles, location


def load_vision_params(engine_cfg):
    """Load the vision-tower pytree from a Qwen2-VL-style HF checkpoint
    (``visual.*`` keys) via ``models.vit.HF_VISION_MAPPING``.

    Layout transforms:
    - patch-embed conv ``[H, C, (T,) ps, ps]`` -> ``[patch_dim, H]``.  A
      temporal dim (Qwen2-VL Conv3d, T=2 frames) is collapsed by summing —
      for a single image the checkpoint's temporal patch is the same frame
      repeated, and conv over a repeated frame equals the summed-kernel conv.
      Element order becomes (ps, ps, C) to match ``multimodal.image.patchify``
      (which flattens [gh, gw, ps, ps, C] row-major).
    - linear ``[out, in]`` -> ``[in, out]`` (our right-multiply layout);
    - layer norms map to {scale, bias} from ``.weight``/``.bias``.
    Returns a pytree matching ``vit.init_vision_params`` structure.
    """
    import jax.numpy as jnp

    from smg_tpu.models.vit import HF_VISION_MAPPING

    cfg = engine_cfg.model
    vcfg = cfg.vision
    if vcfg is None:
        raise ValueError("model config has no vision tower")
    dtype = jnp.dtype(vcfg.dtype)
    handles, location = _open_checkpoint(engine_cfg.model_path)

    def fetch(name: str) -> np.ndarray:
        if name not in location:
            raise KeyError(f"tensor {name} not found in checkpoint")
        return handles[location[name]].get_tensor(name)

    def conv_to_matrix(w: np.ndarray) -> np.ndarray:
        if w.ndim == 5:  # [H, C, T, ps, ps] Conv3d: collapse temporal by sum
            w = w.sum(axis=2)
        H, C, ph, pw = w.shape
        # (ps, ps, C) element order to match patchify's flatten
        return w.transpose(2, 3, 1, 0).reshape(ph * pw * C, H)

    def linear(w: np.ndarray) -> np.ndarray:
        return w.transpose(1, 0)

    def norm(prefix: str) -> dict:
        return {
            "scale": jnp.asarray(fetch(prefix + ".weight"), dtype),
            "bias": jnp.asarray(fetch(prefix + ".bias"), dtype),
        }

    layers: list[dict] = []
    for i in range(vcfg.num_layers):
        layers.append({
            "ln1": norm(HF_VISION_MAPPING["layers.{i}.ln1"].format(i=i)),
            "qkv_w": linear(fetch(HF_VISION_MAPPING["layers.{i}.qkv_w"].format(i=i))),
            "qkv_b": fetch(HF_VISION_MAPPING["layers.{i}.qkv_b"].format(i=i)),
            "proj_w": linear(fetch(HF_VISION_MAPPING["layers.{i}.proj_w"].format(i=i))),
            "proj_b": fetch(HF_VISION_MAPPING["layers.{i}.proj_b"].format(i=i)),
            "ln2": norm(HF_VISION_MAPPING["layers.{i}.ln2"].format(i=i)),
            "fc1_w": linear(fetch(HF_VISION_MAPPING["layers.{i}.fc1_w"].format(i=i))),
            "fc1_b": fetch(HF_VISION_MAPPING["layers.{i}.fc1_b"].format(i=i)),
            "fc2_w": linear(fetch(HF_VISION_MAPPING["layers.{i}.fc2_w"].format(i=i))),
            "fc2_b": fetch(HF_VISION_MAPPING["layers.{i}.fc2_b"].format(i=i)),
        })
    stacked = jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x, dtype) for x in xs]), *layers
    )
    params = {
        "patch_embed": jnp.asarray(
            conv_to_matrix(fetch(HF_VISION_MAPPING["patch_embed"])), dtype
        ),
        "layers": stacked,
        "merger": {
            "ln_q": norm(HF_VISION_MAPPING["merger.ln_q"]),
            "mlp0_w": jnp.asarray(linear(fetch(HF_VISION_MAPPING["merger.mlp0_w"])), dtype),
            "mlp0_b": jnp.asarray(fetch(HF_VISION_MAPPING["merger.mlp0_b"]), dtype),
            "mlp2_w": jnp.asarray(linear(fetch(HF_VISION_MAPPING["merger.mlp2_w"])), dtype),
            "mlp2_b": jnp.asarray(fetch(HF_VISION_MAPPING["merger.mlp2_b"]), dtype),
        },
    }
    logger.info("loaded vision tower: %d layers, patch_embed %s",
                vcfg.num_layers, params["patch_embed"].shape)
    return params


def load_params(engine_cfg, mesh=None, rules=None):
    """Load params for ``engine_cfg.model`` from ``engine_cfg.model_path``."""
    cfg = engine_cfg.model
    from smg_tpu.models.registry import get_model as _module_of

    limits = getattr(_module_of(cfg.arch), "SERVING_LIMITS", {})
    if "checkpoint" in limits:
        # the Llama key map below would load some of its tensors and serve
        # another model
        raise ValueError(limits["checkpoint"])
    dtype = jnp.dtype(engine_cfg.dtype)
    handles, location = _open_checkpoint(engine_cfg.model_path)

    shardings = None
    if mesh is not None:
        from smg_tpu.models.registry import get_model
        from smg_tpu.parallel.sharding import tree_shardings, ShardingRules

        module = get_model(cfg.arch)
        shardings = tree_shardings(module.logical_axes(cfg), mesh, rules or ShardingRules())

    def fetch(name: str) -> np.ndarray:
        if name not in location:
            raise KeyError(f"tensor {name} not found in checkpoint")
        return handles[location[name]].get_tensor(name)

    key_map = _hf_key_map(cfg, cfg.num_layers)
    params: dict = {"layers": {}}
    for path_key, tmpl in key_map.items():
        if "{e}" in tmpl:
            # MoE expert weights: stack experts within each layer
            stack = [
                np.stack([
                    _transform(path_key, fetch(tmpl.format(i=i, e=e)), cfg)
                    for e in range(cfg.num_experts)
                ])
                for i in range(cfg.num_layers)
            ]
            arr = np.stack(stack)  # [L, X, ...]
        elif "{i}" in tmpl:
            stack = [
                _transform(path_key, fetch(tmpl.format(i=i)), cfg)
                for i in range(cfg.num_layers)
            ]
            arr = np.stack(stack)
        else:
            arr = _transform(path_key, fetch(tmpl), cfg)
        target = params
        for k in path_key[:-1]:
            target = target[k]
        sh = None
        if shardings is not None:
            node = shardings
            for k in path_key:
                node = node[k]
            sh = node
        jarr = jnp.asarray(arr, dtype=dtype)
        if sh is not None:
            jarr = jax.device_put(jarr, sh)
        target[path_key[-1]] = jarr
        logger.info("loaded %s %s", "/".join(path_key), jarr.shape)
    return params
