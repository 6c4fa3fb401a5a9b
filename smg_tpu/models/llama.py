"""Llama-family decoder (Llama 2/3/3.x, Mistral, Qwen2-dense) — functional JAX.

Design (TPU-first, not a port):
- Parameters are plain pytrees of stacked per-layer arrays (leading ``L`` axis)
  and the layer stack is a single ``lax.scan`` — one compiled layer body
  regardless of depth, fast XLA compiles, and pipeline-parallel friendly.
- Every array carries *logical* sharding axes (``logical_axes``); actual
  shardings come from ``smg_tpu.parallel.sharding.ShardingRules`` so
  TP/DP/EP relayouts never touch this file.
- KV cache is paged (``smg_tpu/ops/attention.py`` layout) and threaded through
  the layer scan as xs/ys so jit donation can alias the buffers.

Reference parity: serves the model families the reference routes to via
SGLang/vLLM workers (SURVEY.md §0); the in-tree engine replaces that layer.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from smg_tpu.models.config import ModelConfig
from smg_tpu.ops.attention import (
    attention_decode_cached,
    attention_prefill,
    attention_prefill_batched,
    attention_verify_block,
    gather_layer_pages,
    gather_seq_kv,
    page_slots,
    scatter_kv_pages_full,
)
from smg_tpu.ops.norms import rms_norm
from smg_tpu.ops.rope import apply_mrope, apply_rope

Params = dict[str, Any]


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random init (serving weights normally come from safetensors loading;
    random init backs tests and synthetic benches)."""
    E, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    H, K, D, V = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)

    def norm_init(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    layers: Params = {
        "attn_norm": jnp.ones((L, E), dtype),
        "wq": norm_init(ks[1], (L, E, H, D), 0.02),
        "wk": norm_init(ks[2], (L, E, K, D), 0.02),
        "wv": norm_init(ks[3], (L, E, K, D), 0.02),
        "wo": norm_init(ks[4], (L, H, D, E), 0.02 / math.sqrt(2 * L)),
        "mlp_norm": jnp.ones((L, E), dtype),
    }
    if cfg.rms_unit_offset:
        # Gemma convention: stored weight is a delta (scale = 1 + w), so
        # identity init is zeros
        layers["attn_norm"] = jnp.zeros((L, E), dtype)
        layers["mlp_norm"] = jnp.zeros((L, E), dtype)
    if cfg.post_norms:
        zero = jnp.zeros((L, E), dtype) if cfg.rms_unit_offset else jnp.ones((L, E), dtype)
        layers["post_attn_norm"] = zero
        layers["post_mlp_norm"] = zero
    if cfg.qk_norm:
        # Qwen3: per-head RMSNorm weights over head_dim for q and k
        layers["q_norm"] = jnp.ones((L, D), dtype)
        layers["k_norm"] = jnp.ones((L, D), dtype)
    if cfg.num_experts > 0:
        # MoE layers (Qwen-MoE family): router + stacked expert FFNs
        X = cfg.num_experts
        Fm = cfg.moe_intermediate_size or F
        layers["router"] = norm_init(jax.random.fold_in(key, 7), (L, E, X), 0.02)
        layers["w_gate"] = norm_init(ks[5], (L, X, E, Fm), 0.02)
        layers["w_up"] = norm_init(ks[6], (L, X, E, Fm), 0.02)
        layers["w_down"] = norm_init(ks[7], (L, X, Fm, E), 0.02 / math.sqrt(2 * L))
    else:
        layers["w_gate"] = norm_init(ks[5], (L, E, F), 0.02)
        layers["w_up"] = norm_init(ks[6], (L, E, F), 0.02)
        layers["w_down"] = norm_init(ks[7], (L, F, E), 0.02 / math.sqrt(2 * L))
    params: Params = {
        "embed": norm_init(ks[0], (V, E), 0.02),
        "layers": layers,
        "final_norm": (jnp.zeros((E,), dtype) if cfg.rms_unit_offset
                       else jnp.ones((E,), dtype)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm_init(jax.random.fold_in(key, 99), (E, V), 0.02)
    return params


def logical_axes(cfg: ModelConfig) -> Params:
    """Pytree of logical-axis tuples matching ``init_params`` exactly."""
    layers: Params = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "q_heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "q_heads", "head_dim", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if cfg.post_norms:
        layers["post_attn_norm"] = ("layers", "embed")
        layers["post_mlp_norm"] = ("layers", "embed")
    if cfg.qk_norm:
        layers["q_norm"] = ("layers", "head_dim")
        layers["k_norm"] = ("layers", "head_dim")
    if cfg.num_experts > 0:
        layers["router"] = ("layers", "embed", None)
        layers["w_gate"] = ("layers", "experts", "embed", "ffn")
        layers["w_up"] = ("layers", "experts", "embed", "ffn")
        layers["w_down"] = ("layers", "experts", "ffn", "embed")
    else:
        layers["w_gate"] = ("layers", "embed", "ffn")
        layers["w_up"] = ("layers", "embed", "ffn")
        layers["w_down"] = ("layers", "ffn", "embed")
    ax: Params = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("embed",),
    }
    if not cfg.tie_word_embeddings:
        ax["lm_head"] = ("embed", "vocab")
    return ax


def kv_cache_logical_axes() -> tuple[str | None, ...]:
    # [L, P, ps, K*D] — fused kv lanes sharded on tp (contiguous chunks of the
    # fused dim are whole kv-head groups), pages replicated per dp replica
    return ("layers", "pages", None, "kv_lanes")


# The ``smg.*`` named scopes (here, in the other model files, in ops/ and in
# engine/) are each compiled instruction's ``op_name``.  A device trace names
# an operation by its instruction and not by its scope, so the program
# publishes the map from the one to the other when a profile ends
# (``analysis/runtime_guards.ProgramAuditor.scope_map``), and device time can
# be split by layer half and by kernel; an operation belongs to the innermost
# ``smg.`` scope on its path, and every operation of a layer, from its input
# norm to its residual add, stands under a scope of its half's family.
# Metadata only: the compiled code is the same with and without them.
@jax.named_scope("smg.embed")
def embed_tokens(params: Params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    h = params["embed"][tokens]
    if cfg.embed_scale:  # Gemma: embeddings scaled by sqrt(hidden)
        h = h * jnp.asarray(math.sqrt(cfg.hidden_size), h.dtype)
    return h


@jax.named_scope("smg.lm_head")
def unembed(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    h = _norm(h, params["final_norm"], cfg)
    if cfg.tie_word_embeddings:
        logits = jnp.einsum("...e,ve->...v", h, params["embed"]).astype(jnp.float32)
    else:
        logits = jnp.einsum("...e,ev->...v", h, params["lm_head"]).astype(jnp.float32)
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits



def _norm(x: jnp.ndarray, weight: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Config-routed RMSNorm (Gemma models scale by 1 + weight)."""
    return rms_norm(x, weight, cfg.rms_norm_eps, unit_offset=cfg.rms_unit_offset)


def _act(x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """MLP gate activation: silu (llama family) or tanh-gelu (Gemma)."""
    if cfg.activation == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _attn_residual(h, layer, attn, cfg, lora=None, gates=None):
    """Residual add of the attention branch, with the Gemma-2 post-attention
    norm when configured."""
    o = _attn_out(layer, attn, lora, gates)
    if cfg.post_norms:
        o = _norm(o, layer["post_attn_norm"], cfg)
    return h + o


@jax.named_scope("smg.mlp")
def _mlp_residual(h, layer, cfg):
    """Pre-norm -> MLP -> (optional Gemma-2 post-ffn norm) -> residual."""
    o = _mlp(layer, _norm(h, layer["mlp_norm"], cfg), cfg)
    if cfg.post_norms:
        o = _norm(o, layer["post_mlp_norm"], cfg)
    return h + o



def _layer_window(cfg: ModelConfig, l) -> "jnp.ndarray | None":
    """Per-layer sliding window: every ``sliding_window_pattern``-th layer
    is GLOBAL (window 0), the rest use ``cfg.sliding_window`` (Gemma-2
    alternation); ``pattern <= 0`` = EVERY layer windowed (Mistral).
    ``l`` is the traced layer index from the scan; None when the model has
    no window at all.  NOTE ``l`` is stage-LOCAL under pp, so validation
    rejects pp>1 for alternating patterns."""
    if not cfg.sliding_window:
        return None
    p = cfg.sliding_window_pattern
    if p <= 0:
        return jnp.int32(cfg.sliding_window)
    return jnp.where((l % p) == (p - 1), 0, cfg.sliding_window)


def _lora_delta(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                gates: jnp.ndarray) -> jnp.ndarray:
    """Per-token multi-adapter LoRA delta, dense one-hot dispatch.

    ``x`` [..., E_in], ``a`` [N, E_in, r], ``b`` [N, r, E_out] (alpha/r scaling
    pre-folded into b), ``gates`` [..., N] one-hot adapter selection.  A
    TPU-first trade: compute every adapter's (tiny, rank-r)
    delta and mask — static shapes, no routing collectives; adapter slot 0 is
    all-zeros so un-adapted tokens pay nothing semantically (reference LoRA
    serving: Load/Unload/ListLoRAAdapter, sglang_scheduler.proto:48-62)."""
    t = jnp.einsum("...e,ner->...nr", x, a.astype(x.dtype))
    d = jnp.einsum("...nr,nro->...no", t, b.astype(x.dtype))
    return jnp.einsum("...no,...n->...o", d, gates.astype(x.dtype))


def _qkv(layer: Params, cfg: ModelConfig, h: jnp.ndarray,
         lora: Params | None = None, gates: jnp.ndarray | None = None):
    q = jnp.einsum("...e,ehd->...hd", h, layer["wq"])
    k = jnp.einsum("...e,ekd->...kd", h, layer["wk"])
    v = jnp.einsum("...e,ekd->...kd", h, layer["wv"])
    if lora is not None:
        q = q + _lora_delta(h, lora["wq_a"], lora["wq_b"], gates).reshape(q.shape)
        k = k + _lora_delta(h, lora["wk_a"], lora["wk_b"], gates).reshape(k.shape)
        v = v + _lora_delta(h, lora["wv_a"], lora["wv_b"], gates).reshape(v.shape)
    if cfg.qk_norm:
        # Qwen3: per-head RMSNorm over head_dim before rope
        q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    return q, k, v


def _attn_out(layer: Params, attn: jnp.ndarray, lora: Params | None = None,
              gates: jnp.ndarray | None = None) -> jnp.ndarray:
    """Attention output projection (+ optional LoRA delta on wo)."""
    o = jnp.einsum("...hd,hde->...e", attn, layer["wo"])
    if lora is not None:
        flat = attn.reshape(*attn.shape[:-2], attn.shape[-2] * attn.shape[-1])
        o = o + _lora_delta(flat, lora["wo_a"], lora["wo_b"], gates)
    return o


def _mlp(layer: Params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if "router" in layer:
        return _moe_mlp(layer, h, cfg)
    gate = jnp.einsum("...e,ef->...f", h, layer["w_gate"])
    up = jnp.einsum("...e,ef->...f", h, layer["w_up"])
    return jnp.einsum("...f,fe->...e", _act(gate, cfg) * up, layer["w_down"])


def _moe_mlp(layer: Params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Mixture-of-experts FFN (Qwen-MoE family): the tree's one expert layer
    (``ops/moe.py``) with this family's settings: a softmax over all experts
    renormalised over the top-k (the same numbers as a softmax over the top-k
    logits), no scaling, every expert held here.  The token-expert pairs are
    sorted by expert and go through grouped products (XLA's ragged product),
    so the work follows the ``T x k`` rows routed and no ``[T, experts, ..]``
    array exists.  Under an ``ep`` mesh GSPMD partitions the products as it
    sees fit; an exchange between chips that hold different experts is not
    written yet (ROADMAP M1)."""
    from smg_tpu.ops import moe

    x = h.reshape(-1, h.shape[-1])
    routing = moe.route(x, layer["router"], top_k=max(cfg.num_experts_per_tok, 1),
                        scoring="softmax", norm_topk=True, scale=1.0)
    out, _counts = moe.expert_layer(x, routing, layer["w_gate"], layer["w_up"],
                                    layer["w_down"], (0, layer["router"].shape[-1]))
    return out.astype(h.dtype).reshape(h.shape)


# --------------------------------------------------------------------------
# the decoder layer.  Every forward below runs this one block; what differs
# between them is how a layer's queries meet the keys and values its sequence
# holds, so each forward passes that in: ``rotate(q, k)`` applies its
# positions, ``attend(q, k, v, l, state)`` puts the new K and V where that
# forward keeps them (the paged cache, a frame's side buffers, nowhere) and
# returns the attention's output with the state it changed.  ``state`` is
# whatever the forward threads through the layer scan besides ``h``.


def decoder_block(cfg: ModelConfig, rotate, attend, lora_gates, carry, xs):
    """One decoder layer as a ``lax.scan`` step: ``carry`` is ``(h, *state)``,
    ``xs`` the triple of ``_scan_xs``.  ``l`` indexes the layer in ``state``
    (stage-LOCAL under pp)."""
    h, *state = carry
    layer, lora, l = xs
    with jax.named_scope("smg.attn.qkv"):
        q, k, v = _qkv(layer, cfg, _norm(h, layer["attn_norm"], cfg), lora, lora_gates)
        q, k = rotate(q, k)
    with jax.named_scope("smg.attn.kv"):  # the new rows to where the forward keeps them
        attn, state = attend(q, k, v, l, tuple(state))
    with jax.named_scope("smg.attn.out"):
        h = _attn_residual(h, layer, attn, cfg, lora, lora_gates)
    return (_mlp_residual(h, layer, cfg), *state), None


def _scan_xs(layers, lora, num_layers):
    """Layer-scan xs ``(layer, lora_layer, index)``; without a LoRA bank
    ``lora`` is None, an empty pytree — shared by the plain scans here and
    the pp shard_map body (``parallel/pp_serving.py``)."""
    return layers, lora, jnp.arange(num_layers)


def _scan_layers(make_body, consts, carry, layers, lora=None, pp_mesh=None,
                 frozen=()):
    """Run ``make_body(*consts, *frozen)``'s block over the layer stack and
    return the carry.  The factory, not the block, is what the forwards
    hand over: pp runs it under shard_map with per-stage consts (everything
    data-dependent rides the consts tuple so the block never closes over an
    outer tracer); the plain path calls it once with the outer tracers."""
    if pp_mesh is not None:
        from smg_tpu.parallel.pp_serving import pp_serving_scan

        return pp_serving_scan(pp_mesh, make_body, *carry, layers, consts,
                               lora=lora, frozen=frozen)
    L = jax.tree.leaves(layers)[0].shape[0]
    return jax.lax.scan(make_body(*consts, *frozen), carry,
                        _scan_xs(layers, lora, L))[0]


def _scale(cfg: ModelConfig) -> float:
    return cfg.query_scale or 1.0 / math.sqrt(cfg.head_dim)


def _rotary(cfg: ModelConfig, inv_freq, pos, rope_pos=None):
    """``rotate(q, k)`` for ``[..., T, heads, D]`` at positions ``pos``
    ``[..., T]``.  Under M-RoPE the 3-axis ids ``rope_pos`` rotate sectioned
    frequencies; masks and cache destinations keep the sequential ``pos``."""
    if rope_pos is not None:
        rot = lambda x: apply_mrope(x, rope_pos, inv_freq, cfg.mrope_section)
    else:
        rot = lambda x: apply_rope(x, pos, inv_freq)
    return lambda q, k: (rot(q), rot(k))


def _write_side(side, k, v, l, col):
    """Put a layer's new rows ``k``, ``v`` ``[B, (n,) K, D]`` into the side
    buffers ``[L, B, N, K*D]`` from column ``col`` on.  Returns layer ``l``'s
    two buffers and the updated pair."""
    B = k.shape[0]

    def put(buf, x):
        x = x.reshape(1, B, -1, buf.shape[-1]).astype(buf.dtype)
        return jax.lax.dynamic_update_slice(buf, x, (l, 0, col, 0))

    sk, sv = put(side[0], k), put(side[1], v)
    row = lambda buf: jax.lax.dynamic_index_in_dim(buf, l, 0, keepdims=False)
    return row(sk), row(sv), (sk, sv)


def _dense_attention(q, k, v, mask, cfg: ModelConfig):
    """Causal softmax attention with no cache: ``q`` [B, T, H, D], ``k`` and
    ``v`` [B, T, K, D], ``mask`` [B or 1, T, T] true where a query may look.
    No per-layer window (its callers bound their lengths to it)."""
    B, T, H, D = q.shape
    K = cfg.num_kv_heads
    qf = q.astype(jnp.float32).reshape(B, T, K, H // K, D)
    scores = jnp.einsum("btkgd,bskd->bkgts", qf, k.astype(jnp.float32)) * _scale(cfg)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return attn.reshape(B, T, H, D).astype(q.dtype)


def forward_prefill(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [T] padded to bucket
    prefix_len: jnp.ndarray,  # scalar: tokens already cached (radix hit)
    t_real: jnp.ndarray,  # scalar: valid new tokens (<= T)
    k_cache: jnp.ndarray,  # [L, P, ps, K*D] (fused lane layout)
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [mp] pages owned by this sequence
    lora: Params | None = None,  # stacked [L, N, ...] adapter bank
    lora_gates: jnp.ndarray | None = None,  # [N] one-hot (one sequence)
    sp_mesh=None,  # Mesh: sequence-parallel ring attention over the "sp" axis
    attn_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret" (tests)
    input_embeds: jnp.ndarray | None = None,  # [T, E] mm splice rows
    embeds_mask: jnp.ndarray | None = None,  # [T] bool: row comes from input_embeds
    pp_mesh=None,  # Mesh: serving pipeline parallelism over the "pp" axis
    rope_pos: jnp.ndarray | None = None,  # [3, T] M-RoPE position ids
    all_logits: bool = False,  # static: return [T, V] (speculative verify)
):
    """Prefill one sequence chunk; returns (last_token_logits [V], k_cache, v_cache).

    ``sp_mesh`` (long-context serving, SURVEY.md §7.5 "sequence-parallel
    prefill"): the chunk's attention runs as blockwise ring attention with the
    token dim sharded over the ``sp`` mesh axis — KV shards rotate via
    ppermute over ICI instead of every device holding the full chunk.  Only
    valid for COLD chunks (prefix_len==0: the chunk is the entire context);
    chunks extending a cached prefix use the dense gather path.

    ``pp_mesh`` (serving PP, ``parallel/pp_serving.py``): layer stack + KV
    cache (and any LoRA bank) sharded over ``pp``; mutually exclusive with
    sp/pallas (the runner enforces the XLA path)."""
    T = tokens.shape[0]
    if lora is not None:
        lora_gates = jnp.broadcast_to(lora_gates, (T, lora_gates.shape[-1]))
    scale = _scale(cfg)

    ps, mp = k_cache.shape[2], page_table.shape[0]
    with jax.named_scope("smg.prefill.land"):  # where the chunk's rows stand and land
        pos = prefix_len + jnp.arange(T)  # [T]
        # ``page_slots`` for the one table, indexed directly: padded rows and
        # out-of-range positions write to the garbage page (0)
        valid = (jnp.arange(T) < t_real) & (pos < mp * ps)
        pos_c = jnp.minimum(pos, mp * ps - 1)
        dest = jnp.where(valid, page_table[pos_c // ps] * ps + pos_c % ps, 0)
        ctx_len = prefix_len + t_real

    h = embed_tokens(params, cfg, tokens)
    if input_embeds is not None:
        # multimodal splice: placeholder rows take the vision-tower output
        # (reference: EPD encode leg shipping embeddings to prefill)
        h = jnp.where(embeds_mask[:, None], input_embeds.astype(h.dtype), h)

    def make_body(pos, dest, page_table, ctx_len, inv_freq, rope_pos,
                  lora_gates):
        def attend(q, k, v, l, caches):
            """Scatter the chunk into its pages, then attend over them."""
            k_cache, v_cache = scatter_kv_pages_full(*caches, l, k, v, dest)
            if sp_mesh is not None:
                from smg_tpu.parallel.ring_attention import ring_attention

                attn = ring_attention(q[None], k[None], v[None], sp_mesh, scale)[0]
            elif attn_impl.startswith("pallas"):
                # prefix-aware paged kernel: streams only the live prefix pages
                # instead of gathering the whole mp*ps worst-case context
                from smg_tpu.ops.pallas.prefill_attention import paged_attention_prefill

                attn = paged_attention_prefill(
                    q, k.reshape(T, -1), v.reshape(T, -1), k_cache, v_cache, l,
                    page_table, prefix_len, t_real, scale,
                    softcap=cfg.attn_logit_softcap,
                    window=_layer_window(cfg, l),
                    interpret=(attn_impl == "pallas_interpret"),
                )
            else:
                k_ctx, v_ctx = gather_seq_kv(
                    k_cache, v_cache, l, page_table, cfg.num_kv_heads
                )
                attn = attention_prefill(q, k_ctx, v_ctx, pos, ctx_len, scale,
                                         softcap=cfg.attn_logit_softcap,
                                         window=_layer_window(cfg, l))
            return attn, (k_cache, v_cache)

        return partial(decoder_block, cfg, _rotary(cfg, inv_freq, pos, rope_pos),
                       attend, lora_gates)

    h, k_cache, v_cache = _scan_layers(
        make_body, (pos, dest, page_table, ctx_len, inv_freq, rope_pos, lora_gates),
        (h, k_cache, v_cache), params["layers"], lora, pp_mesh,
    )
    if all_logits:
        # speculative verify: every chunk position's next-token distribution
        # in one MXU-friendly pass (ops/speculative.py)
        return unembed(params, cfg, h), k_cache, v_cache
    with jax.named_scope("smg.lm_head"):
        last = jnp.take_along_axis(
            h, jnp.maximum(t_real - 1, 0)[None, None].astype(jnp.int32), axis=0
        )[0]
    logits = unembed(params, cfg, last)
    return logits, k_cache, v_cache


def forward_prefill_batched(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [G, T] padded rows (t_real=0 rows are pure padding)
    prefix_lens: jnp.ndarray,  # [G]
    t_reals: jnp.ndarray,  # [G]
    k_cache: jnp.ndarray,  # [L, P, ps, K*D]
    v_cache: jnp.ndarray,
    page_tables: jnp.ndarray,  # [G, mp]
    no_ctx: bool = False,  # static: all rows cold (prefix 0, single chunk)
    lora: Params | None = None,
    lora_gates: jnp.ndarray | None = None,  # [G, N] one-hot per sequence
    input_embeds: jnp.ndarray | None = None,  # [G, T, E] mm splice rows
    embeds_mask: jnp.ndarray | None = None,  # [G, T] bool: row from input_embeds
    rope_pos: jnp.ndarray | None = None,  # [G, 3, T] M-RoPE position ids
    pp_mesh=None,  # Mesh: serving pipeline parallelism over the "pp" axis
    attn_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret" (tests); no_ctx only
):
    """Prefill several sequences in one device call (fills the MXU and
    amortizes dispatch; single-sequence prefill wastes both).  Returns
    (last_token_logits [G, V], k_cache, v_cache).

    ``no_ctx=True`` (every row is a cold single-chunk prompt — the common
    case) attends over the chunk's own K/V instead of gathering the
    sequence's full page range, cutting attention reads by max_seq_len/T;
    under a ``pallas`` ``attn_impl`` it does so as an online softmax that
    keeps the scores in VMEM (``ops/pallas/flash_prefill.py``; the caller
    vouches for a model with no softcap and no window).
    """
    G_, T = tokens.shape
    ps = k_cache.shape[2]
    mp = page_tables.shape[1]
    scale = _scale(cfg)
    K, D = cfg.num_kv_heads, cfg.head_dim

    with jax.named_scope("smg.prefill.land"):  # where the chunks' rows stand and land
        pos = prefix_lens[:, None] + jnp.arange(T)[None, :]  # [G, T]
        dest = page_slots(page_tables, pos, jnp.arange(T)[None, :] < t_reals[:, None],
                          ps).reshape(-1)  # [G*T]
        ctx_lens = prefix_lens + t_reals

    h = embed_tokens(params, cfg, tokens)  # [G, T, E]
    if input_embeds is not None:
        # mm splice: placeholder rows take vision-tower embeddings
        # (reference: the EPD encode leg's output entering prefill)
        h = jnp.where(embeds_mask[:, :, None], input_embeds.astype(h.dtype), h)
    if lora is not None:
        # per-sequence gate broadcast across the row's tokens
        lora_gates = jnp.broadcast_to(
            lora_gates[:, None, :], (G_, T, lora_gates.shape[-1])
        )

    def make_body(pos, dest, page_tables, ctx_lens, inv_freq, rope_pos,
                  lora_gates):
        def attend(q, k, v, l, caches):
            """Scatter every row's chunk, then attend: over the chunk itself
            when it IS the whole context, else over the row's pages."""
            k_cache, v_cache = scatter_kv_pages_full(
                *caches, l, k.reshape(G_ * T, K, D), v.reshape(G_ * T, K, D), dest
            )
            if no_ctx and attn_impl.startswith("pallas"):
                from smg_tpu.ops.pallas.flash_prefill import flash_attention_prefill

                # prefix 0: a row's context is its own ``t_real`` tokens
                attn = flash_attention_prefill(
                    q, k, v, ctx_lens, scale, interpret=(attn_impl == "pallas_interpret"))
            else:
                if not no_ctx:
                    k, v = (x.reshape(G_, mp * ps, K, D) for x in gather_layer_pages(
                        k_cache, v_cache, l, page_tables))  # [G, mp, ps, KD] each
                attn = attention_prefill_batched(q, k, v, pos, ctx_lens, scale,
                                                 softcap=cfg.attn_logit_softcap,
                                                 window=_layer_window(cfg, l))
            return attn, (k_cache, v_cache)

        return partial(decoder_block, cfg, _rotary(cfg, inv_freq, pos, rope_pos),
                       attend, lora_gates)

    h, k_cache, v_cache = _scan_layers(
        make_body, (pos, dest, page_tables, ctx_lens, inv_freq, rope_pos, lora_gates),
        (h, k_cache, v_cache), params["layers"], lora, pp_mesh,
    )
    with jax.named_scope("smg.lm_head"):
        last_idx = jnp.maximum(t_reals - 1, 0)[:, None, None]  # [G, 1, 1]
        last = jnp.take_along_axis(
            h, jnp.broadcast_to(last_idx, (G_, 1, h.shape[-1])).astype(jnp.int32), axis=1
        )[:, 0]
    logits = unembed(params, cfg, last)  # [G, V]
    return logits, k_cache, v_cache


def forward_decode_horizon(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [B] token fed this step
    positions: jnp.ndarray,  # [B] absolute position of that token (entry + step)
    entry_positions: jnp.ndarray,  # [B] cache token count at horizon entry (fixed)
    step_idx: jnp.ndarray,  # scalar: step within the horizon (0-based)
    k_cache: jnp.ndarray,  # [L, P, ps, K*D] READ-ONLY during the horizon
    v_cache: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, mp]
    hk_all: jnp.ndarray,  # [L, B, N, K*D] horizon side buffers (carried)
    hv_all: jnp.ndarray,
    attn_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret" (tests)
    lora: Params | None = None,
    lora_gates: jnp.ndarray | None = None,  # [B, n_adapters] one-hot per slot
    pp_mesh=None,  # Mesh: serving pipeline parallelism over the "pp" axis
    rope_delta: jnp.ndarray | None = None,  # [B] M-RoPE decode offset per slot
    kv_lanes_sharded: bool = False,  # the cache's K*D axis is split over a mesh
):
    """One decode step against a frozen cache + growing side buffer.

    The new K/V rows are appended to the side buffers (tiny carried arrays);
    the caller scatters the whole horizon into the cache once per
    ``decode_multi`` call (see ``smg_tpu/ops/pallas/decode_attention.py``
    module docs for why the cache must not be updated inside the loop).
    Returns (logits [B, V], hk_all, hv_all).

    Under ``pp_mesh`` the layer stack, the frozen cache, and the side
    buffers shard their layer axis over ``pp`` (``parallel/pp_serving.py``).
    """
    scale = _scale(cfg)
    h = embed_tokens(params, cfg, tokens)  # [B, E]

    def make_body(positions, step_idx, entry_positions, page_tables, inv_freq,
                  rope_delta, lora_gates, k_cache, v_cache):
        # generated tokens are text: all three M-RoPE axes are equal, so
        # decode stays on the standard rope path with a per-slot offset
        rope_positions = (
            positions if rope_delta is None else positions + rope_delta
        )

        def attend(q, k, v, l, side):
            """Append the column to the side buffers, then attend over the
            frozen cache and the columns so far."""
            hk_l, hv_l, side = _write_side(side, k, v, l, step_idx)
            if attn_impl.startswith("pallas"):
                from smg_tpu.ops.pallas.decode_attention import paged_attention_decode_cached

                attn = paged_attention_decode_cached(
                    q, k_cache, v_cache, hk_l, hv_l, step_idx + 1, l,
                    page_tables, entry_positions, scale,
                    softcap=cfg.attn_logit_softcap,
                    window=_layer_window(cfg, l),
                    interpret=(attn_impl == "pallas_interpret"),
                )
            else:
                attn = attention_decode_cached(
                    q, k_cache, v_cache, hk_l, hv_l, step_idx + 1, l,
                    page_tables, entry_positions, scale,
                    softcap=cfg.attn_logit_softcap,
                    window=_layer_window(cfg, l),
                    lanes_sharded=kv_lanes_sharded,
                )
            return attn, side

        # q, k are [B, heads, D]: the lanes stand where rope wants its tokens
        return partial(decoder_block, cfg, _rotary(cfg, inv_freq, rope_positions),
                       attend, lora_gates)

    h, hk_all, hv_all = _scan_layers(
        make_body,
        (positions, step_idx, entry_positions, page_tables, inv_freq, rope_delta,
         lora_gates),
        (h, hk_all, hv_all), params["layers"], lora, pp_mesh,
        frozen=(k_cache, v_cache),
    )
    logits = unembed(params, cfg, h)
    return logits, hk_all, hv_all


def forward_verify_block(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [B, W] verify block per lane: [y0, d1.., pad]
    entry_positions: jnp.ndarray,  # [B] cache token count at block entry
    k_cache: jnp.ndarray,  # [L, P, ps, K*D] READ-ONLY during the block
    v_cache: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, mp]
    rope_delta: jnp.ndarray | None = None,  # [B] M-RoPE decode offset per lane
    kv_lanes_sharded: bool = False,  # the cache's K*D axis is split over a mesh
):
    """Speculative verify block: score W tokens per lane in ONE forward.

    The fused draft-verify analogue of ``forward_decode_horizon``: instead of
    one token per call fed back serially, the block feeds the last committed
    token plus the drafted columns at positions ``entry..entry+W-1`` and
    returns every position's next-token logits — K drafted positions scored
    for the cost class of a single decode step (decode is bandwidth-bound;
    the extra columns ride the same weight pass).  The block's K/V stays in
    SIDE BUFFERS (``attention_verify_block`` attends frozen cache + causal
    block rows); the caller scatters accepted columns into the cache and
    rejected columns to the garbage page AFTER acceptance is known, so a
    rejected draft's KV never lands in a real slot.

    Generated positions are text under M-RoPE (three equal axes), so a
    per-lane ``rope_delta`` rides the standard rope path exactly as in
    horizon decode.  LoRA / pp / pallas are not composed here: the scheduler
    keeps adapter-pinned lanes on the non-speculative path, and pp engines
    fall back likewise (see ``Scheduler._partition_spec``).
    Returns (logits [B, W, V], bk [L, B, W, K*D], bv [L, B, W, K*D])."""
    B, W = tokens.shape
    L = cfg.num_layers

    pos = entry_positions[:, None] + jnp.arange(W)[None, :]  # [B, W]
    rope_positions = pos if rope_delta is None else pos + rope_delta[:, None]

    def attend(q, k, v, l, side):
        """The block's rows into its buffers, then frozen cache + block."""
        bk_l, bv_l, side = _write_side(side, k, v, l, 0)
        attn = attention_verify_block(
            q, k_cache, v_cache, bk_l, bv_l, l, page_tables, entry_positions,
            _scale(cfg), softcap=cfg.attn_logit_softcap,
            window=_layer_window(cfg, l), lanes_sharded=kv_lanes_sharded,
        )
        return attn, side

    shape = (L, B, W, cfg.num_kv_heads * cfg.head_dim)
    h, bk_all, bv_all = jax.lax.scan(
        partial(decoder_block, cfg, _rotary(cfg, inv_freq, rope_positions), attend, None),
        (embed_tokens(params, cfg, tokens),  # [B, W, E]
         jnp.zeros(shape, k_cache.dtype), jnp.zeros(shape, v_cache.dtype)),
        _scan_xs(params["layers"], None, L),
    )[0]
    logits = unembed(params, cfg, h)  # [B, W, V]
    return logits, bk_all, bv_all


def _dense_layer(layer, h, cfg, inv_freq, mask, ring_mesh=None):
    """The block over whole sequences ``h`` [B, T, E] with no cache: dense
    attention under ``mask``, or ring attention (causal) under ``ring_mesh``."""
    B, T = h.shape[:2]
    pos = jnp.arange(T)[None, :].repeat(B, axis=0)

    def attend(q, k, v, l, state):
        if ring_mesh is not None:
            from smg_tpu.parallel.ring_attention import ring_attention

            return ring_attention(q, k, v, ring_mesh, _scale(cfg)), state
        return _dense_attention(q, k, v, mask, cfg), state

    return decoder_block(cfg, _rotary(cfg, inv_freq, pos), attend, None,
                         (h,), (layer, None, None))[0][0]


def forward_embed(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [B, T] right-padded
    lengths: jnp.ndarray,  # [B] valid lengths
) -> jnp.ndarray:
    """Sequence embeddings: final-norm hidden state of the last valid token,
    L2-normalized (serves /v1/embeddings — reference routes embeddings to
    engine ``Embed`` RPCs, ``sglang_scheduler.proto``)."""
    T = tokens.shape[1]
    # window bound on REAL lengths is enforced host-side in runner.embed —
    # T here is the padded bucket and padding columns are masked anyway
    h = embed_tokens(params, cfg, tokens)
    # causal mask also masks padding columns beyond each row's length
    causal = (jnp.tril(jnp.ones((T, T), bool))[None]
              & (jnp.arange(T)[None, None, :] < lengths[:, None, None]))
    h, _ = jax.lax.scan(
        lambda h, layer: (_dense_layer(layer, h, cfg, inv_freq, causal), None),
        h, params["layers"],
    )
    h = _norm(h, params["final_norm"], cfg)
    last = jnp.take_along_axis(
        h, jnp.maximum(lengths - 1, 0)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0].astype(jnp.float32)
    norm = jnp.linalg.norm(last, axis=-1, keepdims=True)
    return last / jnp.maximum(norm, 1e-12)


def forward_train(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [B, T]
    ring_mesh=None,  # Mesh with an "sp" axis: use ring attention (seq parallel)
    pp_mesh=None,  # Mesh with a "pp" axis: microbatch pipeline over stages
    num_microbatches: int = 1,
) -> jnp.ndarray:
    """Dense causal forward for training / eval-logprobs: logits [B, T, V].

    No KV cache.  With ``ring_mesh`` the attention runs as blockwise ring
    attention over the ``sp`` axis (``smg_tpu/parallel/ring_attention.py``) —
    KV shards rotate over ICI instead of the all-gather GSPMD would insert,
    which is what makes million-token-class sequence parallelism viable.
    With ``pp_mesh`` the layer stack runs as a microbatch pipeline over the
    ``pp`` axis (``smg_tpu/parallel/pipeline.py``); embed and unembed stay
    under GSPMD outside the pipeline region.
    """
    h = embed_tokens(params, cfg, tokens)
    layer_fn = lambda layer, x: decoder_layer_train(
        layer, x, cfg, inv_freq, ring_mesh=ring_mesh)

    if pp_mesh is not None and pp_mesh.shape.get("pp", 1) > 1:
        from smg_tpu.parallel.pipeline import pipeline_apply

        h = pipeline_apply(layer_fn, params["layers"], h, pp_mesh,
                           num_microbatches=num_microbatches)
    else:
        h, _ = jax.lax.scan(lambda h, layer: (layer_fn(layer, h), None),
                            h, params["layers"])
    return unembed(params, cfg, h)


def decoder_layer_train(
    layer: Params,
    h: jnp.ndarray,  # [B, T, E]
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    ring_mesh=None,
) -> jnp.ndarray:
    """One decoder layer, dense causal (training/eval) — shared by the
    ``forward_train`` layer scan and the pipeline-parallel schedule
    (``smg_tpu/parallel/pipeline.py``), which scans it over a pp stage's
    local layer shard."""
    T = h.shape[1]
    if cfg.sliding_window and T > cfg.sliding_window:
        # training T is the REAL (unpadded) sequence length, so this bound
        # is exact; the dense layer has no per-layer window alternation
        raise ValueError(
            f"training supports contexts <= sliding_window "
            f"({cfg.sliding_window}); got {T}"
        )
    return _dense_layer(layer, h, cfg, inv_freq,
                        jnp.tril(jnp.ones((T, T), bool))[None], ring_mesh)
