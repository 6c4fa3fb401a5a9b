"""openPangu-Ultra-MoE: latent attention, routed experts, sandwich norms.

``model_type: pangu_ultra_moe`` (``FreedomIntelligence/openPangu-Ultra-MoE-718B``).
The block is the DeepSeek-V3 family's with four norms a layer.  For a token's
hidden vector ``x`` at position ``t``, ``H`` heads:

*Attention* (latent): ``c_q = RMSNorm(W_dq x)``; ``[q_nope_h | q_pe_h] =
W_uq,h c_q``; ``[c | k_pe] = W_dkv x``, ``c = RMSNorm(c)``, ``k_pe =
RoPE_t(k_pe)`` (one rotary key a token, shared by the heads), ``q_pe_h =
RoPE_t(q_pe_h)`` (rotate-half pairing, plain ``rope_theta``, no scaling);
``k_nope_h = W_uk,h c``, ``v_h = W_uv,h c``; scores ``(q_nope_h . k_nope_h +
q_pe_h . k_pe) / sqrt(dn + dr)``, causal softmax, ``o = W_o concat_h(sum p
v_h)``.  **What a token leaves in the cache is ``[c | k_pe]``**, one entry a
layer and no V (``ops/latent_attention.py`` for its layout).

*Block* (``sandwich_norm``): ``h = h + RMSNorm(Attn(RMSNorm(h)))``; ``h = h +
RMSNorm(MLP(RMSNorm(h)))``.  The residual stream itself is never normed.

*MLP*: layers ``0 .. first_k_dense_replace - 1`` a SwiGLU MLP of
``intermediate_size``.  The others: ``s = sigmoid(W_r x)`` in float32 over all
``num_experts`` routed experts (or a softmax: ``moe_scoring``; no selection
bias), the ``top_k`` largest, ``w_i = routed_scaling_factor * s_i / sum_topk
s`` (the division where ``norm_topk_prob``), ``MLP(x) = sum_i w_i E_i(x) +
E_shared(x)``, every expert a SwiGLU MLP of ``moe_intermediate_size``.

**Experts held.**  This process holds the routed experts ``cfg.held_experts``
(all, or one chip's share of a deployment); the router keeps its width, and
the layer adds what the held experts give for the tokens routed to them, and
the shared expert (``ops/moe.py``).  That partial result goes on to the next
layer; nothing stands in for the chips that hold the rest.

**Departures from the equations above**: none in arithmetic.  Prefill runs
attention *expanded* (keys and values rebuilt from the entries), decode runs
it *absorbed* (``q'_h = W_uk,h^T q_nope_h`` meets ``c`` itself, ``o_h =
W_uv,h (sum p c)``): the same products in another order.  The next-token
prediction module (``num_nextn_predict_layers``) is a drafter the model's own
logits do not depend on; it is neither loaded nor served.

**What this module serves**: ``forward_prefill``, ``forward_prefill_batched``
and ``forward_decode_horizon`` on one device; everything in
``SERVING_LIMITS`` is refused at start, not run wrong.  The leading dense
layers and the expert layers are two parameter stacks (their shapes differ),
scanned in turn; layer ``l`` of the model is layer ``l`` of the cache.
``models/longcat_flash.py`` serves another block over the same attention: it
gives these forwards its own ``stack`` and calls ``latent_attention`` twice a
layer.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from smg_tpu.models.config import ModelConfig
from smg_tpu.models.llama import _mlp, _mlp_residual, _norm, embed_tokens, unembed
from smg_tpu.ops import moe
from smg_tpu.ops.attention import attention_decode_cached, page_slots
from smg_tpu.ops.latent_attention import (
    EXPANDED_KV_BYTES,
    SCORE_BLOCK_BYTES,
    entry_lanes,
    latent_attention_prefill,
    latent_attention_prefill_cached,
    scatter_entries,
    value_lanes,
)
from smg_tpu.ops.norms import rms_norm
from smg_tpu.ops.rope import apply_rope

Params = dict[str, Any]

# RANDOM weights (``init_params``).  The attention's projections are drawn at
# 1 / sqrt(fan-in), so that queries, latents and rotary keys have unit size
# and the scores a standard deviation near 1 (a third of their variance from
# the rotary lanes): at normal 0.02 the scores of 7,680-wide inputs are near
# 0, every key weighs the same, and neither a key's position nor its page
# moves the result.  The attention's post-norm has weight ``ATTN_POST_NORM``
# (every other norm weight is 1), so that the sublayer that reads the cache
# speaks louder than an MLP: with equal voices one wrong page of a sequence's
# 44 moves the logits little more than rounding does, and the benchmark's
# comparison could not tell a wrong cache from bfloat16 (PR 29's lesson).
ATTN_POST_NORM = 2.0

# Every expert, routed or shared, is drawn alike, at normal 0.02 with the
# output projection scaled down by depth.  A configuration may say otherwise
# for the routed experts' output projections (``random_routed_out_gain``, read
# by ``ModelConfig``, default 1).  Why anyone would: under random routers the
# scores of a token's 8th and 9th expert lie within rounding of each other in
# a few tokens of a hundred, and bfloat16 then picks the other one, which is
# no fault and moves that token's logits by a whole pick.  Whoever holds
# random weights to a float32 reference has to choose how loud a pick is, so
# that such a token passes and a broken routed path does not; that is the
# comparison's knowledge (``benchmark/configs``), and nothing here is tuned
# for it.


SERVING_LIMITS = {
    "speculative": "pangu_ultra_moe has no verify block, and its next-token prediction "
                   "module (num_nextn_predict_layers) is not loaded: nothing drafts",
    "lora": "pangu_ultra_moe has no LoRA deltas on its projections",
    "embeddings": "pangu_ultra_moe has no embedding forward",
    "mesh": "pangu_ultra_moe runs on one device: the experts' exchange between chips "
            "that hold different experts does not exist yet",
    "kv_transfer": "pangu_ultra_moe cannot export a sequence: the transfer carries K "
                   "and V buffers and its cache has one latent buffer",
    "checkpoint": "pangu_ultra_moe has no safetensors key map yet: it is served with "
                  "seeded random weights (--model-preset), not from --model-path",
}


def cache_lanes(cfg: ModelConfig) -> int:
    return entry_lanes(cfg.kv_lora_rank, cfg.qk_rope_head_dim)


def prefill_workspace_bytes(cfg: ModelConfig, tokens: int, dtype: str) -> int:
    """Bytes a prefill of ``tokens`` tokens holds beside its arguments, from
    shapes and on the high side: a layer's widest activations live together
    (the MLP's gate, up and their product, or the queries, the rebuilt keys
    and values and the heads' outputs, and some hidden vectors), and one block
    of scores with its probabilities.  Compiled for a v5e at the published
    widths a 4,096-token program holds 1.07 to 1.33 GB where this says 1.84."""
    H, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    mlp = 3 * max(cfg.intermediate_size if cfg.first_k_dense_replace else 0,
                  cfg.moe_intermediate_size * (cfg.n_shared_experts + cfg.num_experts_per_tok))
    attention = H * ((dn + dr) + (dn + dv) + dv)
    per_token = (mlp + attention + 6 * cfg.hidden_size) * jnp.dtype(dtype).itemsize
    return tokens * per_token + 3 * SCORE_BLOCK_BYTES


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random weights (tests, benchmarks): the attention's projections normal
    at 1 / sqrt(fan-in), everything else normal 0.02 (the MLPs' output
    projections scaled down by depth, the routed experts' by
    ``cfg.random_routed_out_gain`` besides), norm weights 1 but
    ``ATTN_POST_NORM``."""
    E, F, V, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
    H, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    Fm, X = cfg.moe_intermediate_size, cfg.num_experts
    Fs, Xh = cfg.n_shared_experts * Fm, cfg.held_experts[1]
    Ld = cfg.first_k_dense_replace
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 64))
    out_scale = 0.02 / math.sqrt(2 * L)

    def normal(shape, scale=0.02):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(dtype)

    def attention(n):
        ones = lambda *shape: jnp.ones((n, *shape), dtype)
        return {
            "attn_norm": ones(E), "mlp_norm": ones(E), "post_mlp_norm": ones(E),
            "post_attn_norm": jnp.full((n, E), ATTN_POST_NORM, dtype),
            "w_dq": normal((n, E, rq), E ** -0.5), "q_norm": ones(rq),
            # How the heads' projections are stored is set by what XLA:TPU
            # makes of a decode column (compiled for a v5e, PERF.md): the
            # queries' up-projection as two matrices with the heads fused
            # (one matrix [rq, H, dn + dr], sliced after the product, is
            # copied a layer and column, 75 MB), the latent's two with the
            # heads leading (as [rkv, H, d] both stacks are copied a launch)
            "w_uq_nope": normal((n, H * dn, rq), rq ** -0.5),
            "w_uq_pe": normal((n, dr, H, rq), rq ** -0.5),
            "w_dkv": normal((n, E, rkv), E ** -0.5), "kv_norm": ones(rkv),
            "w_dk_pe": normal((n, E, dr), E ** -0.5),
            "w_uk": normal((n, H, rkv, dn), rkv ** -0.5),
            "w_uv": normal((n, H, rkv, dv), rkv ** -0.5),
            "wo": normal((n, H * dv, E), (H * dv) ** -0.5),
        }

    Lm = L - Ld
    params: Params = {
        "embed": normal((V, E)),
        "dense": {**attention(Ld), "w_gate": normal((Ld, E, F)), "w_up": normal((Ld, E, F)),
                  "w_down": normal((Ld, F, E), out_scale)},
        "moe": {**attention(Lm), "router": normal((Lm, E, X)),
                "w_gate": normal((Lm, Xh, E, Fm)), "w_up": normal((Lm, Xh, E, Fm)),
                "w_down": normal((Lm, Xh, Fm, E), cfg.random_routed_out_gain * out_scale),
                "ws_gate": normal((Lm, E, Fs)), "ws_up": normal((Lm, E, Fs)),
                "ws_down": normal((Lm, Fs, E), out_scale)},
        "final_norm": jnp.ones((E,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal((E, V))
    return params


def logical_axes(cfg: ModelConfig) -> Params:
    """Every array replicated: this module runs on one device."""
    return jax.tree.map(lambda x: (None,) * x.ndim,
                        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))


# --------------------------------------------------------------------------
# the layer.  What differs between prefill and decode is how the queries meet
# what the sequence holds, so each forward passes that in: ``attend(q_nope,
# q_pe, entry, layer, l, state)`` puts the new entries where the forward keeps
# them and returns the heads' outputs [..., H, dv] with the state it changed.


def _scaled(weight, scale: float):
    """A norm's weight times a static ``scale``, in float32, so that ``scale x
    RMSNorm(x)`` is rounded once, with the norm.  A scale of 1 is the weight
    itself: nothing is multiplied in."""
    return weight if scale == 1.0 else weight.astype(jnp.float32) * scale


def _latent_qkv(layer: Params, cfg: ModelConfig, x, positions, inv_freq):
    """Queries ``q_nope`` [..., H, dn], ``q_pe`` [..., H, dr] (rotated), the
    cache entry ``[c | k_pe | 0]`` [..., W] of the tokens ``x`` [..., E], and
    the queries' normed low-rank vector ``c_q`` [..., rq].  A
    model that scales its two normed low-rank vectors (``cfg.mla_q_scale``,
    ``cfg.mla_kv_scale``; ``models/longcat_flash.py``) has them scaled here,
    behind the norms: the entry then holds the scaled latent, and the rotary
    key is not scaled."""
    dn, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("smg.mla.q"):
        c_q = rms_norm(jnp.einsum("...e,er->...r", x, layer["w_dq"]),
                       _scaled(layer["q_norm"], cfg.mla_q_scale), cfg.rms_norm_eps)
        q_nope = jnp.einsum("...r,fr->...f", c_q, layer["w_uq_nope"])
        q_nope = q_nope.reshape(*q_nope.shape[:-1], cfg.num_heads, dn)
        q_pe = apply_rope(jnp.einsum("...r,dhr->...hd", c_q, layer["w_uq_pe"]),
                          positions, inv_freq)
    with jax.named_scope("smg.mla.kv"):
        c = rms_norm(jnp.einsum("...e,ec->...c", x, layer["w_dkv"]),
                     _scaled(layer["kv_norm"], cfg.mla_kv_scale), cfg.rms_norm_eps)
        k_pe = jnp.einsum("...e,ed->...d", x, layer["w_dk_pe"])
        k_pe = apply_rope(k_pe[..., None, :], positions, inv_freq)[..., 0, :]
        pad = jnp.zeros((*c.shape[:-1], cache_lanes(cfg) - rkv - k_pe.shape[-1]), c.dtype)
        entry = jnp.concatenate([c, k_pe, pad], axis=-1)
    return q_nope, q_pe, entry, c_q


def latent_attention(layer: Params, cfg: ModelConfig, x, positions, inv_freq, attend, l, state,
                     index=None):
    """One latent attention over the normed tokens ``x`` [..., E], as cache
    layer ``l``: ``W_o`` over the heads' outputs, and the forward's ``state``
    as ``attend`` changed it.  ``index(x, c_q, state) -> state``: a model
    whose queries choose the cached tokens they read (``models/glm_moe_dsa.py``)
    does so here, between the projections and ``attend``."""
    q_nope, q_pe, entry, c_q = _latent_qkv(layer, cfg, x, positions, inv_freq)
    if index is not None:
        state = index(x, c_q, state)
    out, state = attend(q_nope, q_pe, entry, layer, l, state)
    o = jnp.einsum("...f,fe->...e", out.astype(x.dtype).reshape(*x.shape[:-1], -1), layer["wo"])
    return o, state


@jax.named_scope("smg.moe.residual")
def _moe_residual(h, layer: Params, experts: Params, i, cfg: ModelConfig, live, impl: str):
    """``h + RMSNorm(sum_i w_i E_i(x) + E_shared(x))`` over the held experts,
    ``x = RMSNorm(h)``.  ``experts`` holds the routed experts' weights of all
    expert layers, ``i`` picks this layer's (a layer sliced out for the kernel
    would be a copy of it).  ``live`` [...] marks real tokens: a padded one picks
    no expert.  Returns ``h`` and int32 ``[picks, picks on held experts, held
    experts hit, the same picks again]`` (the last is summed by nobody: the
    decode frame keeps its maximum as ``rows_max``)."""
    x = _norm(h, layer["mlp_norm"], cfg)
    flat = x.reshape(-1, x.shape[-1])
    routing = moe.route(flat, layer["router"], top_k=cfg.num_experts_per_tok,
                        scoring=cfg.moe_scoring, norm_topk=cfg.norm_topk_prob,
                        scale=cfg.routed_scaling_factor)
    alive = live.reshape(-1)
    routing = routing._replace(experts=jnp.where(alive[:, None], routing.experts, -1))
    y, (rows, hit) = moe.expert_layer(flat, routing, experts["w_gate"], experts["w_up"],
                                      experts["w_down"], cfg.held_experts, impl, layer=i)
    o = (y + shared_expert(layer, flat, cfg).astype(jnp.float32)).astype(h.dtype).reshape(h.shape)
    picks = jnp.sum(alive).astype(jnp.int32) * cfg.num_experts_per_tok
    return (h + _norm(o, layer["post_mlp_norm"], cfg),
            jnp.stack([picks, rows, hit, rows]))


@jax.named_scope("smg.moe.shared")
def shared_expert(layer: Params, x, cfg: ModelConfig):
    """The shared expert of an expert layer, whole on every chip: a gated MLP
    of ``moe_intermediate_size`` every token passes (``ws_gate``, ``ws_up``,
    ``ws_down``).  ``models/exaone_moe.py`` adds it the same way."""
    return _mlp({"w_gate": layer["ws_gate"], "w_up": layer["ws_up"],
                 "w_down": layer["ws_down"]}, x, cfg)


#: what a decode frame counts of its routed experts, in the frame's order
#: (``engine/runner.ModelRunner._decode_frame_fn``): token-expert pairs, those on
#: held experts (rows computed), held experts hit summed over layers and
#: columns, and the most rows one layer and column computed.  The scheduler
#: and the step ring read the counts by these names.
ROUTED_COUNTS = ("picks", "picks_held", "experts_hit", "rows_max")


@jax.named_scope("smg.moe.counts")
def merge_counts(total, new):
    """The expert layers' counts of one more layer, or column: the first three
    add up, the fourth is kept as a maximum."""
    return jnp.concatenate([total[:3] + new[:3], jnp.maximum(total[3:], new[3:])])


def _stack(params: Params, cfg: ModelConfig, inv_freq, h, positions, live, state, attend,
           moe_impl: str):
    """Both parameter stacks in turn, each one ``lax.scan``.  Returns ``h``,
    the forwards' ``state`` and the expert layers' counts summed over layers
    (the last kept as a maximum)."""
    Ld = cfg.first_k_dense_replace

    @jax.named_scope("smg.mla.block")
    def attention(h, layer, l, state):
        o, state = latent_attention(layer, cfg, _norm(h, layer["attn_norm"], cfg), positions,
                                    inv_freq, attend, l, state)
        return h + _norm(o, layer["post_attn_norm"], cfg), state

    def dense(carry, xs):
        (h, state), (layer, l) = carry, xs
        h, state = attention(h, layer, l, state)
        return (_mlp_residual(h, layer, cfg), state), None

    routed = ("w_gate", "w_up", "w_down")
    experts = {k: params["moe"][k] for k in routed}

    def expert(carry, xs):
        (h, state, counts), (layer, i) = carry, xs
        h, state = attention(h, layer, Ld + i, state)
        h, c = _moe_residual(h, layer, experts, i, cfg, live, moe_impl)
        counts = merge_counts(counts, c)
        return (h, state, counts), None

    (h, state), _ = jax.lax.scan(dense, (h, state), (params["dense"], jnp.arange(Ld)))
    (h, state, counts), _ = jax.lax.scan(
        expert, (h, state, jnp.zeros((len(ROUTED_COUNTS),), jnp.int32)),
        ({k: v for k, v in params["moe"].items() if k not in routed},
         jnp.arange(cfg.num_layers - Ld)))
    return h, state, counts


def _scale(cfg: ModelConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


# --------------------------------------------------------------------------
# prefill: expanded


def _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, cache, page_tables,
             no_ctx: bool, moe_impl: str, stack, attn_impl: str = "xla"):
    """Solo and grouped prefill: ``tokens`` [G, T], one row a sequence.  Cold
    rows (``no_ctx``) under ``attn_impl`` "pallas" meet their rebuilt keys and
    values in the online-softmax kernel; every other chunk in XLA's forms."""
    G, T = tokens.shape
    rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    with jax.named_scope("smg.prefill.land"):  # where the chunk's rows stand and land
        pos = prefix_lens[:, None] + jnp.arange(T)[None, :]
        real = jnp.arange(T)[None, :] < t_reals[:, None]
        ctx_lens = prefix_lens + t_reals
        dest = page_slots(page_tables, pos, real, cache.shape[2]).reshape(-1)
    scale = _scale(cfg)

    def expanded(layer, q_nope, q_pe, ctx, pos, ctx_lens, *select):
        """Rebuild K and V of the context entries ``ctx`` [g, S, W] and attend."""
        c, k_pe = ctx[..., :rkv], ctx[..., rkv:rkv + dr]
        kernel = attn_impl.startswith("pallas")  # only where ``no_ctx`` calls this
        # the weights are stored by head, so a product batched by head leaves
        # the heads first; the kernel takes them so, XLA's form by token
        out = "ghsd" if kernel else "gshd"
        with jax.named_scope("smg.mla.kv"):
            k_nope = jnp.einsum(f"gsc,hcd->{out}", c, layer["w_uk"])
            v = jnp.einsum(f"gsc,hcd->{out}", c, layer["w_uv"])
        if kernel:
            from smg_tpu.ops.pallas.flash_prefill import flash_attention_prefill

            # prefix 0: a row's context is its own ``t_real`` tokens
            return flash_attention_prefill(
                q_nope, k_nope, v, ctx_lens, scale, q_pe=q_pe, k_pe=k_pe, kv_heads_first=True,
                interpret=(attn_impl == "pallas_interpret"))
        return latent_attention_prefill(q_nope, q_pe, k_nope, k_pe, v, pos, ctx_lens, scale,
                                        *select)

    def attend(q_nope, q_pe, entry, layer, l, cache, select=None):
        """``select`` [G, T, S] bool: the context positions each query reads
        (``ops/sparse_attention.py``; S the chunk where ``no_ctx``, else the
        table's width); None reads all."""
        cache = scatter_entries(cache, l, entry.reshape(G * T, -1), dest)
        if not no_ctx:
            # the pages hold the context, the chunk's own entries among them,
            # read back as decode will read them
            return latent_attention_prefill_cached(
                q_nope, q_pe, cache, l, page_tables, layer["w_uk"], layer["w_uv"], pos,
                ctx_lens, scale, rkv, dr, select), cache
        ctx = entry.astype(q_nope.dtype)  # the chunk is the whole context
        kv_bytes = G * T * cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim) \
            * ctx.dtype.itemsize
        chosen = () if select is None else (select,)
        if G > 1 and kv_bytes > EXPANDED_KV_BYTES:
            out = jax.lax.map(
                lambda r: expanded(layer, *(x[None] for x in r))[0],
                (q_nope, q_pe, ctx, pos, ctx_lens, *chosen))
        else:
            out = expanded(layer, q_nope, q_pe, ctx, pos, ctx_lens, *chosen)
        return out, cache

    h = embed_tokens(params, cfg, tokens)
    h, cache, _counts = stack(params, cfg, inv_freq, h, pos, real, cache, attend, moe_impl)
    with jax.named_scope("smg.lm_head"):
        last = jnp.take_along_axis(
            h, jnp.maximum(t_reals - 1, 0)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return unembed(params, cfg, last), cache


def forward_prefill(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [T] padded to bucket
    prefix_len: jnp.ndarray,  # scalar: tokens of the sequence before this chunk
    t_real: jnp.ndarray,  # scalar: valid new tokens (<= T)
    k_cache: jnp.ndarray,  # [L, P, ps, W]: the latent entries
    v_cache: jnp.ndarray,  # of zero size: this cache has no V buffer
    page_table: jnp.ndarray,  # [mp]
    attn_impl: str = "xla",  # the solo chunk attends in XLA's form; kept for the runner
    moe_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret"
    stack=_stack,  # the layers: another model of latent attention gives its own
    **unserved,
):
    """One chunk of one sequence behind the prefix its pages hold.  Returns
    (last_token_logits [V], k_cache, v_cache)."""
    _refuse(cfg, unserved)
    logits, k_cache = _prefill(
        params, cfg, inv_freq, tokens[None], prefix_len[None], t_real[None], k_cache,
        page_table[None], False, moe_impl, stack)
    return logits[0], k_cache, v_cache


def forward_prefill_batched(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [G, T] padded rows (t_real=0 rows are pure padding)
    prefix_lens: jnp.ndarray,  # [G]
    t_reals: jnp.ndarray,  # [G]
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    page_tables: jnp.ndarray,  # [G, mp]
    no_ctx: bool = False,  # static: every row starts its sequence
    moe_impl: str = "xla",
    attn_impl: str = "xla",  # "pallas" | "pallas_interpret": the kernel, where ``no_ctx``
    stack=_stack,
    **unserved,
):
    """Several sequences' chunks in one call.  Returns (logits [G, V],
    k_cache, v_cache)."""
    _refuse(cfg, unserved)
    logits, k_cache = _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache,
                               page_tables, no_ctx, moe_impl, stack,
                               attn_impl if no_ctx else "xla")
    return logits, k_cache, v_cache


def _refuse(cfg: ModelConfig, unserved: dict) -> None:
    """The runner passes every model the Llama family's keywords; this model
    serves none of them, and one that is set is an error, not ignored."""
    on = sorted(k for k, v in unserved.items() if v is not None and v is not False)
    if on:
        raise ValueError(f"{cfg.arch} does not take {', '.join(on)}")


# --------------------------------------------------------------------------
# decode: absorbed


def forward_decode_horizon(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [B] token fed this column
    positions: jnp.ndarray,  # [B] absolute position of that token
    entry_positions: jnp.ndarray,  # [B] cache token count at the frame's entry
    step_idx: jnp.ndarray,  # scalar: column within the frame
    k_cache: jnp.ndarray,  # [L, P, ps, W] read-only during the frame
    page_tables: jnp.ndarray,  # [B, mp]
    side: jnp.ndarray,  # [L, B, N, W] the frame's side buffer
    live: jnp.ndarray,  # [B] bool: the lane holds a sequence
    attn_impl: str = "xla",
    moe_impl: str = "xla",
    stack=_stack,
):
    """One decode column.  The frozen cache and the side buffer are read, the
    column's entries go to the side buffer.  Returns (logits [B, V], side,
    counts): int32 ``[picks, picks on held experts, held experts hit, most
    picks on held experts in one layer]`` of this column (what ``stack``
    counts)."""
    rkv = cfg.kv_lora_rank
    scale = _scale(cfg)

    def attend(q_nope, q_pe, entry, layer, l, side, select=None):
        """``select``: ``(slots, paged, fresh)`` of
        ``ops/sparse_attention.selected_slots``, the cached and fresh tokens
        each lane reads; None reads all."""
        B, W = entry.shape
        side = jax.lax.dynamic_update_slice(
            side, entry.reshape(1, B, 1, W).astype(side.dtype), (l, 0, step_idx, 0))
        side_l = jax.lax.dynamic_index_in_dim(side, l, 0, keepdims=False)
        with jax.named_scope("smg.mla.q"):
            q_abs = jnp.einsum("bhd,hcd->bhc", q_nope, layer["w_uk"])
            pad = jnp.zeros((*q_abs.shape[:-1], W - rkv - q_pe.shape[-1]), q_abs.dtype)
            q = jnp.concatenate([q_abs, q_pe, pad], axis=-1)  # on the entry's lanes
        if select is not None:
            from smg_tpu.ops.sparse_attention import attend_selected, gather_selected

            slots, paged, fresh = select
            ctx = attend_selected(q, gather_selected(k_cache, l, slots), side_l, paged, fresh,
                                  scale, value_lanes(rkv))
        elif attn_impl.startswith("pallas"):
            from smg_tpu.ops.pallas.decode_attention import latent_attention_decode_cached

            ctx = latent_attention_decode_cached(
                q, k_cache, side_l, step_idx + 1, l, page_tables, entry_positions,
                latent=value_lanes(rkv), scale=scale,
                interpret=(attn_impl == "pallas_interpret"))
        else:
            # one "head" as wide as the entry, the cache as its own V
            ctx = attention_decode_cached(q, k_cache, k_cache, side_l, side_l, step_idx + 1,
                                          l, page_tables, entry_positions, scale)
        with jax.named_scope("smg.mla.kv"):
            out = jnp.einsum("bhc,hcd->bhd", ctx[..., :rkv], layer["w_uv"])
        return out, side

    h = embed_tokens(params, cfg, tokens)
    h, side, counts = stack(params, cfg, inv_freq, h, positions, live, side, attend, moe_impl)
    return unembed(params, cfg, h), side, counts
