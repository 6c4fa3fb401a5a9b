"""Olmo-Hybrid: gated-delta linear-attention layers beside full attention.

``model_type: olmo_hybrid`` (``allenai/Olmo-Hybrid-7B``).  ``layer_types``
repeats one **period**: ``n`` ``linear_attention`` layers and then one
``full_attention`` layer (three and one, eight times, as published).  The
parameters are stacked by period and the stack is one ``lax.scan``, so depth
costs no compile time.

**The layers**, for token ``t`` with layer input ``x_t``.  Both kinds place
their norms as OLMo 2/3 do: none before a sublayer,
``h = h + RMSNorm(sublayer(h))`` for the mixer and for the SwiGLU MLP.

*Linear attention* (the gated delta rule, as in Gated DeltaNet), ``H`` heads
with keys of ``dk`` and values of ``dv``:

- ``q~, k~, v~ = W_q x_t, W_k x_t, W_v x_t``; every channel ``u`` of the three
  passes a causal depthwise convolution over time of width 4 and SiLU:
  ``u_t = silu(sum_{i=0..3} c_i u~_{t-3+i})``;
- per head: ``q_t = l2norm(q_t) / sqrt(dk)``, ``k_t = l2norm(k_t)``;
  ``b_t = 2 sigmoid(w_b x_t)`` (the 2 is ``linear_allow_neg_eigval``; 1
  without it); ``a_t = exp(-exp(A_log) softplus(w_a x_t + dt_bias))``;
- state ``S`` of ``[dv, dk]`` per head and sequence, zero at the start,
  float32: ``S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T``,
  ``o_t = S_t q_t``;
- ``y_t = W_o [RMSNorm_head(o_t) * silu(W_g x_t)]``, the norm's weight of
  ``dv`` shared over the heads.

*Full attention*: ``W_q, W_k, W_v, W_o``, RMSNorm over the whole width of
``q`` and of ``k`` before the heads are split, causal softmax attention over
the paged cache, **no rotary embedding** when ``rope_parameters.rope_theta``
is null (position reaches these layers through the recurrent ones); a number
there applies the usual rotate-half rope.

**What a sequence holds.**  Pages of K and V for the full layers only (the
cache's layer axis is the number of periods), and for every linear layer one
slot of recurrent state, ``[dk, H * dv]`` float32, and the last three inputs
of the convolution (``ops/linear_attention.py`` for the layouts).  Slot 0 is
the garbage slot.

**Departures from the equations above**: none in arithmetic.  The state is
held transposed with the heads fused (``[dk, H * dv]``), prefill runs the
recurrence in its chunked form, decode in the kernel ``smg.linattn.decode``.

**What this module serves**: ``forward_prefill``, ``forward_prefill_batched``
and ``forward_decode_horizon``, the forwards the runner launches for serving
on one device.  Verify blocks for speculation, embeddings, training, LoRA,
KV transfer, a mesh and a checkpoint are refused at start
(``SERVING_LIMITS``), not run wrong.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from smg_tpu.models.config import ModelConfig
from smg_tpu.models.llama import _mlp, _write_side, embed_tokens, unembed
from smg_tpu.ops.attention import (
    attention_decode_cached,
    attention_prefill,
    attention_prefill_batched,
    gather_layer_pages,
    gather_seq_kv,
    page_slots,
    scatter_kv_pages_full,
)
from smg_tpu.ops.linear_attention import (
    causal_conv,
    conv_decode,
    gated_delta_chunked,
    gated_delta_step,
    heads_to_pool,
    pool_to_heads,
    read_state,
    read_tail,
    tail_block,
    write_state,
    write_tail,
)
from smg_tpu.ops.norms import rms_norm
from smg_tpu.ops.rope import apply_rope

Params = dict[str, Any]

# How loudly the two kinds of mixer speak into the residual stream of RANDOM
# weights: the weight ``init_params`` gives their post-norms (an MLP's is 1; a
# full layer's is ``FULL_MIXER_NORM`` x sqrt(linear layers a period)).  With
# every weight at 1 the layers that read the pages are 4 of 32 equal voices at
# the benchmark's cut, one wrong page of a sequence's 44 moves the logits by
# 0.22 standard deviations, and the linear mixers' own rounding (they make most
# of the serving path's error: the delta rule carries every key's rounding
# forward, and a head whose state a strong decay has just emptied hands on the
# normalised direction of a near-zero vector) moves them by 0.25, most of the
# 0.30 the benchmark's comparison allows: that comparison could not tell a
# wrong cache from rounding.  At these weights, on a v5e over 24 seeds
# (PERF.md, Findings, PR 29): error 0.13-0.16, one wrong page 0.58-0.70, a
# wrong or emptied state slot over 4.
LINEAR_MIXER_NORM = 0.3
FULL_MIXER_NORM = 1.0

# what the engine must refuse for this architecture, each with its sentence
SERVING_LIMITS = {
    "speculative": "olmo_hybrid has no verify block: a rejected draft would have "
                   "to take its tokens out of the recurrent state again",
    "lora": "olmo_hybrid has no LoRA deltas on its projections",
    "embeddings": "olmo_hybrid has no embedding forward",
    "mesh": "olmo_hybrid runs on one device: neither the state pool nor its "
            "kernel is partitioned over a mesh",
    "kv_transfer": "olmo_hybrid cannot export a sequence: its recurrent state "
                   "is not in the pages",
    "checkpoint": "olmo_hybrid has no safetensors key map yet: it is served with "
                  "seeded random weights (--model-preset), not from --model-path",
}


def period_of(layer_types: tuple[str, ...]) -> tuple[int, int]:
    """``(periods, linear layers a period)`` of a ``layer_types`` list that is
    one period (linear layers, then one full layer) repeated; ValueError with
    a sentence for any other list."""
    kinds = set(layer_types)
    if not kinds <= {"linear_attention", "full_attention"}:
        raise ValueError(f"olmo_hybrid: unknown layer types {sorted(kinds)}")
    if "full_attention" not in kinds or "linear_attention" not in kinds:
        raise ValueError("olmo_hybrid: layer_types needs both kinds of layer")
    n = layer_types.index("full_attention")
    period = ("linear_attention",) * n + ("full_attention",)
    if n == 0 or len(layer_types) % (n + 1) or layer_types != period * (len(layer_types) // (n + 1)):
        raise ValueError(
            "olmo_hybrid: layer_types must repeat one period of linear_attention "
            f"layers followed by one full_attention layer; got {list(layer_types)} "
            "(a stack in any other order is models/nemotron_h.py's, whose layers "
            "are written out one by one from a pattern string)")
    return len(layer_types) // (n + 1), n


def conv_channels(cfg: ModelConfig) -> int:
    H = cfg.linear_num_heads
    return H * (2 * cfg.linear_key_head_dim + cfg.linear_value_head_dim)


def state_shapes(cfg: ModelConfig, slots: int) -> tuple[tuple, tuple]:
    """Shapes of the two state pools for ``slots`` slots (the garbage slot
    included): recurrent state float32, convolution tail in the model's dtype,
    a slot's tail as whole tiles (``ops.linear_attention.tail_block``)."""
    P, n = period_of(cfg.layer_types)
    H, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    K = cfg.linear_conv_kernel_dim
    return ((P * n, slots, dk, H * dv),
            (P * n, slots, *tail_block(conv_channels(cfg), K, cfg.dtype)))


def decode_step(cfg: ModelConfig) -> dict:
    """The linear layers' decode step, for the runner: the name it goes by in
    ``loads()``, the forwards' keyword that picks its form, what the layers
    are called, and whether the kernel's blocks fit this shape."""
    from smg_tpu.ops.pallas import linattn_decode

    return {"name": "linattn_decode", "arg": "linattn_impl", "layers": "linear-attention",
            "kernel_fits": linattn_decode.supported(
                cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim)}


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random weights (tests, benchmarks).  ``A_log`` and ``dt_bias`` as
    Gated DeltaNet initialises them: ``exp(A_log)`` uniform in (0, 16), the
    softplus of ``dt_bias`` log-uniform in (0.001, 0.1).  Norm weights 1 but
    for the two mixers' post-norms (``LINEAR_MIXER_NORM``, ``FULL_MIXER_NORM``)."""
    E, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hl, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    P, n = period_of(cfg.layer_types)
    L = P * (n + 1)
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 32))

    def normal(shape, scale=0.02):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(dtype)

    out_scale = 0.02 / math.sqrt(2 * L)

    def mlp(lead):
        return {
            "attn_post_norm": jnp.ones((*lead, E), dtype),
            "mlp_post_norm": jnp.ones((*lead, E), dtype),
            "w_gate": normal((*lead, E, F)),
            "w_up": normal((*lead, E, F)),
            "w_down": normal((*lead, F, E), out_scale),
        }

    dt = jnp.exp(jax.random.uniform(next(ks), (P, n, Hl), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    lin = {
        **mlp((P, n)),
        "wq": normal((P, n, E, Hl, dk)),
        "wk": normal((P, n, E, Hl, dk)),
        "wv": normal((P, n, E, Hl, dv)),
        "wg": normal((P, n, E, Hl, dv)),
        "wo": normal((P, n, Hl, dv, E), out_scale),
        "conv": jax.random.uniform(next(ks), (P, n, cfg.linear_conv_kernel_dim,
                                              conv_channels(cfg)), jnp.float32,
                                   -0.5, 0.5).astype(dtype),
        "w_a": normal((P, n, E, Hl)),
        "w_b": normal((P, n, E, Hl)),
        "A_log": jnp.log(jax.random.uniform(next(ks), (P, n, Hl), jnp.float32, 1e-3, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
        "o_norm": jnp.ones((P, n, dv), dtype),
        "attn_post_norm": jnp.full((P, n, E), LINEAR_MIXER_NORM, dtype),
    }
    full = {
        **mlp((P,)),
        "attn_post_norm": jnp.full((P, E), FULL_MIXER_NORM * math.sqrt(n), dtype),
        "wq": normal((P, E, H, D)),
        "wk": normal((P, E, K, D)),
        "wv": normal((P, E, K, D)),
        "wo": normal((P, H, D, E), out_scale),
        "q_norm": jnp.ones((P, H * D), dtype),
        "k_norm": jnp.ones((P, K * D), dtype),
    }
    params: Params = {
        "embed": normal((V, E)),
        "periods": {"lin": lin, "full": full},
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
# the two kinds of layer.  What differs between prefill and decode is how a
# layer reaches what its sequence holds, so each takes that as a function:
# ``mix(qkv, g, beta)`` runs the convolution and the recurrence over the
# linear layer's state, ``attend(q, k, v)`` writes and reads the pages.  Both
# return their result and whatever they changed, which the layer hands back.


@jax.named_scope("smg.mlp")
def _mlp_residual(h, layer, cfg):
    return h + rms_norm(_mlp(layer, h, cfg), layer["mlp_post_norm"], cfg.rms_norm_eps)


@jax.named_scope("smg.linattn.layer")
def linear_layer(h, layer: Params, cfg: ModelConfig, mix):
    """``h`` [..., E].  ``mix(qkv [..., C], g [..., H], beta [..., H])`` returns
    the recurrence's outputs ``o`` [..., H, dv] (float32) and its new state.
    Returns ``(h, new state)``."""
    f32 = jnp.float32
    qkv = jnp.concatenate([
        jnp.einsum("...e,ehd->...hd", h, layer[w]).reshape(*h.shape[:-1], -1)
        for w in ("wq", "wk", "wv")], axis=-1)
    # the two per-head gates feed an exponential summed over the sequence:
    # their 30 columns are accumulated and kept in float32
    a = jnp.einsum("...e,eh->...h", h, layer["w_a"], preferred_element_type=f32)
    b = jnp.einsum("...e,eh->...h", h, layer["w_b"], preferred_element_type=f32)
    g = -jnp.exp(layer["A_log"].astype(f32)) * jax.nn.softplus(a + layer["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
    o, state = mix(qkv, g, beta)
    gate = jnp.einsum("...e,ehd->...hd", h, layer["wg"]).astype(f32)
    o = rms_norm(o, layer["o_norm"], cfg.rms_norm_eps) * jax.nn.silu(gate)
    y = jnp.einsum("...hd,hde->...e", o.astype(h.dtype), layer["wo"])
    h = h + rms_norm(y, layer["attn_post_norm"], cfg.rms_norm_eps)
    return _mlp_residual(h, layer, cfg), state


def split_qkv(y, cfg: ModelConfig):
    """The convolution's output ``y`` [..., C] as normalised ``q``, ``k``
    [..., H, dk] and ``v`` [..., H, dv], float32."""
    H, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)
    heads = lambda x, d: x.reshape(*x.shape[:-1], H, d)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    return l2(heads(q, dk)) * (dk ** -0.5), l2(heads(k, dk)), heads(v, dv)


@jax.named_scope("smg.attn.layer")
def full_layer(h, layer: Params, cfg: ModelConfig, positions, inv_freq, attend):
    """``h`` [..., E].  ``attend(q [..., H, D], k, v [..., K, D])`` returns the
    attention's output [..., H, D] and the caches it wrote.  Returns ``(h,
    caches)``."""
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    proj = lambda w: jnp.einsum("...e,ehd->...hd", h, layer[w])
    q, k, v = proj("wq"), proj("wk"), proj("wv")
    whole = lambda x, w, n: rms_norm(
        x.reshape(*x.shape[:-2], n * D), layer[w], cfg.rms_norm_eps).reshape(x.shape)
    q, k = whole(q, "q_norm", H), whole(k, "k_norm", K)
    if cfg.rope_theta:
        q, k = apply_rope(q, positions, inv_freq), apply_rope(k, positions, inv_freq)
    out, caches = attend(q, k, v)
    y = jnp.einsum("...hd,hde->...e", out.astype(h.dtype), layer["wo"])
    h = h + rms_norm(y, layer["attn_post_norm"], cfg.rms_norm_eps)
    return _mlp_residual(h, layer, cfg), caches


def _stack(params: Params, cfg: ModelConfig, h, carry, lin, full):
    """The layers as one scan over periods.  ``lin(h, layer, li, carry)`` and
    ``full(h, layer, p, carry)`` run one layer each (``li`` the linear layer's
    index in the state pools, ``p`` the full layer's in the cache) and return
    ``(h, carry)``."""
    P, n = period_of(cfg.layer_types)
    # a linear layer's weights are sliced out of the flat stack by the layer's
    # own index, so that each slice feeds its matmul directly: sliced by
    # period first, the ``[n, ...]`` block of every weight is copied out once
    # a period (1.3 GB a period at the published widths)
    flat = jax.tree.map(lambda x: x.reshape(P * n, *x.shape[2:]), params["periods"]["lin"])

    def linear(c, li):
        h, carry = c
        with jax.named_scope("smg.linattn.layer"):
            layer = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, li, 0, keepdims=False), flat)
        return lin(h, layer, li, carry), None

    def body(c, xs):
        full_layer_params, p = xs
        # the period's linear layers as a scan too: unrolled, every program
        # holds their body n times over (twice the compile time and size)
        c, _ = jax.lax.scan(linear, c, p * n + jnp.arange(n))
        return full(*c[:1], full_layer_params, p, c[1]), None

    (h, carry), _ = jax.lax.scan(body, (h, carry), (params["periods"]["full"], jnp.arange(P)))
    return h, carry


# --------------------------------------------------------------------------
# prefill


def _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache, v_cache,
             page_tables, s_pool, c_pool, slots, attention):
    """Solo and grouped prefill: ``tokens`` [G, T], one row a sequence.
    ``attention(q, k, v, kc, vc, p, pos)`` is the full layers' attention over
    caches the chunk is already in.  The recurrence runs in its chunked form
    from the state in ``slots`` (zero for a row that starts its sequence); a
    padded token has ``beta`` 0 and ``g`` 0 and stays out of the convolution's
    tail, a padded row names the garbage slot."""
    G, T = tokens.shape
    K, D, H = cfg.num_kv_heads, cfg.head_dim, cfg.linear_num_heads
    with jax.named_scope("smg.prefill.land"):  # where the chunks' rows stand and land
        pos = prefix_lens[:, None] + jnp.arange(T)[None, :]
        real = jnp.arange(T)[None, :] < t_reals[:, None]
        dest = page_slots(page_tables, pos, real, k_cache.shape[2]).reshape(-1)
        keep = (prefix_lens > 0).astype(jnp.float32)  # 0 where the sequence starts here
    taps = cfg.linear_conv_kernel_dim - 1
    h = embed_tokens(params, cfg, tokens)

    def lin(h, layer, li, carry):
        kc, vc, s_pool, c_pool = carry

        def mix(qkv, g, beta):
            tail = read_tail(c_pool, li, slots, taps)  # [G, K-1, C]
            y, tail = causal_conv(qkv, tail * keep[:, None, None].astype(tail.dtype),
                                  layer["conv"], t_reals)
            q, k, v = split_qkv(y, cfg)
            S0 = pool_to_heads(read_state(s_pool, li, slots), H) * keep[:, None, None, None]
            o, S = gated_delta_chunked(q, k, v, jnp.where(real[..., None], g, 0.0),
                                       jnp.where(real[..., None], beta, 0.0), S0)
            return o, (write_state(s_pool, li, slots, heads_to_pool(S)),
                       write_tail(c_pool, li, slots, tail))

        h, (s_pool, c_pool) = linear_layer(h, layer, cfg, mix)
        return h, (kc, vc, s_pool, c_pool)

    def full(h, layer, p, carry):
        kc, vc, s_pool, c_pool = carry

        def attend(q, k, v):
            kc2, vc2 = scatter_kv_pages_full(kc, vc, p, k.reshape(G * T, K, D),
                                             v.reshape(G * T, K, D), dest)
            return attention(q, k, v, kc2, vc2, p, pos), (kc2, vc2)

        h, (kc, vc) = full_layer(h, layer, cfg, pos, inv_freq, attend)
        return h, (kc, vc, s_pool, c_pool)

    h, carry = _stack(params, cfg, h, (k_cache, v_cache, s_pool, c_pool), lin, full)
    with jax.named_scope("smg.lm_head"):
        last = jnp.take_along_axis(
            h, jnp.maximum(t_reals - 1, 0)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return (unembed(params, cfg, last), *carry)


def forward_prefill(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [T] padded to bucket
    prefix_len: jnp.ndarray,  # scalar: tokens of the sequence before this chunk
    t_real: jnp.ndarray,  # scalar: valid new tokens (<= T)
    k_cache: jnp.ndarray,  # [periods, P, ps, K*D]: the full layers' pages
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [mp]
    s_pool: jnp.ndarray,  # [linear layers, slots, dk, H*dv] float32
    c_pool: jnp.ndarray,  # [linear layers, slots, R, W]: ``tail_block``
    slot: jnp.ndarray,  # scalar: the sequence's state slot
    attn_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret" (tests)
):
    """One chunk of one sequence, behind the prefix its pages and its slot
    hold.  Returns (last_token_logits [V], k_cache, v_cache, s_pool, c_pool)."""
    T = tokens.shape[0]
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def attention(q, k, v, kc, vc, p, pos):
        if attn_impl.startswith("pallas"):
            from smg_tpu.ops.pallas.prefill_attention import paged_attention_prefill

            return paged_attention_prefill(
                q[0], k[0].reshape(T, -1), v[0].reshape(T, -1), kc, vc, p, page_table,
                prefix_len, t_real, scale, interpret=(attn_impl == "pallas_interpret"))[None]
        k_ctx, v_ctx = gather_seq_kv(kc, vc, p, page_table, cfg.num_kv_heads)
        return attention_prefill(q[0], k_ctx, v_ctx, pos[0], prefix_len + t_real, scale)[None]

    logits, *rest = _prefill(
        params, cfg, inv_freq, tokens[None], prefix_len[None], t_real[None], k_cache,
        v_cache, page_table[None], s_pool, c_pool, slot[None], attention)
    return (logits[0], *rest)


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
    s_pool: jnp.ndarray,
    c_pool: jnp.ndarray,
    slots: jnp.ndarray,  # [G]; a padded row names slot 0
    no_ctx: bool = False,  # static: every row starts its sequence
    attn_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret" (tests); no_ctx only
):
    """Several sequences' chunks in one call.  Returns (logits [G, V],
    k_cache, v_cache, s_pool, c_pool)."""
    G, T = tokens.shape
    K, D = cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(D)
    ctx_lens = prefix_lens + t_reals

    def attention(q, k, v, kc, vc, p, pos):
        if no_ctx and attn_impl.startswith("pallas"):
            from smg_tpu.ops.pallas.flash_prefill import flash_attention_prefill

            return flash_attention_prefill(q, k, v, ctx_lens, scale,
                                           interpret=(attn_impl == "pallas_interpret"))
        if no_ctx:  # the chunk is the whole context
            return attention_prefill_batched(q, k, v, pos, ctx_lens, scale)
        kl, vl = gather_layer_pages(kc, vc, p, page_tables)  # [G, mp, ps, KD]
        return attention_prefill_batched(q, kl.reshape(G, -1, K, D), vl.reshape(G, -1, K, D),
                                         pos, ctx_lens, scale)

    return _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache,
                    v_cache, page_tables, s_pool, c_pool, slots, attention)


# --------------------------------------------------------------------------
# decode


def forward_decode_horizon(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [B] token fed this column
    positions: jnp.ndarray,  # [B] absolute position of that token
    entry_positions: jnp.ndarray,  # [B] cache token count at the frame's entry
    step_idx: jnp.ndarray,  # scalar: column within the frame
    k_cache: jnp.ndarray,  # read-only during the frame
    v_cache: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, mp]
    hk_all: jnp.ndarray,  # [periods, B, N, K*D] the frame's side buffers
    hv_all: jnp.ndarray,
    s_pool: jnp.ndarray,
    c_pool: jnp.ndarray,
    slots: jnp.ndarray,  # [B]; a padded row names slot 0
    runs: jnp.ndarray,  # [B] bool: the lane runs this column
    attn_impl: str = "xla",
    linattn_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret"
):
    """One decode column.  The full layers read the frozen cache and the
    side buffers, as in ``models/llama.py``; the linear layers advance the
    state in their slots by one token, in place.  A lane with ``runs`` false
    (a padded row) gets ``alpha`` 1 and ``beta`` 0 and keeps its convolution
    tail, so its slot is left bit for bit.  Returns (logits [B, V], hk_all,
    hv_all, s_pool, c_pool)."""
    B = tokens.shape[0]
    K, D = cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(D)
    h = embed_tokens(params, cfg, tokens)

    def lin(h, layer, li, carry):
        hk, hv, s_pool, c_pool = carry

        def mix(qkv, g, beta):
            y, c_new = conv_decode(c_pool, li, slots, runs, qkv, layer["conv"])
            q, k, v = split_qkv(y, cfg)
            alpha = jnp.where(runs[:, None], jnp.exp(g), 1.0)
            beta = jnp.where(runs[:, None], beta, 0.0)
            if linattn_impl.startswith("pallas"):
                from smg_tpu.ops.pallas.linattn_decode import linattn_decode

                o, s_new = linattn_decode(s_pool, li, slots, q, k, v, alpha, beta,
                                          interpret=(linattn_impl == "pallas_interpret"))
            else:
                o, s_new = gated_delta_step(s_pool, li, slots, q, k, v, alpha, beta)
            return o, (s_new, c_new)

        h, (s_pool, c_pool) = linear_layer(h, layer, cfg, mix)
        return h, (hk, hv, s_pool, c_pool)

    def full(h, layer, p, carry):
        hk_all, hv_all, s_pool, c_pool = carry

        def attend(q, k, v):
            hk_l, hv_l, side = _write_side((hk_all, hv_all), k, v, p, step_idx)
            if attn_impl.startswith("pallas"):
                from smg_tpu.ops.pallas.decode_attention import paged_attention_decode_cached

                out = paged_attention_decode_cached(
                    q, k_cache, v_cache, hk_l, hv_l, step_idx + 1, p, page_tables,
                    entry_positions, scale, interpret=(attn_impl == "pallas_interpret"))
            else:
                out = attention_decode_cached(
                    q, k_cache, v_cache, hk_l, hv_l, step_idx + 1, p, page_tables,
                    entry_positions, scale)
            return out, side

        h, (hk_all, hv_all) = full_layer(h, layer, cfg, positions, inv_freq, attend)
        return h, (hk_all, hv_all, s_pool, c_pool)

    h, carry = _stack(params, cfg, h, (hk_all, hv_all, s_pool, c_pool), lin, full)
    return (unembed(params, cfg, h), *carry)

