"""LongCat-Flash: two latent-attention sublayers a layer, the routed experts as
a shortcut round the second, identity experts among the router's outputs.

``model_type: longcat_flash`` (``meituan-longcat/LongCat-Flash-Chat``).  ``N`` is
RMSNorm, every MLP is SwiGLU.  One layer ``l`` of the model, input ``h``:

    u   = h + MLA_0(N_in0(h))            # cache layer 2l
    x0  = N_post0(u)
    m   = MoE(x0)                        # the shortcut branch
    v   = u + FFN_0(x0)                  # dense, ffn_hidden_size
    w   = v + MLA_1(N_in1(v))            # cache layer 2l + 1
    z   = w + FFN_1(N_post1(w))          # dense
    out = z + m

*The branch*: ``s = softmax(W_r x)`` in float32 over the router's
``num_experts`` outputs, of which the last ``zero_experts`` are identity
experts; the ``moe_topk`` largest of ``s + b`` (``b`` a selection bias an
output: it picks and does not weigh); ``w_i = routed_scaling_factor x s_i``,
not renormalised; ``MoE(x) = sum_{picked real i} w_i E_i(x) + (sum_{picked
identity i} w_i) x``, each ``E_i`` a SwiGLU MLP of ``expert_ffn_hidden_size``.
A token runs 0 to ``moe_topk`` real experts.  Nothing below ``m`` reads it
until the last line, and the program says no more than that: the branch is
computed from ``x0`` and added behind ``FFN_1``, so XLA may place it beside
the second sublayer, and nothing here orders the two (compiled for a v5e it
routes at once and runs the experts' products last, behind ``FFN_1``'s first
two products; PERF.md, Findings, PR 43).

*The attention*, both sublayers: ``models/pangu_moe.py``'s latent attention
(``latent_attention``, ``_latent_qkv``), with the two scales the config turns
on: ``c_q = s_q N(W_dq x)``, ``s_q = sqrt(hidden / q_lora_rank)``; ``c = s_kv
N(c)``, ``s_kv = sqrt(hidden / kv_lora_rank)``; ``k_pe`` is not scaled.  A
token leaves ``[c | k_pe]`` in **each** sublayer: layer ``l`` of the model is
cache layers ``2l`` and ``2l + 1`` (``ModelConfig.num_cache_layers`` is twice
``num_layers``).

**Experts held.**  As ``models/pangu_moe.py``: this process holds the real
experts ``cfg.held_experts``; a pick on a real expert held elsewhere adds
nothing here; a pick on an identity expert adds ``w_i x`` on the token's own
chip, whatever the chip holds (``ops/moe.py``).

**Departures from the equations above**, none in arithmetic:

- a scale is folded into its norm's weight in float32 (``pangu_moe._scaled``:
  ``s N(x)`` rounded once, with the norm);
- prefill runs attention expanded and decode absorbed (``q'_h = W_uk,h^T
  q_nope_h`` meets the scaled ``c`` itself, ``o_h = W_uv,h (sum p c)``): the
  same products in another order;
- ``W_uq`` is stored as its two parts and ``W_dkv`` as the latent's beside the
  rotary key's, as ``pangu_moe.init_params`` says why; the rotary rows of both
  are stored de-interleaved (a published row ``2i`` at ``i``, ``2i + 1`` at
  ``d/2 + i``), so that rotate-half over the stored lanes is the published
  interleaved pairing (the scores are the same numbers: queries and keys are
  permuted alike);
- the two sublayers' weights are two stacks ``params["layers"]["sub"][i]``, so
  that the scan hands each sublayer its matrices without a second index.

**What this module serves**: ``forward_prefill``, ``forward_prefill_batched``
and ``forward_decode_horizon`` on one device, which are ``pangu_moe``'s over
this file's ``_stack``; everything in ``SERVING_LIMITS`` is refused at start,
not run wrong.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from smg_tpu.models import pangu_moe
from smg_tpu.models.config import ModelConfig
from smg_tpu.models.llama import _mlp, _mlp_residual, _norm
from smg_tpu.models.pangu_moe import cache_lanes  # noqa: F401  (the callers', by this name)
from smg_tpu.models.pangu_moe import latent_attention
from smg_tpu.ops import moe

Params = dict[str, Any]

# RANDOM weights (``init_params``), drawn so that every part of the block
# speaks and a comparison of logits against a float32 reference (the
# benchmark's, tolerance 0.30 of a row's deviation) hears each of them.  The
# sizes below are in units of a normed vector's lanes (mean square 1); they
# were set on a v5e at the published widths (PERF.md, Findings, PR 43) and
# none is a configuration's to say.
#
# *The attention.*  Its projections are drawn at 1 / sqrt(fan-in) *of their
# scaled inputs* (``W_uq`` over ``s_q``, ``W_uk`` and ``W_uv`` over ``s_kv``), so
# that keys and values have unit size with the scales in, and a program that
# leaves a scale out has them of another size.  The queries are ``SCORE_STD``
# times unit size, which is the scores' standard deviation: a softmax over
# ``n`` keys then rests on about ``n / exp(sd^2)`` of them, and one page of a
# context says something (at 1.0 one wrong page of 44 moved the logits by
# 0.43-0.48 of a row's deviation, at 2.0 by 0.7-1.6, at 2.5 rounding itself by
# 0.08-0.10).  ``W_o`` is ``ATTN_OUT_GAIN`` over 1 / sqrt(fan-in): the block has
# no norm behind its attention, a softmax over some tens of unit values leaves
# a fifth of a unit, and at 4 the first sublayer says one unit beside dense
# MLPs that say most of one.  At 16 the controls on the cache read the same
# and those on the expert branch a third to a sixth as much: the attention's
# outputs add up along the stream (its size grew 1 : 3 : 5.5 : 7.6 over the
# four layers) and the branch is heard against that.
#
# *The routers read lanes that only the embedding writes*, as
# ``models/exaone_moe.py``'s do and for its reason: the pick of the 12 largest
# of 768 scores is a step, the 12th and the 13th lie 3 % of the logits'
# deviation apart, and with routers that read the whole normed stream a
# bfloat16 program and a float32 reference picked otherwise in one token-layer
# of twenty, neither wrong, the logits a whole pick apart; the routed experts
# then had to be drawn too quiet for a comparison to hear them.  So the last
# ``route_lanes`` lanes of the embedding are ``+-EMBED_STD`` (a sign a token and
# lane), no projection writes there (``W_o`` and every ``W_down`` have zeros in
# those columns), and the routers' rows are zero everywhere else.  The stream
# there is the token's signs times one magnitude a token: the identity term
# adds ``w x`` to it and the norm divides it, each lane alike, so every
# rounding moves the magnitude and no sign, the logits are the signs'
# projection times a positive number, and their order is the same in either
# precision (the selection bias, small beside the scores, can still part them
# where ``s + b`` of the 12th and 13th agree to a part in a thousand).  Which
# outputs a token picks then depends on the token and the layer, not on its
# context, which a checkpoint's routers do not do; the rows an expert gets in
# a column are those of tokens drawn apart.  The magnitude is the embedding's
# over the stream's root mean square, which grows with depth, so layer
# ``l``'s router is drawn ``sqrt(2l + 1)`` times as loud (the sublayers behind
# it): ``ROUTER_GAIN`` is the logits' deviation where the first attention
# says one unit, and it came out 2.1, 2.1, 2.0, 2.0 over the four layers (the
# identity term, which grows the routing lanes too, makes up what the square
# root misses).  At 2 a token's identity picks weigh 0.8-1.0 together and a
# pick on a held expert 0.13-0.21.
#
# *The experts*: a real expert's result is ``ROUTED_OUT`` times its input's
# size (its output projection's scale follows from the widths,
# ``_swiglu_size``).  A token sends one pick in four layers to a chip's 16 of
# 768 outputs; at 15 that pick moves the stream by a third of its size, and
# held experts that give nothing move every compared row by 0.7-2.6 of its
# deviation where rounding moves it by 0.05-0.08.  The dense MLPs are normal
# 0.02, their output projections scaled down by depth.
EMBED_STD = 0.02
SCORE_STD = 2.0
ATTN_OUT_GAIN = 4.0
ROUTE_LANES = 128
ROUTER_GAIN = 2.0
ROUTED_OUT = 15.0

# the selection bias: small beside the scores' spread and not zero, so that
# the pick by ``s + b`` is a path that runs (a token in some tens picks
# otherwise than by ``s``)
SELECT_BIAS_STD = 2e-4


def route_lanes(hidden: int) -> int:
    """How many lanes at the end of the residual stream the routers read."""
    return min(ROUTE_LANES, hidden // 4)


def _swiglu_size(std: float) -> float:
    """Root mean square of ``silu(g) u`` for ``g`` and ``u`` normal of
    deviation ``std``, by quadrature: what an expert's hidden activations are
    where its first two projections are drawn at ``std / sqrt(hidden)``."""
    g = np.linspace(-12.0, 12.0, 4801) * std
    density = np.exp(-0.5 * (g / std) ** 2)
    silu2 = (g / (1.0 + np.exp(-g))) ** 2
    return float(np.sqrt(np.sum(silu2 * density) / np.sum(density)) * std)


SERVING_LIMITS = {
    "speculative": "longcat_flash has no verify block and no drafter of its own",
    "lora": "longcat_flash has no LoRA deltas on its projections",
    "embeddings": "longcat_flash has no embedding forward",
    "mesh": "longcat_flash runs on one device: the experts' exchange between chips "
            "that hold different experts does not exist yet",
    "kv_transfer": "longcat_flash cannot export a sequence: the transfer carries K "
                   "and V buffers and its cache has one latent buffer",
    "checkpoint": "longcat_flash has no safetensors key map yet: it is served with "
                  "seeded random weights (--model-preset), not from --model-path",
}

#: the frame's counts: ``pangu_moe``'s four and the picks on identity experts
ROUTED_COUNTS = (*pangu_moe.ROUTED_COUNTS, "picks_zero")


def prefill_workspace_bytes(cfg: ModelConfig, tokens: int, dtype: str) -> int:
    """``pangu_moe.prefill_workspace_bytes`` at this model's widths, and the
    branch's float32 result, which lives through the second sublayer."""
    return (pangu_moe.prefill_workspace_bytes(cfg, tokens, dtype)
            + tokens * cfg.hidden_size * 4)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random weights (tests, benchmarks), drawn as the comment above says;
    norm weights 1."""
    E, F, V, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
    H, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    Fm, X, Xh = cfg.moe_intermediate_size, cfg.num_experts, cfg.held_experts[1]
    sq, skv = cfg.mla_q_scale / SCORE_STD, cfg.mla_kv_scale
    R = route_lanes(E)
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 64))
    out_scale = 0.02 / math.sqrt(2 * 2 * L)  # 2L blocks of one attention and one MLP
    # the routing lanes hear no sublayer: only the embedding writes them
    speaks = (jnp.arange(E) < E - R).astype(jnp.float32)

    def normal(shape, scale=0.02, dtype=dtype, mask=None):
        x = jax.random.normal(next(ks), shape, jnp.float32) * scale
        return (x if mask is None else x * mask).astype(dtype)

    def sublayer():
        ones = lambda *shape: jnp.ones((L, *shape), dtype)
        return {
            "attn_norm": ones(E), "mlp_norm": ones(E),
            "w_dq": normal((L, E, rq), E ** -0.5), "q_norm": ones(rq),
            "w_uq_nope": normal((L, H * dn, rq), rq ** -0.5 / sq),
            "w_uq_pe": normal((L, dr, H, rq), rq ** -0.5 / sq),
            "w_dkv": normal((L, E, rkv), E ** -0.5), "kv_norm": ones(rkv),
            "w_dk_pe": normal((L, E, dr), E ** -0.5),
            "w_uk": normal((L, H, rkv, dn), rkv ** -0.5 / skv),
            "w_uv": normal((L, H, rkv, dv), rkv ** -0.5 / skv),
            "wo": normal((L, H * dv, E), ATTN_OUT_GAIN * (H * dv) ** -0.5, mask=speaks),
            "w_gate": normal((L, E, F)), "w_up": normal((L, E, F)),
            "w_down": normal((L, F, E), out_scale, mask=speaks),
        }

    signs = jnp.where(jax.random.bernoulli(next(ks), 0.5, (V, R)), EMBED_STD, -EMBED_STD)
    embed = normal((V, E), EMBED_STD).at[:, E - R:].set(signs.astype(dtype))
    depth = jnp.sqrt(2.0 * jnp.arange(L) + 1.0)[:, None, None]
    router = jnp.zeros((L, E, X), dtype).at[:, E - R:].set(
        normal((L, R, X), ROUTER_GAIN / (EMBED_STD * R ** 0.5), mask=depth))
    # an expert's hidden activations by the widths, its result ROUTED_OUT units
    down = ROUTED_OUT / (_swiglu_size(0.02 * E ** 0.5) * Fm ** 0.5)
    return {
        "embed": embed,
        "layers": {"sub": (sublayer(), sublayer()), "router": router,
                   "select_bias": normal((L, X), SELECT_BIAS_STD, jnp.float32)},
        "experts": {"w_gate": normal((L, Xh, E, Fm)), "w_up": normal((L, Xh, E, Fm)),
                    "w_down": normal((L, Xh, Fm, E), down, mask=speaks)},
        "final_norm": jnp.ones((E,), dtype),
        "lm_head": normal((E, V)),
    }


def logical_axes(cfg: ModelConfig) -> Params:
    """Every array replicated: this module runs on one device."""
    return jax.tree.map(lambda x: (None,) * x.ndim,
                        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))


@jax.named_scope("smg.moe.counts")
def merge_counts(total, new):
    """The counts of one more layer, or column: all add up but the fourth,
    which is kept as a maximum."""
    return jnp.where(np.array([name != "rows_max" for name in ROUTED_COUNTS]), total + new,
                     jnp.maximum(total, new))


@jax.named_scope("smg.scmoe.shortcut")
def shortcut(x, layer: Params, experts: Params, i, cfg: ModelConfig, live, impl: str):
    """The expert branch ``MoE(x)`` of the normed tokens ``x`` [..., E], float32:
    what the held experts give for the picks on them, and the identity picks'
    ``w_i x``.  ``experts`` holds the routed experts' weights of all layers,
    ``i`` picks this layer's.  ``live`` [...] marks real tokens: a padded one
    picks nothing, an identity expert included.  Returns ``m`` and the layer's
    counts."""
    flat = x.reshape(-1, x.shape[-1])
    routing = moe.route(flat, layer["router"], top_k=cfg.num_experts_per_tok,
                        scoring=cfg.moe_scoring, norm_topk=cfg.norm_topk_prob,
                        scale=cfg.routed_scaling_factor, select_bias=layer["select_bias"])
    alive = live.reshape(-1)
    routing = routing._replace(experts=jnp.where(alive[:, None], routing.experts, -1))
    y, (rows, hit) = moe.expert_layer(flat, routing, experts["w_gate"], experts["w_up"],
                                      experts["w_down"], cfg.held_experts, impl, layer=i)
    zero = jnp.int32(0)
    if cfg.zero_experts:
        same, zero = moe.identity_picks(flat, routing, cfg.num_experts - cfg.zero_experts)
        y = y + same
    picks = jnp.sum(alive).astype(jnp.int32) * cfg.num_experts_per_tok
    return y.reshape(*x.shape[:-1], -1), jnp.stack([picks, rows, hit, rows, zero])


def _stack(params: Params, cfg: ModelConfig, inv_freq, h, positions, live, state, attend,
           moe_impl: str):
    """The layers, one ``lax.scan`` whose step is the double block.  Returns
    ``h``, the forwards' ``state`` and the branches' counts summed over the
    layers (the fourth kept as a maximum)."""
    experts = params["experts"]

    def block(carry, xs):
        (h, state, counts), (layer, l) = carry, xs
        first, second = layer["sub"]
        with jax.named_scope("smg.mla.block"):
            o, state = latent_attention(first, cfg, _norm(h, first["attn_norm"], cfg),
                                        positions, inv_freq, attend, 2 * l, state)
            u = h + o
        with jax.named_scope("smg.mlp"):
            x0 = _norm(u, first["mlp_norm"], cfg)
        m, c = shortcut(x0, layer, experts, l, cfg, live, moe_impl)
        with jax.named_scope("smg.mlp"):
            v = u + _mlp(first, x0, cfg)
        with jax.named_scope("smg.mla.block"):
            o, state = latent_attention(second, cfg, _norm(v, second["attn_norm"], cfg),
                                        positions, inv_freq, attend, 2 * l + 1, state)
            w = v + o
        z = _mlp_residual(w, second, cfg)
        with jax.named_scope("smg.scmoe.join"):  # the shortcut's branch meets the layer
            out = (z.astype(jnp.float32) + m).astype(h.dtype)
        return (out, state, merge_counts(counts, c)), None

    (h, state, counts), _ = jax.lax.scan(
        block, (h, state, jnp.zeros((len(ROUTED_COUNTS),), jnp.int32)),
        (params["layers"], jnp.arange(cfg.num_layers)))
    return h, state, counts


forward_prefill = partial(pangu_moe.forward_prefill, stack=_stack)
forward_prefill_batched = partial(pangu_moe.forward_prefill_batched, stack=_stack)
forward_decode_horizon = partial(pangu_moe.forward_decode_horizon, stack=_stack)
