"""Kimi-Linear: Kimi Delta Attention layers beside unrotated latent attention,
routed experts behind a dense first layer.

``model_type: kimi_linear`` (``moonshotai/Kimi-Linear-48B-A3B-Instruct``).
``layer_types`` names every layer ``kda`` or ``full_attention``: periods of
KDA layers closed by one latent layer (three and one as published; the last
period of the 27 layers has two).  Every layer is ``h <- h + mixer(N1(h))``,
``h <- h + ffn(N2(h))`` with RMSNorms, then a final norm and the head (untied).
No matrix has a bias.  ``u`` is a sublayer's normed input.

*KDA* (``H`` heads, keys ``dk`` and values ``dv``, rank ``r = dk``):

- ``q', k', v' = silu(conv(W_q u)), silu(conv(W_k u)), silu(conv(W_v u))``
  (the convolution causal, depthwise, ``linear_conv_kernel_dim`` taps, no
  bias); a head: ``q = l2norm(q') / sqrt(dk)``, ``k = l2norm(k')``;
- **the decay is a vector a head**: ``g = -exp(A_log[h]) softplus(W_f2 (W_f1
  u) + dt_bias)`` in ``[H, dk]``, ``a = exp(g)``; ``beta = sigmoid(W_b u)``
  in ``[H]``;
- state ``S`` ``[dk, dv]`` a head and sequence, zero at the start, float32:
  ``S' = a[:, None] S``, ``w = beta (v - S'^T k)``, ``S = S' + k w^T``, ``o =
  S^T q`` (``ops.linear_attention``: the delta rule with the decay a number a
  key channel);
- ``out = W_o (RMSNorm_head(o) sigmoid(W_g2 (W_g1 u)))``, the norm over each
  head's ``dv`` lanes with one learned weight a lane.

*Latent attention, unrotated*: ``[q_n | q_r]_h = W_q u`` (one step, no low
rank); ``[c | k_r] = W_dkv u``, ``c <- RMSNorm(c)``; ``[k_n | v]_h = W_ukv,h
c``; scores ``(q_n . k_n + q_r . k_r) / sqrt(dn + dr)``, causal softmax,
``W_o``.  **Nothing is rotated at any position** (``cfg.rope_theta`` 0: order
reaches these layers through the KDA layers); the ``dr`` lanes are a key all
heads share, as in ``models/pangu_moe.py``, only still.  What a token leaves
in the cache is ``[c | k_r]`` (``ops/latent_attention.py``); prefill runs
expanded and decode absorbed, and both are ``pangu_moe``'s forwards given
this module's stack (as ``models/longcat_flash.py`` gives its own).

*Feed-forward*: the first ``first_k_dense_replace`` layers a SwiGLU MLP of
``intermediate_size``; every other layer float32 sigmoid scores over all
``num_experts`` outputs, the ``top_k`` largest of score plus a selection bias
an expert, weights the scores alone, renormalised, times
``routed_scaling_factor``; an expert ``W_down (silu(W_gate u) W_up u)`` of
``moe_intermediate_size``; one shared expert of that shape on every token,
unweighted.  **Experts held**: this process holds the routed experts
``cfg.held_experts`` (all, or one chip's share of a deployment); the router
keeps its width, a pick on an expert held elsewhere adds nothing and nothing
stands in for the absent chips (``ops/moe.py``).

**What a sequence holds.**  Latent pages for the latent layers only (one
buffer, no V), and for every KDA layer one slot of state, ``[dk, H * dv]``
float32, and the convolution's last inputs in the model's dtype.  Slot 0 is
the garbage slot.

**Departures from the equations above**: none in arithmetic.  ``W_q``, ``W_k``
and ``W_v`` of a KDA layer are one matrix; the state is held transposed with
the heads fused; prefill runs the recurrence in chunks, decode in the kernel
``smg.kda.decode`` where it fits (``decode_step``).  The stack is the leading
periods written out (those with a dense layer, and any that does not repeat)
and one ``lax.scan`` over the run of equal periods behind them (``layout``).

**What this module serves**: ``forward_prefill``, ``forward_prefill_batched``
and ``forward_decode_horizon`` on one device, and ``forward_train`` (the dense
causal forward, from zero state).  Everything in ``SERVING_LIMITS`` is refused
at start, not run wrong.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from smg_tpu.models import pangu_moe
from smg_tpu.models.config import ModelConfig
from smg_tpu.models.llama import _mlp, _norm, embed_tokens, unembed
from smg_tpu.models.nemotron_h import EMBED_STD, _attention_size, route_lanes
from smg_tpu.models.olmo_hybrid import conv_channels, split_qkv
from smg_tpu.models.pangu_moe import (  # noqa: F401  (the runner's, and the layer's)
    ROUTED_COUNTS,
    cache_lanes,
    merge_counts,
    shared_expert,
)
from smg_tpu.ops import moe
from smg_tpu.ops.latent_attention import latent_attention_prefill
from smg_tpu.ops.linear_attention import (
    heads_to_pool,
    kda_causal_conv,
    kda_chunked,
    kda_conv_decode,
    kda_step,
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

# RANDOM weights (``init_params``), drawn in the form ``models/nemotron_h.py``
# draws its own and for its reasons (its comment has them): sizes in units of
# the embedding's lanes, every input projection normal at 1 / sqrt(fan-in),
# every output projection so that its part adds a stated number of units to
# the stream, the routers reading lanes that only the embedding writes,
# through signs, so that a bfloat16 program and a float32 reference pick the
# same experts.  ``DRAW`` has every part at one unit; whoever compares random
# weights against a reference says otherwise in the configuration
# (``random_weights`` in a config.json, ``ModelConfig.random_init``).  What is
# this module's own: **the decays spread over (0, 1) channel by channel**
# (``exp(A_log)`` uniform in (1, 16) a head, the softplus of ``dt_bias``
# log-uniform in (``dt_min``, ``dt_max``) a channel, the decay's low-rank
# input ``decay_gain`` of a unit), so that a head's channels forget at rates
# two orders of magnitude apart and the mean of a head's decays is not the
# model; the latent attention's query ``score_std`` times unit size, a third
# of the scores' variance from the key the heads share; and **the routers read
# the last ``ROUTER_LANES`` of the embedding's own lanes only**, so that the
# scores lie on thirteen coarse levels: top 8 of 256 then takes the five
# experts or so above the boundary level and three of the fourteen on it, and
# which three is the selection bias's to say.  With all 128 lanes a level
# holds three experts, the bias decides one pick in two layers, and one token
# in ten picks the same held experts in every layer with the bias and without
# it (counted over the cut's 20,480 tokens): a comparison that drops the bias
# reads nothing on such a token.  At 12 lanes no token of them does, and a
# token's held picks differ in 11 places of its 11 layers' on average.  A
# checkpoint has none of this.
DRAW = {"kda_out": 1.0, "attn_out": 1.0, "dense_out": 1.0, "routed_out": 1.0,
        "shared_out": 1.0, "score_std": 1.0, "dt_min": 0.001, "dt_max": 0.1,
        "decay_gain": 0.5}
ROUTER_LANES = 12
# The routers' logits have deviation ``ROUTER_GAIN`` and the selection bias is
# ``SELECT_BIAS_LEVELS`` of the gap between two levels' scores at the last
# pick: half and a third of what ``models/nemotron_h.py`` draws (2.0 and 1/6).
# The bias must order a level and cross none, **in both precisions**, and a
# level's gap is a gap of scores: at logits of 4.6 and 6.1, where a stream a
# quarter smaller than ``_stream_sizes`` foresees puts the boundary levels under
# a gain of 2, the sigmoid is nearly flat, the gap is 0.008 where the drawing
# counted on 0.026, a bias of deviation 0.0044 carries an expert across it, and
# where two such sums fall within the 4e-4 by which the two programs' levels
# differ (each rounds a token's magnitude its own way) they pick otherwise: 12
# of a sequence's 7,744 token-layers did on the chip, each a whole pick of 1.5
# units (``scripts/time_kimi_linear.py --picks``; PERF.md, Findings, PR 50).
# Under a gain of 1 the levels about the last pick lie where the sigmoid is
# steep whatever a stream's size within a half of the forecast, and a bias a
# third as large is twelve of its deviations from crossing.
ROUTER_GAIN = 1.0
SELECT_BIAS_LEVELS = 1.0 / 18.0
# root mean squares under unit normal inputs: ``silu(a) b``, and a unit vector
# times a sigmoid of a unit normal
_SWIGLU_RMS = 0.596
_GATED_RMS = 0.541

SERVING_LIMITS = {
    "speculative": "kimi_linear has no verify block: a rejected draft would have to take "
                   "its row out of the KDA layers' recurrent state again",
    "lora": "kimi_linear has no LoRA deltas on its projections",
    "embeddings": "kimi_linear has no embedding forward",
    "mesh": "kimi_linear runs on one device: neither the state pool and its kernel nor "
            "the experts' exchange between chips is partitioned over a mesh",
    "kv_transfer": "kimi_linear cannot export a sequence: its recurrent state is not in "
                   "the pages, and the transfer carries K and V buffers where its cache "
                   "has one latent buffer",
    "checkpoint": "kimi_linear has no safetensors key map yet: it is served with seeded "
                  "random weights (--model-preset), not from --model-path",
}


# A single cold row pads to an octave of the prefill ladder, as a group does,
# and not to a rung between two (1,536): compiled for a v5e at the published
# widths, the one row of 1,536 tokens is the one shape of the ladder that
# XLA:TPU refuses, for VMEM: it stages the expert layer's gather of 2,048 rows
# through VMEM beside its 7 MB operand (``[1536, 2304]`` bfloat16) and the two
# pass 16 MiB; 1,024, 2,048, 3,072 and 4,096 tokens compile, and so does 1,536
# with a buffer of 1,536 rows (PERF.md, Findings, PR 50; the chip's warm-up
# found it, ``tests/test_tpu_compile_recurrent.py`` keeps it).  The runner
# reads this (``RecurrentModelRunner._prefill_rung``).
OCTAVE_RUNGS_ONLY = True


def drawing(cfg: ModelConfig) -> dict:
    """``DRAW`` with what the configuration sets of it."""
    given = dict(cfg.random_init)
    unknown = sorted(set(given) - set(DRAW))
    if unknown:
        raise ValueError(f"kimi_linear: random_weights names {unknown}, which the drawing "
                         f"does not have ({', '.join(sorted(DRAW))} are set)")
    return {**DRAW, **given}


def router_lanes(hidden: int) -> int:
    """The lanes at the stream's end that the routers read: ``ROUTER_LANES`` of
    the ``route_lanes`` that only the embedding writes."""
    return min(ROUTER_LANES, route_lanes(hidden))


def select_bias_std(cfg: ModelConfig) -> float:
    """``SELECT_BIAS_LEVELS`` of the gap between the scores of two neighbouring
    levels of a router's logits, at the level the last pick falls on
    (``models/nemotron_h.select_bias_std`` at this module's ``router_lanes``)."""
    from statistics import NormalDist

    at = ROUTER_GAIN * NormalDist().inv_cdf(1.0 - cfg.num_experts_per_tok / cfg.num_experts)
    slope = math.exp(-at) / (1.0 + math.exp(-at)) ** 2  # the sigmoid's, at the last pick
    return SELECT_BIAS_LEVELS * slope * 2.0 * ROUTER_GAIN / math.sqrt(router_lanes(cfg.hidden_size))


def layout(cfg: ModelConfig) -> dict:
    """The stack as this module runs it: ``periods`` of ``(first layer, KDA
    layers)``, each closed by one latent layer, and ``scan = (first, last)``,
    the run of equal periods behind the dense layers that is one ``lax.scan``
    (None where no two periods repeat).  ValueError with a sentence for a
    stack in any other order."""
    kinds = cfg.layer_types or ()
    if set(kinds) - {"kda", "full_attention"}:
        raise ValueError(f"kimi_linear: unknown kinds of layer "
                         f"{sorted(set(kinds) - {'kda', 'full_attention'})}")
    if not kinds or kinds[-1] != "full_attention":
        raise ValueError("kimi_linear: the stack must end on a full_attention (latent) layer: "
                         "KDA layers behind the last one belong to no period this program runs")
    if not 0 <= cfg.first_k_dense_replace <= len(kinds):
        raise ValueError(f"kimi_linear: first_k_dense_replace {cfg.first_k_dense_replace} "
                         f"of {len(kinds)} layers")
    periods, first = [], 0
    for l, kind in enumerate(kinds):
        if kind == "full_attention":
            periods.append((first, l - first))
            first = l + 1
    # the longest run of equal periods that holds no dense layer
    best = None
    i = 0
    while i < len(periods):
        j = i
        while j + 1 < len(periods) and periods[j + 1][1] == periods[i][1]:
            j += 1
        lo = next((p for p in range(i, j + 1) if periods[p][0] >= cfg.first_k_dense_replace), None)
        if lo is not None and j > lo and (best is None or j - lo > best[1] - best[0]):
            best = (lo, j)
        i = j + 1
    return {"periods": periods, "scan": best}


def count(cfg: ModelConfig, kind: str) -> int:
    return sum(1 for t in cfg.layer_types if t == kind)


def state_shapes(cfg: ModelConfig, slots: int) -> tuple[tuple, tuple]:
    """Shapes of the two state pools for ``slots`` slots (the garbage slot
    included): recurrent state float32, convolution tail in the model's dtype,
    a slot's tail as whole tiles (``ops.linear_attention.tail_block``)."""
    Lk = count(cfg, "kda")
    H, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    return ((Lk, slots, dk, H * dv),
            (Lk, slots, *tail_block(conv_channels(cfg), cfg.linear_conv_kernel_dim, cfg.dtype)))


def prefill_workspace_bytes(cfg: ModelConfig, tokens: int, dtype: str) -> int:
    """Bytes a prefill of ``tokens`` tokens holds beside its arguments, from
    shapes and on the high side: a KDA layer's projections in the model's
    dtype, in float32 the convolution's input and output, ``q``, ``k``, ``v``
    and the cumulated decay as they come and as the chunks hold them, with the
    scan's operands; the ``[SUB, SUB, dk]`` decay weights of the diagonal
    blocks of the chunks made at a time (the compiled program reduces them
    where it makes them: room, 192 MiB at the benchmark's cut, over the 1,958
    MiB its largest launch holds; ``tests/test_tpu_compile_recurrent.py``);
    and what ``pangu_moe``'s count has for a latent layer and an expert layer,
    which this model's are."""
    from smg_tpu.ops.linear_attention import _KDA_CHUNKS_AT_ONCE, CHUNK, SUB

    H, dk = cfg.linear_num_heads, cfg.linear_key_head_dim
    C = conv_channels(cfg)
    kda = tokens * (C * jnp.dtype(dtype).itemsize + 4 * (3 * C + 8 * H * dk))
    weights = 3 * _KDA_CHUNKS_AT_ONCE * H * CHUNK * SUB * dk * 4
    return kda + weights + pangu_moe.prefill_workspace_bytes(cfg, tokens, dtype)


def decode_step(cfg: ModelConfig) -> dict:
    """The KDA layers' decode step, for the runner: the name it goes by in
    ``loads()``, the forwards' keyword that picks its form, what the layers
    are called, and whether the kernel's blocks fit this shape."""
    from smg_tpu.ops.pallas import linattn_decode

    return {"name": "kda_decode", "arg": "kda_impl", "layers": "KDA",
            "kernel_fits": linattn_decode.supported(
                cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim)}


def _stream_sizes(cfg: ModelConfig) -> list[float]:
    """The residual stream's expected size before every expert layer's
    feed-forward part under ``init_params``' drawing, in units of the
    embedding's."""
    d = drawing(cfg)
    size2, sizes = 1.0, []
    for l, kind in enumerate(cfg.layer_types):
        size2 += d["kda_out" if kind == "kda" else "attn_out"] ** 2
        if l < cfg.first_k_dense_replace:
            size2 += d["dense_out"] ** 2
        else:
            sizes.append(math.sqrt(size2))
            size2 += d["routed_out"] ** 2 + d["shared_out"] ** 2
    return sizes


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random weights (tests, benchmarks), drawn as the comment above says at
    the sizes ``drawing(cfg)`` gives; norm weights 1."""
    E, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rkv = cfg.kv_lora_rank
    Hl, dk, dvl = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    r, C = dk, conv_channels(cfg)
    Fm, X, Xh = cfg.moe_intermediate_size, cfg.num_experts, cfg.held_experts[1]
    Lk, La, Ld = count(cfg, "kda"), count(cfg, "full_attention"), cfg.first_k_dense_replace
    Le = cfg.num_layers - Ld
    RL, RR = route_lanes(E), router_lanes(E)
    d = drawing(cfg)
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 64))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(dtype)

    def quiet(w):
        """An output projection [..., E] that writes nothing to the routers' lanes."""
        return w.at[..., E - RL:].set(0)

    ones = lambda *shape: jnp.ones(shape, dtype)
    signs = jnp.where(jax.random.bernoulli(next(ks), 0.5, (V, RL)), EMBED_STD, -EMBED_STD)
    embed = jnp.concatenate([normal((V, E - RL), EMBED_STD), signs.astype(dtype)], axis=1)
    dt = jnp.exp(jax.random.uniform(next(ks), (Lk, Hl * dk), jnp.float32,
                                    math.log(d["dt_min"]), math.log(d["dt_max"])))
    kda = {
        "norm": ones(Lk, E),
        "w_qkv": normal((Lk, E, C), E ** -0.5),
        "conv": jax.random.uniform(next(ks), (Lk, cfg.linear_conv_kernel_dim, C), jnp.float32,
                                   -0.5, 0.5).astype(dtype),
        "w_f1": normal((Lk, E, r), E ** -0.5),
        "w_f2": normal((Lk, r, Hl * dk), d["decay_gain"] * r ** -0.5),
        "A_log": jnp.log(jax.random.uniform(next(ks), (Lk, Hl), jnp.float32, 1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
        "w_b": normal((Lk, E, Hl), E ** -0.5),
        "w_g1": normal((Lk, E, r), E ** -0.5),
        "w_g2": normal((Lk, r, Hl * dvl), r ** -0.5),
        "o_norm": ones(Lk, dvl),
        "wo": quiet(normal((Lk, Hl * dvl, E),
                           d["kda_out"] * EMBED_STD / _GATED_RMS * (Hl * dvl) ** -0.5)),
    }
    mla = {
        "norm": ones(La, E),
        # stored as ``models/pangu_moe.py`` stores the queries' up-projection
        # and the latent's two, and for its reasons (what XLA:TPU makes of a
        # decode column)
        "w_q_nope": normal((La, H * dn, E), d["score_std"] * E ** -0.5),
        "w_q_pe": normal((La, dr, H, E), d["score_std"] * E ** -0.5),
        "w_dkv": normal((La, E, rkv), E ** -0.5), "kv_norm": ones(La, rkv),
        "w_dk_pe": normal((La, E, dr), E ** -0.5),
        "w_uk": normal((La, H, rkv, dn), rkv ** -0.5),
        "w_uv": normal((La, H, rkv, dv), rkv ** -0.5),
        "wo": quiet(normal((La, H * dv, E), d["attn_out"] * EMBED_STD
                           / _attention_size(d["score_std"]) * (H * dv) ** -0.5)),
    }
    dense = {
        "norm": ones(Ld, E),
        "w_gate": normal((Ld, E, F), E ** -0.5), "w_up": normal((Ld, E, F), E ** -0.5),
        "w_down": quiet(normal((Ld, F, E), d["dense_out"] * EMBED_STD / _SWIGLU_RMS * F ** -0.5)),
    }
    # a token's picks on held experts weigh ``scale / top_k`` each, near enough
    held_picks = max(cfg.num_experts_per_tok * Xh / X, 1.0)
    routed = (cfg.routed_scaling_factor / cfg.num_experts_per_tok) * math.sqrt(held_picks) \
        * _SWIGLU_RMS
    louder = jnp.asarray(_stream_sizes(cfg), jnp.float32)[:, None, None]
    router = jnp.zeros((Le, E, X), jnp.float32).at[:, E - RR:].set(
        jnp.where(jax.random.bernoulli(next(ks), 0.5, (Le, RR, X)), 1.0, -1.0) * louder
        * ROUTER_GAIN * RR ** -0.5)
    moe_p = {
        "norm": ones(Le, E),
        "router": router.astype(dtype),
        "select_bias": jax.random.normal(next(ks), (Le, X), jnp.float32) * select_bias_std(cfg),
        "ws_gate": normal((Le, E, Fm), E ** -0.5), "ws_up": normal((Le, E, Fm), E ** -0.5),
        "ws_down": quiet(normal((Le, Fm, E),
                                d["shared_out"] * EMBED_STD / _SWIGLU_RMS * Fm ** -0.5)),
    }
    experts = {
        "w_gate": normal((Le, Xh, E, Fm), E ** -0.5), "w_up": normal((Le, Xh, E, Fm), E ** -0.5),
        "w_down": quiet(normal((Le, Xh, Fm, E), d["routed_out"] * EMBED_STD / routed * Fm ** -0.5)),
    }
    return {
        "embed": embed,
        "kda": kda,
        "mla": mla,
        "dense": dense,
        "moe": moe_p,
        "experts": experts,
        "final_norm": ones(E),
        "lm_head": normal((E, V), 0.02),
    }


def logical_axes(cfg: ModelConfig) -> Params:
    """Every array replicated: this module runs on one device."""
    return jax.tree.map(lambda x: (None,) * x.ndim,
                        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))


# --------------------------------------------------------------------------
# the layers.  What differs between prefill and decode is how a layer reaches
# what its sequence holds, so each takes that as a function: ``mix(qkv, g,
# beta)`` runs the convolution and the recurrence over the KDA layer's slot,
# ``attend(q_nope, q_pe, entry, layer, l, state)`` (``pangu_moe``'s) writes and
# reads the latent pages.  Both return their result and whatever they changed.


@jax.named_scope("smg.kda.layer")
def kda_layer(h, layer: Params, cfg: ModelConfig, mix):
    """``h`` [..., E].  ``mix(qkv [..., C], g [..., H, dk], beta [..., H])``
    (``g`` the log of the decay) returns the recurrence's outputs ``o``
    [..., H, dv] (float32) and its new state.  Returns ``(h, new state)``."""
    f32 = jnp.float32
    H, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    u = _norm(h, layer["norm"], cfg)
    with jax.named_scope("smg.kda.proj"):
        qkv = jnp.einsum("...e,ec->...c", u, layer["w_qkv"])
        # the decay feeds an exponential summed over the sequence, and beta
        # weighs every write: their columns are accumulated and kept in float32
        f = jnp.einsum("...r,rc->...c", jnp.einsum("...e,er->...r", u, layer["w_f1"]),
                       layer["w_f2"], preferred_element_type=f32)
        b = jnp.einsum("...e,eh->...h", u, layer["w_b"], preferred_element_type=f32)
        gate = jnp.einsum("...r,rc->...c", jnp.einsum("...e,er->...r", u, layer["w_g1"]),
                          layer["w_g2"])
    with jax.named_scope("smg.kda.gates"):
        dt = jax.nn.softplus(f + layer["dt_bias"].astype(f32)).reshape(*f.shape[:-1], H, dk)
        g = -jnp.exp(layer["A_log"].astype(f32))[:, None] * dt
        beta = jax.nn.sigmoid(b)
    o, state = mix(qkv, g, beta)
    with jax.named_scope("smg.kda.gate_norm"):
        o = rms_norm(o, layer["o_norm"], cfg.rms_norm_eps) * jax.nn.sigmoid(
            gate.astype(f32).reshape(*gate.shape[:-1], H, dv))
    with jax.named_scope("smg.kda.out_proj"):
        y = jnp.einsum("...f,fe->...e", o.astype(h.dtype).reshape(*h.shape[:-1], -1), layer["wo"])
    return h + y, state


def _latent_qkv(layer: Params, cfg: ModelConfig, x, positions, inv_freq):
    """Queries ``q_nope`` [..., H, dn], ``q_pe`` [..., H, dr] and the cache
    entry ``[c | k_r | 0]`` [..., W] of the tokens ``x`` [..., E]: the query in
    one step, nothing rotated (``cfg.rope_theta`` 0; a number there rotates
    ``q_pe`` and ``k_r`` at their positions, which is another model: the
    benchmark's control serves it so, and must hear it)."""
    dn, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("smg.mla.q"):
        q_nope = jnp.einsum("...e,fe->...f", x, layer["w_q_nope"])
        q_nope = q_nope.reshape(*q_nope.shape[:-1], cfg.num_heads, dn)
        q_pe = jnp.einsum("...e,dhe->...hd", x, layer["w_q_pe"])
    with jax.named_scope("smg.mla.kv"):
        c = rms_norm(jnp.einsum("...e,ec->...c", x, layer["w_dkv"]), layer["kv_norm"],
                     cfg.rms_norm_eps)
        k_pe = jnp.einsum("...e,ed->...d", x, layer["w_dk_pe"])
        if cfg.rope_theta:
            q_pe = apply_rope(q_pe, positions, inv_freq)
            k_pe = apply_rope(k_pe[..., None, :], positions, inv_freq)[..., 0, :]
        pad = jnp.zeros((*c.shape[:-1], cache_lanes(cfg) - rkv - k_pe.shape[-1]), c.dtype)
        entry = jnp.concatenate([c, k_pe, pad], axis=-1)
    return q_nope, q_pe, entry


@jax.named_scope("smg.mla.block")
def latent_layer(h, layer: Params, cfg: ModelConfig, positions, inv_freq, attend, l, state):
    """One latent attention sublayer as cache layer ``l``; returns ``(h,
    state)`` with the forward's ``state`` as ``attend`` changed it."""
    x = _norm(h, layer["norm"], cfg)
    q_nope, q_pe, entry = _latent_qkv(layer, cfg, x, positions, inv_freq)
    out, state = attend(q_nope, q_pe, entry, layer, l, state)
    o = jnp.einsum("...f,fe->...e", out.astype(x.dtype).reshape(*x.shape[:-1], -1), layer["wo"])
    return h + o, state


@jax.named_scope("smg.mlp")
def dense_layer(h, layer: Params, cfg: ModelConfig):
    return h + _mlp(layer, _norm(h, layer["norm"], cfg), cfg)


@jax.named_scope("smg.moe.residual")
def moe_layer(h, layer: Params, experts: Params, i, cfg: ModelConfig, live, impl: str):
    """``h + sum_i w_i E_i(u) + E_shared(u)`` over the held experts, ``u =
    RMSNorm(h)``.  ``experts`` holds the routed experts' weights of all expert
    layers, ``i`` picks this layer's.  ``live`` [...] marks real tokens: a
    padded one picks no expert.  Returns ``h`` and the layer's counts
    (``ROUTED_COUNTS``)."""
    u = _norm(h, layer["norm"], cfg)
    flat = u.reshape(-1, u.shape[-1])
    routing = moe.route(flat, layer["router"], top_k=cfg.num_experts_per_tok,
                        scoring=cfg.moe_scoring, norm_topk=cfg.norm_topk_prob,
                        scale=cfg.routed_scaling_factor, select_bias=layer["select_bias"])
    alive = live.reshape(-1)
    routing = routing._replace(experts=jnp.where(alive[:, None], routing.experts, -1))
    y, (rows, hit) = moe.expert_layer(flat, routing, experts["w_gate"], experts["w_up"],
                                      experts["w_down"], cfg.held_experts, impl, layer=i)
    o = (y + shared_expert(layer, flat, cfg).astype(jnp.float32)).astype(h.dtype).reshape(h.shape)
    picks = jnp.sum(alive).astype(jnp.int32) * cfg.num_experts_per_tok
    return h + o, jnp.stack([picks, rows, hit, rows])


def _at(tree: Params, i, scope: str):
    """Layer ``i`` of a stack, taken out under the layer's own ``scope``: a
    static slice for a whole number, and for a traced one a dynamic slice
    that feeds its product directly."""
    with jax.named_scope(scope):
        if isinstance(i, int):
            return jax.tree.map(lambda x: x[i], tree)
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), tree)


def stack_with(cfg: ModelConfig, kda, carry):
    """The ``stack`` that ``pangu_moe``'s forwards take, for this model:
    ``stack(params, cfg, inv_freq, h, positions, live, state, attend,
    moe_impl) -> (h, state, counts)``.  ``kda(h, layer, li, carry)`` runs one
    KDA layer over the state pools ``carry`` and returns ``(h, carry)``; the
    forward's ``state`` (the latent cache, or the side buffer) goes through
    the latent layers, and comes back as ``(state, *carry)``."""
    plan = layout(cfg)
    Ld = cfg.first_k_dense_replace

    def stack(params, _cfg, inv_freq, h, positions, live, state, attend, moe_impl):
        experts = params["experts"]

        def ffn(h, counts, l):
            if isinstance(l, int) and l < Ld:
                return dense_layer(h, _at(params["dense"], l, "smg.mlp"), cfg), counts
            h, c = moe_layer(h, _at(params["moe"], l - Ld, "smg.moe.residual"), experts,
                             l - Ld, cfg, live, moe_impl)
            return h, merge_counts(counts, c)

        def period(c, first, k0, p, n):
            """Layers ``first .. first + n`` (``n`` KDA layers and the latent
            layer ``p``); ``k0`` the first's index among the KDA layers."""
            h, state, pools, counts = c
            for i in range(n):
                h, pools = kda(h, _at(params["kda"], k0 + i, "smg.kda.layer"), k0 + i, pools)
                h, counts = ffn(h, counts, first + i)
            h, state = latent_layer(h, _at(params["mla"], p, "smg.mla.block"), cfg, positions,
                                    inv_freq, attend, p, state)
            h, counts = ffn(h, counts, first + n)
            return h, state, pools, counts

        def run(c, first, k0, p, n, periods):
            """``periods`` equal periods from period ``p`` on as one scan:
            period ``p + s`` starts ``s`` whole periods further in every stack."""
            body = lambda c, s: (period(c, first + s * (n + 1), k0 + s * n, p + s, n), None)
            return jax.lax.scan(body, c, jnp.arange(periods))[0]

        c = (h, state, carry, jnp.zeros((len(ROUTED_COUNTS),), jnp.int32))
        lo, hi = plan["scan"] or (len(plan["periods"]), -1)
        k0 = 0
        for p, (first, n) in enumerate(plan["periods"]):
            if p == lo:
                c = run(c, first, k0, p, n, hi - lo + 1)
            elif not lo < p <= hi:
                c = period(c, first, k0, p, n)
            k0 += n
        h, state, pools, counts = c
        return h, (state, *pools), counts

    return stack


# --------------------------------------------------------------------------
# prefill


def _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache, page_tables,
             s_pool, c_pool, slots, no_ctx: bool, attn_impl: str, moe_impl: str):
    """Solo and grouped prefill: ``tokens`` [G, T], one row a sequence.  The
    latent layers are ``pangu_moe._prefill``'s (expanded; the chunk's entries
    written first).  The recurrence runs in its chunked form from the state in
    ``slots`` (zero for a row that starts its sequence); a padded token has
    ``beta`` 0 and ``g`` 0 and stays out of the convolution's tail, a padded
    row names the garbage slot."""
    G, T = tokens.shape
    H = cfg.linear_num_heads
    with jax.named_scope("smg.prefill.land"):
        real = jnp.arange(T)[None, :] < t_reals[:, None]
        keep = (prefix_lens > 0).astype(jnp.float32)  # 0 where the sequence starts here
    taps = cfg.linear_conv_kernel_dim - 1

    def kda(h, layer, li, pools):
        s_pool, c_pool = pools

        def mix(qkv, g, beta):
            tail = read_tail(c_pool, li, slots, taps)  # [G, K-1, C]
            y, tail = kda_causal_conv(qkv, tail * keep[:, None, None].astype(tail.dtype),
                                      layer["conv"], t_reals)
            q, k, v = split_qkv(y, cfg)
            S0 = pool_to_heads(read_state(s_pool, li, slots), H) * keep[:, None, None, None]
            o, S = kda_chunked(q, k, v, jnp.where(real[..., None, None], g, 0.0),
                               jnp.where(real[..., None], beta, 0.0), S0)
            return o, (write_state(s_pool, li, slots, heads_to_pool(S)),
                       write_tail(c_pool, li, slots, tail))

        return kda_layer(h, layer, cfg, mix)

    logits, (k_cache, s_pool, c_pool) = pangu_moe._prefill(
        params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache, page_tables, no_ctx,
        moe_impl, stack_with(cfg, kda, (s_pool, c_pool)), attn_impl)
    return logits, k_cache, s_pool, c_pool


def forward_prefill(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,  # read only where ``cfg.rope_theta`` is set: not by this model
    tokens: jnp.ndarray,  # [T] padded to bucket
    prefix_len: jnp.ndarray,  # scalar: tokens of the sequence before this chunk
    t_real: jnp.ndarray,  # scalar: valid new tokens (<= T)
    k_cache: jnp.ndarray,  # [latent layers, P, ps, W]: the latent entries
    v_cache: jnp.ndarray,  # of zero size: this cache has no V buffer
    page_table: jnp.ndarray,  # [mp]
    s_pool: jnp.ndarray,  # [KDA layers, slots, dk, H*dv] float32
    c_pool: jnp.ndarray,  # [KDA layers, slots, R, W]: ``tail_block``
    slot: jnp.ndarray,  # scalar: the sequence's state slot
    attn_impl: str = "xla",  # the solo chunk attends in XLA's form; kept for the runner
    moe_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret"
):
    """One chunk of one sequence, behind the prefix its pages and its slot
    hold.  Returns (last_token_logits [V], k_cache, v_cache, s_pool, c_pool)."""
    logits, k_cache, s_pool, c_pool = _prefill(
        params, cfg, inv_freq, tokens[None], prefix_len[None], t_real[None], k_cache,
        page_table[None], s_pool, c_pool, slot[None], False, "xla", moe_impl)
    return logits[0], k_cache, v_cache, s_pool, c_pool


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
    attn_impl: str = "xla",  # "pallas" | "pallas_interpret": the kernel, where ``no_ctx``
    moe_impl: str = "xla",
):
    """Several sequences' chunks in one call.  Returns (logits [G, V],
    k_cache, v_cache, s_pool, c_pool)."""
    logits, k_cache, s_pool, c_pool = _prefill(
        params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache, page_tables, s_pool,
        c_pool, slots, no_ctx, attn_impl if no_ctx else "xla", moe_impl)
    return logits, k_cache, v_cache, s_pool, c_pool


def forward_train(params: Params, cfg: ModelConfig, inv_freq: jnp.ndarray,
                  tokens: jnp.ndarray,  # [B, T]
                  moe_impl: str = "xla") -> jnp.ndarray:
    """Dense causal forward from zero state, no cache: logits [B, T, V]."""
    G, T = tokens.shape
    H, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (G, T))
    whole = jnp.full((G,), T, jnp.int32)

    def kda(h, layer, _li, pools):
        def mix(qkv, g, beta):
            tail = jnp.zeros((G, cfg.linear_conv_kernel_dim - 1, qkv.shape[-1]), qkv.dtype)
            y, _ = kda_causal_conv(qkv, tail, layer["conv"], whole)
            o, _ = kda_chunked(*split_qkv(y, cfg), g, beta, jnp.zeros((G, H, dk, dv), jnp.float32))
            return o, pools

        return kda_layer(h, layer, cfg, mix)

    def attend(q_nope, q_pe, entry, layer, _l, state):
        c, k_pe = entry[..., :rkv], entry[..., rkv:rkv + dr]
        k_nope = jnp.einsum("gsc,hcd->gshd", c, layer["w_uk"])
        v = jnp.einsum("gsc,hcd->gshd", c, layer["w_uv"])
        return latent_attention_prefill(q_nope, q_pe, k_nope, k_pe, v, pos, whole,
                                        pangu_moe._scale(cfg)), state

    h, _, _ = stack_with(cfg, kda, ())(
        params, cfg, inv_freq, embed_tokens(params, cfg, tokens), pos,
        jnp.ones((G, T), jnp.bool_), None, attend, moe_impl)
    return unembed(params, cfg, h)


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
    k_cache: jnp.ndarray,  # [latent layers, P, ps, W] read-only during the frame
    v_cache: jnp.ndarray,  # of zero size
    page_tables: jnp.ndarray,  # [B, mp]
    side: jnp.ndarray,  # [latent layers, B, N, W] the frame's one side buffer
    s_pool: jnp.ndarray,
    c_pool: jnp.ndarray,
    slots: jnp.ndarray,  # [B]; a padded row names slot 0
    runs: jnp.ndarray,  # [B] bool: the lane runs this column
    attn_impl: str = "xla",
    kda_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret"
    moe_impl: str = "xla",
):
    """One decode column.  The latent layers read the frozen cache and the
    side buffer, absorbed, as in ``models/pangu_moe.py``; the KDA layers
    advance the state in their slots by one token, in place.  A lane with
    ``runs`` false (a padded row) gets a decay of 1 in every channel and
    ``beta`` 0 and keeps its convolution tail, so its slot is left bit for
    bit, and picks no expert.  Returns (logits [B, V], side, s_pool, c_pool,
    counts)."""
    def kda(h, layer, li, pools):
        s_pool, c_pool = pools

        def mix(qkv, g, beta):
            y, c_new = kda_conv_decode(c_pool, li, slots, runs, qkv, layer["conv"])
            q, k, v = split_qkv(y, cfg)
            alpha = jnp.where(runs[:, None, None], jnp.exp(g), 1.0)
            beta = jnp.where(runs[:, None], beta, 0.0)
            if kda_impl.startswith("pallas"):
                from smg_tpu.ops.pallas.linattn_decode import kda_decode

                o, s_new = kda_decode(s_pool, li, slots, q, k, v, alpha, beta,
                                      interpret=(kda_impl == "pallas_interpret"))
            else:
                o, s_new = kda_step(s_pool, li, slots, q, k, v, alpha, beta)
            return o, (s_new, c_new)

        return kda_layer(h, layer, cfg, mix)

    logits, (side, s_pool, c_pool), counts = pangu_moe.forward_decode_horizon(
        params, cfg, inv_freq, tokens, positions, entry_positions, step_idx, k_cache,
        page_tables, side, runs, attn_impl=attn_impl, moe_impl=moe_impl,
        stack=stack_with(cfg, kda, (s_pool, c_pool)))
    return logits, side, s_pool, c_pool, counts
