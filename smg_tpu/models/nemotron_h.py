"""Nemotron-H (Nemotron 3): one mixer or one feed-forward part a layer, in any
order a pattern string gives.

``model_type: nemotron_h`` (``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``).
``hybrid_override_pattern`` has a letter a layer; layer ``l`` is ``h <- h +
part_l(RMSNorm_l(h))`` with the one part its letter names, then a final norm and
the head (untied).  No matrix has a bias.  The parameters are stacked a kind of
layer and the stack is written out a layer at a time (``_stack``), so a pattern
need not repeat a period.

**The three parts**, for token ``t`` with normed layer input ``u_t``.

*``M``, Mamba-2*: ``H`` heads of ``P``, ``R`` groups of ``H / R`` heads, state
``N``, ``d = H P``:

- ``z, xBC, dt~ = W_z u, W_xBC u, W_dt u`` (``d | d + 2 R N | H``; a checkpoint
  has the three as one matrix ``W_in``, its outputs in this order);
- every channel of ``xBC`` passes a causal depthwise convolution over time of
  ``K`` taps with a bias, and SiLU; ``x, B, C = split(xBC)`` (``x`` [H, P],
  ``B`` and ``C`` [R, N]: head ``h`` reads group ``h // (H / R)``);
- ``dt = softplus(dt~ + dt_bias)``, ``a = exp(-dt exp(A_log))`` a head;
- state ``S`` of ``[P, N]`` a head and sequence, zero at the start, float32:
  ``S_t = a_t S_{t-1} + (dt_t x_t) B_t^T``, ``y_t = S_t C_t + D x_t``;
- ``out = W_out RMSNorm_groups(y silu(z))``: the gate first, then the norm over
  each of the ``R`` groups of ``d / R`` lanes, one learned weight a lane.

*``*``, attention*: ``W_q, W_k, W_v, W_o``, causal softmax over ``q k /
sqrt(head_dim)`` through the paged cache, **no rotary embedding** (the
state-space layers carry order).

*``E``, experts in a latent*: the router scores ``u`` over all
``cfg.num_experts`` outputs (float32 sigmoid; the ``top_k`` largest of score
plus a selection bias an expert, weighed by the scores alone, renormalised,
times ``routed_scaling_factor``); ``c = W_dl u`` (the model's width to
``moe_latent_size``); ``m = sum_i w_i W2_i relu(W1_i c)^2`` over the picks (no
gate matrix); ``out = W_ul m + Ws2 relu(Ws1 u)^2``, the shared expert on the
uncut input.  **Experts held**: this process holds the routed experts
``cfg.held_experts`` (all, or one chip's share of a deployment); the router
keeps its width, ``m`` sums the picks on held experts, in the latent, before
``W_ul``, and a pick on an expert held elsewhere adds nothing (``ops/moe.py``).
The router, both latent projections and the shared expert are whole on every
chip.

**What a sequence holds.**  Pages of K and V for the ``*`` layers only, and for
every ``M`` layer one slot of state, ``[N, H * P]`` float32, and the last ``K -
1`` inputs of the convolution in the model's dtype (``ops/ssm.py`` for the
layouts).  Slot 0 is the garbage slot.

**Departures from the equations above**: none in arithmetic.  The state is
held transposed with the heads fused, prefill runs the recurrence in chunks of
``ssm_chunk_size``, decode in the kernel ``smg.ssm.decode`` where it fits
(``decode_step``); the expert layer sorts its pairs by expert and computes the
held ones (``ops/moe.py``).

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

from smg_tpu.models.config import ModelConfig
from smg_tpu.models.llama import _norm, _write_side, embed_tokens, unembed
from smg_tpu.models.pangu_moe import ROUTED_COUNTS, merge_counts  # noqa: F401  (the runner's)
from smg_tpu.ops import moe, ssm
from smg_tpu.ops.moe import relu2 as _relu2
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
    heads_to_pool,
    pool_to_heads,
    read_state,
    read_tail,
    write_state,
    write_tail,
)

Params = dict[str, Any]

# RANDOM weights (``init_params``).  Sizes are in units of the embedding's
# lanes (``EMBED_STD``); every input projection is normal at 1 / sqrt(fan-in)
# and a part's output projection is drawn so that the part adds a stated number
# of units to the stream.  **How loud each part is, how sharp the attention
# and how fast the states decay is not this module's to say**: ``DRAW`` has
# every part at one unit and Mamba-2's published time steps, and whoever
# compares random weights against a reference says otherwise in the
# configuration (``random_weights`` in a config.json, ``ModelConfig.random_init``;
# ``benchmark/configs/nemotron-3-super-120b-a12b.json`` does, and says under
# ``assumed`` why).  A checkpoint has none of this.
#
# What is the module's is the form of the drawing, which no size changes.
#
# *The routers read lanes that only the embedding writes, through signs*, as
# ``models/longcat_flash.py``'s read such lanes and for its reason: the pick of
# the ``top_k`` largest of some hundreds of scores is a step, and a bfloat16
# program and a float32 reference that pick otherwise in one token-layer are a
# whole pick apart with neither wrong.  The last ``route_lanes`` lanes of the
# embedding are ``+-EMBED_STD`` (a sign a token and lane), no output projection
# writes there (``quiet``), and the routers' rows are zero everywhere else and
# ``+-c`` there, one ``c`` a layer.  A router's logits are then one positive
# number a token (the embedding's size over the stream's, which each program
# rounds its own way) times an even whole number, a sum of signs, exact in
# either precision: the scores lie on levels, and the selection bias, drawn at
# ``SELECT_BIAS_LEVELS`` of the gap between two levels' scores where the last
# pick falls, orders the experts of a level and moves none to another.  Which
# experts of the boundary level a token picks is the bias's to say, and the
# same in either precision.  **Which experts a token picks then depends on the
# token and the layer and not on its context, which a checkpoint's routers do
# not do**; ``tests/test_nemotron_h.py`` routes on the whole stream with
# routers drawn plainly, so the router's input path is compared too.  The
# stream grows with depth, so an expert layer's ``c`` is as many times larger
# as the parts before it have made the stream (``_stream_sizes``):
# ``ROUTER_GAIN`` is the logits' deviation in every layer.
#
# *The attention*: queries ``score_std`` times unit size, which is the scores'
# deviation; a softmax over ``n`` keys then rests on about ``n / exp(sd^2)`` of
# them and its output, a weighted mean of unit values, is ``sqrt(exp(sd^2) /
# n)`` of a unit (``_attention_size``, at a context of ``ATTN_CONTEXT``), which
# ``W_o``'s scale takes out, so that ``attn_out`` is in the stream's units as
# the other parts' are.
#
# *What the tokens of a context have in common says nothing of a context*, and
# a softmax's mean keeps all of it while it averages what tells them apart
# away: a squared ReLU is positive on every hidden unit and a gated state-space
# output nearly so, and a plain random output projection turns that into one
# vector added to every token's stream.  So the state-space layers' ``W_out``,
# the shared experts' and the routed experts' down-projections are drawn with
# rows that sum to nothing over their inputs (``centred``).
#
# *The state-space layers*: ``exp(A_log)`` uniform in (1, 16) as Mamba-2
# initialises it; ``dt`` log-uniform in (``dt_min``, ``dt_max``); ``D`` 1; the
# convolution's taps uniform in (-0.5, 0.5) and its bias normal 0.1; ``B`` and
# ``C`` are projected ``bc_gain`` times as loud as ``x``.
EMBED_STD = 0.02
ROUTE_LANES = 128
ROUTER_GAIN = 2.0
SELECT_BIAS_LEVELS = 1.0 / 6.0
ATTN_CONTEXT = 512
#: the sizes a configuration may set (``ModelConfig.random_init``), and what
#: each is without: units a part adds to the stream, the attention scores'
#: deviation, the time steps' range (Mamba-2's published one), ``B`` and ``C``
#: beside ``x``
DRAW = {"mamba_out": 1.0, "attn_out": 1.0, "routed_out": 1.0, "shared_out": 1.0,
        "score_std": 1.0, "dt_min": 0.001, "dt_max": 0.1, "bc_gain": 1.0}

# what the engine must refuse for this architecture, each with its sentence
SERVING_LIMITS = {
    "speculative": "nemotron_h does not load its next-token module and has no verify "
                   "block: a rejected draft would have to take its row out of the "
                   "state-space layers' recurrent state again",
    "lora": "nemotron_h has no LoRA deltas on its projections",
    "embeddings": "nemotron_h has no embedding forward",
    "mesh": "nemotron_h runs on one device: neither the state pool and its kernel nor "
            "the experts' exchange between chips is partitioned over a mesh",
    "kv_transfer": "nemotron_h cannot export a sequence: its recurrent state is not "
                   "in the pages",
    "checkpoint": "nemotron_h has no safetensors key map yet: it is served with "
                  "seeded random weights (--model-preset), not from --model-path",
    "dense_mlp_layer": "nemotron_h: a '-' layer (a dense MLP) in hybrid_override_pattern "
                       "is not served; the patterns served are made of M, E and *",
}

KINDS = ("mamba", "moe", "full_attention")

def route_lanes(hidden: int) -> int:
    """How many lanes at the end of the residual stream the routers read
    (fewer at toy widths, where a router of some tens of outputs needs coarse
    levels for a level to hold several experts)."""
    return min(ROUTE_LANES, max(hidden // 16, 8))


def drawing(cfg: ModelConfig) -> dict:
    """``DRAW`` with what the configuration sets of it."""
    given = dict(cfg.random_init)
    unknown = sorted(set(given) - set(DRAW))
    if unknown:
        raise ValueError(f"nemotron_h: random_weights names {unknown}, which the drawing "
                         f"does not have ({', '.join(sorted(DRAW))} are set)")
    return {**DRAW, **given}


def _stream_sizes(cfg: ModelConfig) -> list[float]:
    """The residual stream's expected size before every expert layer under
    ``init_params``' drawing, in units of the embedding's."""
    d = drawing(cfg)
    out = {"mamba": d["mamba_out"] ** 2, "full_attention": d["attn_out"] ** 2,
           "moe": d["routed_out"] ** 2 + d["shared_out"] ** 2}
    size2, sizes = 1.0, []
    for kind in cfg.layer_types:
        if kind == "moe":
            sizes.append(math.sqrt(size2))
        size2 += out[kind]
    return sizes


def _attention_size(score_std: float) -> float:
    """The size of a softmax-weighted mean of ``ATTN_CONTEXT`` unit values
    under scores of deviation ``score_std``."""
    return min(1.0, math.sqrt(math.exp(score_std ** 2) / ATTN_CONTEXT))


def select_bias_std(cfg: ModelConfig) -> float:
    """``SELECT_BIAS_LEVELS`` of the gap between the scores of two neighbouring
    levels of a router's logits, at the level the last pick falls on: the
    logits are ``ROUTER_GAIN / sqrt(route_lanes)`` times a sum of that many
    signs, near enough normal."""
    from statistics import NormalDist

    at = ROUTER_GAIN * NormalDist().inv_cdf(1.0 - cfg.num_experts_per_tok / cfg.num_experts)
    slope = math.exp(-at) / (1.0 + math.exp(-at)) ** 2  # the sigmoid's, at the last pick
    return SELECT_BIAS_LEVELS * slope * 2.0 * ROUTER_GAIN / math.sqrt(route_lanes(cfg.hidden_size))


def layers_of(cfg: ModelConfig) -> list[tuple[str, int]]:
    """``(kind, index among the layers of its kind)`` of every layer."""
    seen = dict.fromkeys(KINDS, 0)
    out = []
    for kind in cfg.layer_types:
        if kind not in seen:
            raise ValueError(f"nemotron_h: unknown kind of layer {kind!r}")
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def count(cfg: ModelConfig, kind: str) -> int:
    return sum(1 for t in cfg.layer_types if t == kind)


def conv_channels(cfg: ModelConfig) -> int:
    return cfg.ssm_num_heads * cfg.ssm_head_dim + 2 * cfg.ssm_groups * cfg.ssm_state_size


def state_shapes(cfg: ModelConfig, slots: int) -> tuple[tuple, tuple]:
    """Shapes of the two state pools for ``slots`` slots (the garbage slot
    included): recurrent state float32, convolution tail in the model's dtype.

    The tail is still one flat row a slot, ``[layers, slots, (K - 1) * C]``,
    with the slot on the tiles' sublanes (a decode column writes a 60 KB row at
    thirty times its bytes: ``PERF.md``, Findings, PR 54) and not
    ``ops.linear_attention.tail_block``'s whole tiles a slot, which
    ``models/kimi_linear.py`` and ``models/olmo_hybrid.py`` take: the
    benchmark's drive of this model scales the pool's slots as a pool of three
    axes (``benchmark/architectures/nemotron_h.py``, ``Drive.decode``) and a
    change of the program may not edit it.  Once that line takes a pool of any
    rank, the second shape here is ``(Lm, slots, *tail_block(conv_channels(cfg),
    cfg.ssm_conv_kernel, cfg.dtype))`` and nothing else changes."""
    Lm = count(cfg, "mamba")
    return ((Lm, slots, cfg.ssm_state_size, cfg.ssm_num_heads * cfg.ssm_head_dim),
            (Lm, slots, (cfg.ssm_conv_kernel - 1) * conv_channels(cfg)))


def prefill_workspace_bytes(cfg: ModelConfig, tokens: int, dtype: str) -> int:
    """Bytes a prefill of ``tokens`` tokens holds beside its arguments, from
    shapes and on the high side: a state-space layer's projections in the
    model's dtype; in float32 the convolution's input and output, each once
    more as the chunks' view of it, and the recurrence's ``x``, its ``y`` and
    the gated ``y`` as they come and as the chunks hold them; an expert layer's
    rows gathered pick by pick in the latent.  For two rows of 2,048 at the
    published widths this is 2.53 GB where the compiler counts 1.09: the sum
    was fitted to 2.46 GB, which held a second state pool until a launch
    stopped copying it (``ops/ssm.py``, State layout); what it keeps free of
    pages is the cache plan's to change."""
    DI, C = cfg.ssm_num_heads * cfg.ssm_head_dim, conv_channels(cfg)
    return tokens * ((DI + C) * jnp.dtype(dtype).itemsize + 4 * (4 * C + 10 * DI)
                     + 4 * cfg.num_experts_per_tok * cfg.moe_latent_size)


def decode_step(cfg: ModelConfig) -> dict:
    """The state-space layers' decode step, for the runner: the name it goes
    by in ``loads()``, the forwards' keyword that picks its form, what the
    layers are called, and whether the kernel's blocks fit this shape."""
    from smg_tpu.ops.pallas import ssm_decode

    return {"name": "ssm_decode", "arg": "ssm_impl", "layers": "state-space",
            "kernel_fits": ssm_decode.supported(cfg.ssm_num_heads, cfg.ssm_head_dim,
                                                cfg.ssm_state_size, cfg.ssm_groups)}


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random weights (tests, benchmarks), drawn as the comment above says at
    the sizes ``drawing(cfg)`` gives; norm weights 1."""
    E, V = cfg.hidden_size, cfg.vocab_size
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hm, P, N, R = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups
    DI, C = Hm * P, conv_channels(cfg)
    Z, F, Fs = cfg.moe_latent_size, cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size
    X, Xh = cfg.num_experts, cfg.held_experts[1]
    Lm, Le, La = (count(cfg, k) for k in KINDS)
    RL = route_lanes(E)
    d = drawing(cfg)
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 64))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(dtype)

    def quiet(w):
        """An output projection [..., E] that writes nothing to the routers' lanes."""
        return w.at[..., E - RL:].set(0)

    def centred(w):
        """An output projection [..., in, out] whose rows sum to nothing over
        its inputs: what all its inputs have in common says nothing."""
        return (w.astype(jnp.float32) - jnp.mean(w.astype(jnp.float32), axis=-2,
                                                 keepdims=True)).astype(dtype)

    signs = jnp.where(jax.random.bernoulli(next(ks), 0.5, (V, RL)), EMBED_STD, -EMBED_STD)
    embed = jnp.concatenate([normal((V, E - RL), EMBED_STD), signs.astype(dtype)], axis=1)
    # the root mean square of relu(n)^2 for a unit normal n: sqrt(E[n^4] / 2)
    relu2 = math.sqrt(1.5)
    dt = jnp.exp(jax.random.uniform(next(ks), (Lm, Hm), jnp.float32,
                                    math.log(d["dt_min"]), math.log(d["dt_max"])))
    mamba = {
        "norm": jnp.ones((Lm, E), dtype),
        "w_z": normal((Lm, E, DI), E ** -0.5),
        "w_xbc": jnp.concatenate([normal((Lm, E, DI), E ** -0.5),
                                  normal((Lm, E, C - DI), d["bc_gain"] * E ** -0.5)], axis=-1),
        "w_dt": normal((Lm, E, Hm), E ** -0.5),
        "conv_w": jax.random.uniform(next(ks), (Lm, cfg.ssm_conv_kernel, C), jnp.float32,
                                     -0.5, 0.5).astype(dtype),
        "conv_b": normal((Lm, C), 0.1),
        "A_log": jnp.log(jax.random.uniform(next(ks), (Lm, Hm), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((Lm, Hm), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
        "gate_norm": jnp.ones((Lm, DI), dtype),
        "w_out": quiet(centred(normal((Lm, DI, E), d["mamba_out"] * EMBED_STD * DI ** -0.5))),
    }
    # a token's picks on held experts weigh ``scale / top_k`` each, near enough
    held_picks = max(cfg.num_experts_per_tok * Xh / X, 1.0)
    routed = (cfg.routed_scaling_factor / cfg.num_experts_per_tok) * math.sqrt(held_picks) * relu2
    louder = jnp.asarray(_stream_sizes(cfg), jnp.float32)[:, None, None]
    router = jnp.zeros((Le, E, X), jnp.float32).at[:, E - RL:].set(
        jnp.where(jax.random.bernoulli(next(ks), 0.5, (Le, RL, X)), 1.0, -1.0) * louder
        * ROUTER_GAIN * RL ** -0.5)
    moe_p = {
        "norm": jnp.ones((Le, E), dtype),
        "router": router.astype(dtype),
        "select_bias": jax.random.normal(next(ks), (Le, X), jnp.float32)
                       * select_bias_std(cfg),
        "w_dl": normal((Le, E, Z), E ** -0.5),
        "w_ul": quiet(normal((Le, Z, E), d["routed_out"] * EMBED_STD / routed * Z ** -0.5)),
        "ws_up": normal((Le, E, Fs), E ** -0.5),
        "ws_down": quiet(centred(normal((Le, Fs, E),
                                        d["shared_out"] * EMBED_STD / relu2 * Fs ** -0.5))),
    }
    experts = {
        "w_up": normal((Le, Xh, Z, F), Z ** -0.5),
        "w_down": centred(normal((Le, Xh, F, Z), F ** -0.5)),
    }
    attn = {
        "norm": jnp.ones((La, E), dtype),
        # the three input projections are stored [out, in], as
        # ``models/mimo.py``'s are: compiled for a v5e, a decode frame copies
        # an [in, out] ``wq`` into that layout
        "wq": normal((La, H * D, E), d["score_std"] * E ** -0.5),
        "wk": normal((La, K * D, E), E ** -0.5),
        "wv": normal((La, K * D, E), E ** -0.5),
        "wo": quiet(normal((La, H * D, E), d["attn_out"] * EMBED_STD
                           / _attention_size(d["score_std"]) * (H * D) ** -0.5)),
    }
    return {
        "embed": embed,
        "mamba": mamba,
        "moe": moe_p,
        "experts": experts,
        "attn": attn,
        "final_norm": jnp.ones((E,), dtype),
        "lm_head": normal((E, V), 0.02),
    }


def logical_axes(cfg: ModelConfig) -> Params:
    """Every array replicated: this module runs on one device."""
    return jax.tree.map(lambda x: (None,) * x.ndim,
                        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))


# --------------------------------------------------------------------------
# the three kinds of layer.  What differs between prefill and decode is how a
# layer reaches what its sequence holds, so each takes that as a function:
# ``mix(xbc, dt, g)`` runs the convolution and the recurrence over the layer's
# slot, ``attend(q, k, v)`` writes and reads the pages.  Both return their
# result and whatever they changed, which the layer hands back.


@jax.named_scope("smg.ssm.layer")
def mamba_layer(h, layer: Params, cfg: ModelConfig, mix):
    """``h`` [..., E].  ``mix(xbc [..., C], dt [..., H], g [..., H])`` (``g``
    the log of the decay) returns ``y`` [..., H, P] (float32, the ``D x`` term
    in it) and the new state.  Returns ``(h, new state)``."""
    f32 = jnp.float32
    u = _norm(h, layer["norm"], cfg)
    with jax.named_scope("smg.ssm.in_proj"):
        z = jnp.einsum("...e,ed->...d", u, layer["w_z"])
        xbc = jnp.einsum("...e,ec->...c", u, layer["w_xbc"])
        # the step feeds an exponential summed over the sequence: its columns
        # are accumulated and kept in float32
        dt = jnp.einsum("...e,eh->...h", u, layer["w_dt"], preferred_element_type=f32)
    dt = jax.nn.softplus(dt + layer["dt_bias"].astype(f32))
    y, state = mix(xbc, dt, -dt * jnp.exp(layer["A_log"].astype(f32)))
    with jax.named_scope("smg.ssm.gate_norm"):
        R = cfg.ssm_groups
        y = y.reshape(*h.shape[:-1], R, -1) * jax.nn.silu(z.astype(f32)).reshape(
            *h.shape[:-1], R, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = y.reshape(*h.shape[:-1], -1) * layer["gate_norm"].astype(f32)
    with jax.named_scope("smg.ssm.out_proj"):
        out = jnp.einsum("...d,de->...e", y.astype(h.dtype), layer["w_out"])
    return h + out, state


def split_xbc(y, cfg: ModelConfig):
    """The convolution's output ``y`` [..., C] as ``x`` [..., H, P] and ``B``,
    ``C`` [..., R, N], float32."""
    H, P, N, R = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups
    x, B, C = jnp.split(y, [H * P, H * P + R * N], axis=-1)
    return (x.reshape(*x.shape[:-1], H, P), B.reshape(*B.shape[:-1], R, N),
            C.reshape(*C.shape[:-1], R, N))


@jax.named_scope("smg.attn.layer")
def attention_layer(h, layer: Params, cfg: ModelConfig, attend):
    """``h`` [..., E].  ``attend(q [..., H, D], k, v [..., K, D])`` returns the
    attention's output [..., H, D] and the caches it wrote.  Returns ``(h,
    caches)``."""
    u = _norm(h, layer["norm"], cfg)
    D = cfg.head_dim
    proj = lambda w: jnp.einsum("...e,fe->...f", u, layer[w]).reshape(*u.shape[:-1], -1, D)
    out, caches = attend(proj("wq"), proj("wk"), proj("wv"))
    out = out.astype(h.dtype).reshape(*h.shape[:-1], -1)
    return h + jnp.einsum("...f,fe->...e", out, layer["wo"]), caches


@jax.named_scope("smg.moe.shared")
def shared_expert(layer: Params, u):
    """The shared expert, whole on every chip: ungated, on the uncut input."""
    return jnp.einsum("...f,fe->...e", _relu2(jnp.einsum("...e,ef->...f", u, layer["ws_up"])),
                      layer["ws_down"])


@jax.named_scope("smg.moe.residual")
def moe_layer(h, layer: Params, experts: Params, i, cfg: ModelConfig, live, impl: str):
    """``h + W_ul sum_i w_i E_i(W_dl u) + E_shared(u)`` over the held experts,
    ``u = RMSNorm(h)``.  ``experts`` holds the routed experts' weights of all
    expert layers, ``i`` picks this layer's.  ``live`` [...] marks real tokens:
    a padded one picks no expert.  Returns ``h`` and the layer's counts
    (``ROUTED_COUNTS``)."""
    u = _norm(h, layer["norm"], cfg)
    flat = u.reshape(-1, u.shape[-1])
    routing = moe.route(flat, layer["router"], top_k=cfg.num_experts_per_tok,
                        scoring=cfg.moe_scoring, norm_topk=cfg.norm_topk_prob,
                        scale=cfg.routed_scaling_factor, select_bias=layer["select_bias"])
    alive = live.reshape(-1)
    routing = routing._replace(experts=jnp.where(alive[:, None], routing.experts, -1))
    with jax.named_scope("smg.moe.latent_down"):
        c = jnp.einsum("te,ez->tz", flat, layer["w_dl"])
    m, (rows, hit) = moe.expert_layer(c, routing, None, experts["w_up"], experts["w_down"],
                                      cfg.held_experts, impl, layer=i)
    with jax.named_scope("smg.moe.latent_up"):
        o = jnp.einsum("tz,ze->te", m.astype(h.dtype), layer["w_ul"])
    o = (o + shared_expert(layer, flat)).reshape(h.shape)
    picks = jnp.sum(alive).astype(jnp.int32) * cfg.num_experts_per_tok
    return h + o, jnp.stack([picks, rows, hit, rows])


def _stack(params: Params, cfg: ModelConfig, h, carry, live, moe_impl, mamba, attention):
    """The layers in the pattern's order, written out one after another.
    ``mamba(h, layer, li, carry)`` and ``attention(h, layer, p, carry)`` run
    one layer each (``li`` the layer's index in the state pools, ``p`` in the
    cache) and return ``(h, carry)``.  Returns ``h``, the carry and the expert
    layers' counts."""
    counts = jnp.zeros((len(ROUTED_COUNTS),), jnp.int32)

    def at(tree, i, scope):  # a layer's weights out of their stack, under its scope
        with jax.named_scope(scope):
            return jax.tree.map(lambda x: x[i], tree)

    for kind, i in layers_of(cfg):
        if kind == "mamba":
            h, carry = mamba(h, at(params["mamba"], i, "smg.ssm.layer"), i, carry)
        elif kind == "full_attention":
            h, carry = attention(h, at(params["attn"], i, "smg.attn.layer"), i, carry)
        else:
            h, c = moe_layer(h, at(params["moe"], i, "smg.moe.residual"), params["experts"],
                             i, cfg, live, moe_impl)
            counts = merge_counts(counts, c)
    return h, carry, counts


# --------------------------------------------------------------------------
# prefill


def _prefill(params, cfg, tokens, prefix_lens, t_reals, k_cache, v_cache, page_tables,
             s_pool, c_pool, slots, attention, moe_impl):
    """Solo and grouped prefill: ``tokens`` [G, T], one row a sequence.
    ``attention(q, k, v, kc, vc, p, pos)`` is the attention over caches the
    chunk is already in.  The recurrence runs in its chunked form from the
    state in ``slots`` (zero for a row that starts its sequence); a padded
    token has ``dt`` 0 and decays nothing and stays out of the convolution's
    tail, a padded row names the garbage slot."""
    G, T = tokens.shape
    K, D, H = cfg.num_kv_heads, cfg.head_dim, cfg.ssm_num_heads
    with jax.named_scope("smg.prefill.land"):  # where the chunks' rows stand and land
        pos = prefix_lens[:, None] + jnp.arange(T)[None, :]
        real = jnp.arange(T)[None, :] < t_reals[:, None]
        dest = page_slots(page_tables, pos, real, k_cache.shape[2]).reshape(-1)
        keep = (prefix_lens > 0).astype(jnp.float32)  # 0 where the sequence starts here
    taps = cfg.ssm_conv_kernel - 1
    h = embed_tokens(params, cfg, tokens)

    def mamba(h, layer, li, carry):
        kc, vc, s_pool, c_pool = carry

        def mix(xbc, dt, g):
            tail = read_tail(c_pool, li, slots, taps)  # [G, K-1, C]
            y, tail = ssm.causal_conv(xbc, tail * keep[:, None, None].astype(tail.dtype),
                                      layer["conv_w"], t_reals, layer["conv_b"])
            x, B, C = split_xbc(y, cfg)
            # the scan wants its carry laid out otherwise than the pool lies
            # (``ops/ssm.py``, State layout): the barriers keep that relayout
            # on the rows, which cross them in the pool's layout both ways
            rows = jax.lax.optimization_barrier(read_state(s_pool, li, slots))
            S0 = pool_to_heads(rows, H) * keep[:, None, None, None]
            y, S = ssm.ssd_chunked(x, jnp.where(real[..., None], dt, 0.0),
                                   jnp.where(real[..., None], g, 0.0), B, C, S0,
                                   cfg.ssm_chunk_size)
            y = y + layer["D"].astype(jnp.float32)[:, None] * x
            rows = jax.lax.optimization_barrier(heads_to_pool(S))
            return y, (write_state(s_pool, li, slots, rows),
                       write_tail(c_pool, li, slots, tail))

        h, (s_pool, c_pool) = mamba_layer(h, layer, cfg, mix)
        return h, (kc, vc, s_pool, c_pool)

    def attn(h, layer, p, carry):
        kc, vc, s_pool, c_pool = carry

        def attend(q, k, v):
            kc2, vc2 = scatter_kv_pages_full(kc, vc, p, k.reshape(G * T, K, D),
                                             v.reshape(G * T, K, D), dest)
            return attention(q, k, v, kc2, vc2, p, pos), (kc2, vc2)

        h, (kc, vc) = attention_layer(h, layer, cfg, attend)
        return h, (kc, vc, s_pool, c_pool)

    h, carry, _counts = _stack(params, cfg, h, (k_cache, v_cache, s_pool, c_pool), real,
                               moe_impl, mamba, attn)
    with jax.named_scope("smg.lm_head"):
        last = jnp.take_along_axis(
            h, jnp.maximum(t_reals - 1, 0)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return (unembed(params, cfg, last), *carry)


def forward_prefill(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,  # unused: no layer has a rotary embedding
    tokens: jnp.ndarray,  # [T] padded to bucket
    prefix_len: jnp.ndarray,  # scalar: tokens of the sequence before this chunk
    t_real: jnp.ndarray,  # scalar: valid new tokens (<= T)
    k_cache: jnp.ndarray,  # [attention layers, P, ps, K*D]
    v_cache: jnp.ndarray,
    page_table: jnp.ndarray,  # [mp]
    s_pool: jnp.ndarray,  # [state-space layers, slots, N, H*P] float32
    c_pool: jnp.ndarray,  # [state-space layers, slots, (K-1) * C]
    slot: jnp.ndarray,  # scalar: the sequence's state slot
    attn_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret" (tests)
    moe_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret"
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
        params, cfg, tokens[None], prefix_len[None], t_real[None], k_cache, v_cache,
        page_table[None], s_pool, c_pool, slot[None], attention, moe_impl)
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
    moe_impl: str = "xla",
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

    return _prefill(params, cfg, tokens, prefix_lens, t_reals, k_cache, v_cache, page_tables,
                    s_pool, c_pool, slots, attention, moe_impl)


def forward_train(params: Params, cfg: ModelConfig, inv_freq: jnp.ndarray,
                  tokens: jnp.ndarray,  # [B, T]
                  moe_impl: str = "xla") -> jnp.ndarray:
    """Dense causal forward from zero state, no cache: logits [B, T, V]."""
    G, T = tokens.shape
    H, P, N = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size
    scale = 1.0 / math.sqrt(cfg.head_dim)
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (G, T))
    whole = jnp.full((G,), T, jnp.int32)

    def mamba(h, layer, _li, carry):
        def mix(xbc, dt, g):
            tail = jnp.zeros((G, cfg.ssm_conv_kernel - 1, xbc.shape[-1]), xbc.dtype)
            y, _ = ssm.causal_conv(xbc, tail, layer["conv_w"], whole, layer["conv_b"])
            x, B, C = split_xbc(y, cfg)
            y, _ = ssm.ssd_chunked(x, dt, g, B, C, jnp.zeros((G, H, N, P), jnp.float32),
                                   cfg.ssm_chunk_size)
            return y + layer["D"].astype(jnp.float32)[:, None] * x, None

        return mamba_layer(h, layer, cfg, mix)[0], carry

    def attn(h, layer, _p, carry):
        attend = lambda q, k, v: (attention_prefill_batched(q, k, v, pos, whole, scale), None)
        return attention_layer(h, layer, cfg, attend)[0], carry

    h, _, _ = _stack(params, cfg, embed_tokens(params, cfg, tokens), None,
                     jnp.ones((G, T), jnp.bool_), moe_impl, mamba, attn)
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
    k_cache: jnp.ndarray,  # read-only during the frame
    v_cache: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, mp]
    hk_all: jnp.ndarray,  # [attention layers, B, N, K*D] the frame's side buffers
    hv_all: jnp.ndarray,
    s_pool: jnp.ndarray,
    c_pool: jnp.ndarray,
    slots: jnp.ndarray,  # [B]; a padded row names slot 0
    runs: jnp.ndarray,  # [B] bool: the lane runs this column
    attn_impl: str = "xla",
    ssm_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret"
    moe_impl: str = "xla",
):
    """One decode column.  The attention layers read the frozen cache and the
    side buffers, as in ``models/llama.py``; the state-space layers advance the
    state in their slots by one token, in place.  A lane with ``runs`` false
    (a padded row) gets ``dt`` 0 and a decay of 1 and keeps its convolution
    tail, so its slot is left bit for bit, and picks no expert.  Returns
    (logits [B, V], hk_all, hv_all, s_pool, c_pool, counts)."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    h = embed_tokens(params, cfg, tokens)

    def mamba(h, layer, li, carry):
        hk, hv, s_pool, c_pool = carry

        def mix(xbc, dt, g):
            y, c_new = ssm.conv_decode(c_pool, li, slots, runs, xbc, layer["conv_w"],
                                       layer["conv_b"])
            x, B, C = split_xbc(y, cfg)
            dt = jnp.where(runs[:, None], dt, 0.0)
            decay = jnp.where(runs[:, None], jnp.exp(g), 1.0)
            if ssm_impl.startswith("pallas"):
                from smg_tpu.ops.pallas.ssm_decode import ssm_decode

                y, s_new = ssm_decode(s_pool, li, slots, x, dt, decay, B, C,
                                      interpret=(ssm_impl == "pallas_interpret"))
            else:
                y, s_new = ssm.ssd_step(s_pool, li, slots, x, dt, decay, B, C)
            y = y + layer["D"].astype(jnp.float32)[:, None] * x
            return y, (s_new, c_new)

        h, (s_pool, c_pool) = mamba_layer(h, layer, cfg, mix)
        return h, (hk, hv, s_pool, c_pool)

    def attn(h, layer, p, carry):
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

        h, (hk_all, hv_all) = attention_layer(h, layer, cfg, attend)
        return h, (hk_all, hv_all, s_pool, c_pool)

    h, carry, counts = _stack(params, cfg, h, (hk_all, hv_all, s_pool, c_pool), runs,
                              moe_impl, mamba, attn)
    return (unembed(params, cfg, h), *carry, counts)
