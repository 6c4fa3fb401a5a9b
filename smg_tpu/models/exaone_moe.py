"""K-EXAONE: a post-norm block with q/k norms, sliding layers that rotate and
full layers that do not, routed experts beside a shared one, and the model's
own next-token module as a drafter.

``model_type: exaone_moe`` (``LGAI-EXAONE/K-EXAONE-236B-A23B``).  What the
configuration does not carry is the family's (``transformers``' ``exaone4``
for the block and the attention, DeepSeek-V3's for the router and the
next-token module, whose keys the configuration has); every such item is
marked *assumed*.  For a token's hidden vector ``h`` at position ``i``, ``H``
query and ``G`` key/value heads of ``D`` lanes:

*Block* (assumed): ``a = h + RMSNorm(Attn_l(h))``, ``h' = a + RMSNorm(MLP_l(a))``:
the norms sit on each sublayer's **output** and none on its input; a final
RMSNorm and an untied head; ``rms_norm_eps`` in every norm.

*Attention*: ``q_h = RMSNorm_q(W_q,h x)``, ``k_g = RMSNorm_k(W_k,g x)`` over
the ``D`` lanes of a head, one learned weight vector each a layer (assumed),
``v_g = W_v,g x``.  Rotary, rotate-half pairing over all ``D`` lanes at the
published base (``cfg.swa_rope_theta``: the sliding layers' base, as
``models/mimo.py`` names it), **in a ``sliding_attention`` layer only**: a
``full_attention`` layer's queries and keys are not rotated (assumed;
``cfg.rope_theta`` 0, the tree's word for full layers without rotary).  Scores
``q . k / sqrt(D)`` for ``j <= i`` and, in a sliding layer, ``j > i -
sliding_window``; a plain softmax, no sink, no bias.

*MLP*: the first ``first_k_dense_replace`` layers a SwiGLU MLP of
``intermediate_size``.  Else ``s = sigmoid(W_r x)`` in float32 over all
``num_experts``; the ``top_k`` picked are the largest of ``s + b`` (``b`` a
parameter, used to pick and not to weigh; assumed: the configuration has
DeepSeek-V3's routing keys and no ``topk_method``); ``w_e =
routed_scaling_factor * s_e / sum_picked s``; ``MLP(x) = sum_picked w_e E_e(x)
+ E_shared(x)``, every expert a SwiGLU MLP of ``moe_intermediate_size``.  This
process holds the routed experts ``cfg.held_experts`` and adds what they give
(``ops/moe.py``); the shared expert is whole here
(``models/pangu_moe.shared_expert``).

*Next-token module* (assumed: DeepSeek-V3's form, whose key
``num_nextn_predict_layers`` is).  For position ``i`` with the stack's last
hidden vector ``h_i`` (before the final norm) and the next token ``t_{i+1}``:
``u_i = W_eh [RMSNorm_e(Embed(t_{i+1})) ; RMSNorm_h(h_i)]``, one block of the
kind above with ``full_attention`` over the module's **own** keys and values
of positions ``<= i`` and the expert MLP (assumed sparse: the configuration
names the module's attention kind and not its MLP's), a norm of its own, the
model's head: logits for ``t_{i+2}``.  It shares the embedding and the head.
One module, one draft a column, as published; it is not applied twice.

**What a sequence holds.**  Pages for the full layers and, as one more layer
of the same pages, for the module's attention; for the sliding layers a ring
of the last tokens a layer (``models/mimo.py``, ``ops/window_attention.py``).

**Departures from the equations above**: none in arithmetic.  With
speculation off the module's weights are held and not run.

**What this module serves**: ``models/mimo.py``'s three forwards with this
block, and with speculation on ``forward_verify_column`` (the stack over two
rows a lane, ``[last accepted token, the module's draft]``),
``forward_mtp_prefill`` and ``forward_mtp_column`` (the module behind the
stack) and ``mtp_logits``.  A draft is accepted where it is the model's own
greedy token, so what a lane emits is what the one-row column emits
(``engine/window_runner.SelfDraftingRunner``).  Everything in
``SERVING_LIMITS`` is refused at start, not run wrong.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from smg_tpu.models import mimo
from smg_tpu.models.config import ModelConfig
from smg_tpu.models.llama import _mlp, _norm, embed_tokens, unembed
from smg_tpu.models.mimo import (  # noqa: F401  (the runner's, by these names)
    ROUTED_COUNTS,
    _scale,
    layer_kinds,
    layer_runs,
    merge_counts,
    prefill_workspace_bytes,
    side_buffers,
)
from smg_tpu.models.pangu_moe import shared_expert
from smg_tpu.ops import moe
from smg_tpu.ops import window_attention as wa
from smg_tpu.ops.attention import attention_verify_cached
from smg_tpu.ops.norms import rms_norm
from smg_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict[str, Any]

# RANDOM weights (``init_params``), drawn, as ``models/mimo.py``'s are, so
# that what a sequence holds shows in the logits, and so that every sublayer
# speaks.  A post-norm block changes what that takes.  A sublayer's output is
# rescaled to its norm's weight, so how loudly it speaks is that weight
# (``POST_NORM_WEIGHT``, the embedding's scale: the token's own embedding and
# every sublayer are of one order) and not its output projection's scale; but
# ``rms_norm_eps`` sits under the same root, so an output whose mean square is
# not far above 1e-5 is rescaled by less and one far below it is silenced (at
# normal 0.02 throughout, an expert layer's output read 2e-7 and leaving the
# shared expert out moved no logit).  And a sublayer's **input** is the
# residual stream itself, about ``1 / IN_GAIN`` in deviation, with no norm
# before it.  So the projections that read the stream (values, gates, ups,
# queries and keys) are drawn at ``IN_GAIN / sqrt(hidden)``, which makes what
# they give of order 1, and the output projections at ``1 / sqrt(fan-in)``.  The
# scores' deviation is the product of the q and k norms' weights, ``*_SCORE_STD``,
# 2.0 in both kinds of layer: against the benchmark's check (tolerance 0.30 of
# a row's deviation) flatter scores shrink what rounding moves and what one
# wrong page of 44 moves alike (at 1.0: 0.04-0.10 and 0.29-0.33), sharper ones
# raise both (at 3.0: 0.11-0.29 and 0.45-0.78), and at 2.0 the tolerance sits
# in the middle (0.08-0.20 and 0.39-0.89; my chip runs, PR 41).
# **The routers read lanes that only the embedding writes.**  The pick of the
# 8 largest of 128 scores is a step: wherever the 8th and the 9th lie closer
# than rounding moves them, a bfloat16 program and a float32 reference pick
# different experts, neither is wrong, and the logits differ by a whole pick.
# With routers that read the whole stream that met one token-layer in ten
# (bfloat16 leaves the stream 0.2-1 % off and the 8th and 9th of 128 scores
# lie 6 % of their deviation apart), so the routed experts had to be drawn a
# quarter as loud as the shared one for a comparison to pass, and were then
# too quiet for it to see (PERF.md, Findings, PR 41).  So the last
# ``route_lanes`` lanes of the stream are the routers': the sublayers' output
# norms weigh them 0, the stream there is the token's embedding in every
# layer, bit for bit in either precision, and a router is drawn on those
# lanes alone (deviation of a score's logit 1, its sigmoids over (0.25,
# 0.75)).  Which experts a token meets then depends on the token and not on
# its context, which a checkpoint's routers do not do; the rows an expert gets
# in a column, which is what its time depends on, are those of tokens drawn
# apart: a little more even than under routers that read the context (a
# column hits 7 % more of the held experts: PERF.md, section 5).  The routed
# experts' output projections are drawn as loud as the shared expert's (this
# model takes no ``random_routed_out_gain``): a token's expected one pick on a
# chip's share (8 x 16/128) at its weight of 2.5/8 is a third as loud as the
# shared expert, and held experts that give nothing move the logits by 1.4 to
# 2.0 of a row's deviation where rounding moves them by 0.07-0.12 (my chip
# runs, PR 41, call R1).  The module's routers read its own input there.
# A full-attention layer's output norm is drawn five times as loud: the cut
# has one such layer beside four sliding ones, and at one order with the rest
# one wrong page of a sequence's 44 moved the logits by 0.37-0.53 of a row's
# deviation where rounding moves them by 0.13-0.16 (my chip run, PR 41, call
# 1): what the pages hold has to show.
POST_NORM_WEIGHT = 0.02
FULL_ATTN_NORM_WEIGHT = 0.1
IN_GAIN = 25.0
FULL_SCORE_STD, WINDOW_SCORE_STD = 2.0, 2.0
ROUTE_LANES = 128


def route_lanes(hidden: int) -> int:
    """How many lanes at the end of the residual stream the routers read."""
    return min(ROUTE_LANES, hidden // 4)

SERVING_LIMITS = {
    "speculative": "exaone_moe drafts with its own next-token module (tier mtp) and verifies "
                   "two rows a lane inside the decode frame; the n-gram and draft-model tiers, "
                   "whose verify block is host-drafted, are not served. A lane that samples "
                   "(temperature > 0), carries penalties or a grammar, or runs a frame of one "
                   "column without the device's stop state emits one token a column: its "
                   "draft row is computed and never accepted",
    "lora": "exaone_moe has no LoRA deltas on its projections",
    "embeddings": "exaone_moe has no embedding forward",
    "mesh": "exaone_moe runs on one device: neither the window store nor the experts' "
            "exchange between chips that hold different experts is partitioned over a mesh",
    "kv_transfer": "exaone_moe cannot export a sequence: what it holds for its window "
                   "layers is not in the pages",
    "checkpoint": "exaone_moe has no safetensors key map yet: it is served with "
                  "seeded random weights (--model-preset), not from --model-path",
}


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random weights (tests, benchmarks), drawn as the comment above says."""
    E, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, D = cfg.num_heads, cfg.head_dim
    Fm, X, Xh = cfg.moe_intermediate_size, cfg.num_experts, cfg.held_experts[1]
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 96))

    def normal(shape, scale=0.02, dt=dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(dt)

    def stack(kind: str, n: int) -> Params:
        window, routed = kind.startswith("window"), kind.endswith("moe")
        kw, vw = cfg.kv_lanes(window)
        # the routing lanes hear no sublayer: only the embedding writes them
        speaks = (jnp.arange(E) < E - route_lanes(E)).astype(jnp.float32)
        post = lambda weight=POST_NORM_WEIGHT: jnp.broadcast_to(weight * speaks, (n, E)).astype(dtype)
        # a head's query and key are normed to unit mean square a lane, so
        # the scores' deviation is the product of the two norms' weights
        reads = IN_GAIN * E ** -0.5
        sharp = WINDOW_SCORE_STD if window else FULL_SCORE_STD
        p = {"post_attn_norm": post() if window else post(FULL_ATTN_NORM_WEIGHT),
             "post_mlp_norm": post(),
             "q_norm": jnp.full((n, D), sharp ** 0.5, dtype),
             "k_norm": jnp.full((n, D), sharp ** 0.5, dtype),
             "wq": normal((n, H * D, E), reads),
             "wk": normal((n, kw, E), reads),
             "wv": normal((n, vw, E), reads),
             "wo": normal((n, H * D, E), (H * D) ** -0.5)}
        if routed:
            R = route_lanes(E)
            router = jnp.zeros((n, E, X), dtype).at[:, E - R:].set(
                normal((n, R, X), 1.0 / (0.02 * R ** 0.5)))
            p.update(router=router,
                     select_bias=normal((n, X), mimo.SELECT_BIAS_STD, jnp.float32),
                     w_gate=normal((n, Xh, E, Fm), reads), w_up=normal((n, Xh, E, Fm), reads),
                     w_down=normal((n, Xh, Fm, E), Fm ** -0.5),
                     ws_gate=normal((n, E, Fm), reads), ws_up=normal((n, E, Fm), reads),
                     ws_down=normal((n, Fm, E), Fm ** -0.5))
        else:
            p.update(w_gate=normal((n, E, F), reads), w_up=normal((n, E, F), reads),
                     w_down=normal((n, F, E), F ** -0.5))
        return p

    kinds = layer_kinds(cfg)
    params: Params = {"embed": normal((V, E)), "final_norm": jnp.ones((E,), dtype)}
    for kind in sorted(set(kinds)):
        params[kind] = stack(kind, kinds.count(kind))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal((E, V))
    if cfg.mtp_layers:
        params["mtp"] = {"enorm": jnp.ones((E,), dtype), "hnorm": jnp.ones((E,), dtype),
                         # of two normed halves, onto the stream's scale
                         "w_eh": normal((2 * E, E), POST_NORM_WEIGHT * (2 * E) ** -0.5),
                         "norm": jnp.ones((E,), dtype),
                         MTP_KIND: stack(MTP_KIND, cfg.mtp_layers)}
    return params


def params_whose_drafts_are_right(params: Params) -> Params:
    """``params`` changed so that the module's every draft is the model's own
    next token (random weights never take the accept branch; tests and
    ``scripts/time_mtp_accept.py`` do, with these): all attention and MLP
    output projections zero in the stack and in the module, ``W_eh`` passing
    the embedding's half.  The stack's logits behind token ``t`` and the
    module's behind the pair ``(., t)`` are then both ``head(norm(Embed(t)))``."""
    quiet = ("wo", "w_down", "ws_down")
    hush = lambda stack: {k: jnp.zeros_like(v) if k in quiet else v for k, v in stack.items()}
    out = {k: hush(v) if isinstance(v, dict) and k != "mtp" else v for k, v in params.items()}
    m = params["mtp"]
    E = m["w_eh"].shape[1]
    w_eh = jnp.concatenate([jnp.eye(E, dtype=m["w_eh"].dtype), jnp.zeros_like(m["w_eh"][E:])])
    out["mtp"] = {**m, "w_eh": w_eh, MTP_KIND: hush(m[MTP_KIND])}
    return out


def logical_axes(cfg: ModelConfig) -> Params:
    """Every array replicated: this module runs on one device."""
    return jax.tree.map(lambda x: (None,) * x.ndim,
                        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))


# --------------------------------------------------------------------------
# the layer.  ``attend`` is ``models/mimo.py``'s: how the queries meet what the
# sequence holds is the forward's to say.

MTP_KIND = "full_moe"  # the module's one block: full attention, the expert MLP


@jax.named_scope("smg.attn.qkv")
def _qkv(layer: Params, cfg: ModelConfig, x, positions, inv_freq):
    """``q`` [..., H, D] and ``k`` [..., G, D], normed a head and, with
    ``inv_freq``, rotated; ``v`` [..., G, D] of the tokens ``x`` [..., E]."""
    heads = lambda y: y.reshape(*y.shape[:-1], -1, cfg.head_dim)
    q = heads(jnp.einsum("...e,fe->...f", x, layer["wq"]))
    k = heads(jnp.einsum("...e,fe->...f", x, layer["wk"]))
    v = heads(jnp.einsum("...e,fe->...f", x, layer["wv"]))
    if cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    if inv_freq is not None:
        q, k = apply_rope(q, positions, inv_freq), apply_rope(k, positions, inv_freq)
    return q, k, v


def _moe(h, layer: Params, experts: Params, i, cfg: ModelConfig, live, impl: str):
    """``sum_i w_i E_i(h) + E_shared(h)`` over the held experts, float32, and
    the counts ``models/mimo._moe_residual`` gives; arguments as there."""
    flat = h.reshape(-1, h.shape[-1])
    routing = moe.route(flat, layer["router"], top_k=cfg.num_experts_per_tok,
                        scoring=cfg.moe_scoring, norm_topk=cfg.norm_topk_prob,
                        scale=cfg.routed_scaling_factor, select_bias=layer["select_bias"])
    alive = live.reshape(-1)
    routing = routing._replace(experts=jnp.where(alive[:, None], routing.experts, -1))
    y, (rows, hit) = moe.expert_layer(flat, routing, experts["w_gate"], experts["w_up"],
                                      experts["w_down"], cfg.held_experts, impl, layer=i)
    if cfg.n_shared_experts:
        y = y + shared_expert(layer, flat, cfg).astype(jnp.float32)
    picks = jnp.sum(alive).astype(jnp.int32) * cfg.num_experts_per_tok
    return y.reshape(h.shape), jnp.stack([picks, rows, hit, rows])


@jax.named_scope("smg.moe.residual")
def _moe_residual(h, layer: Params, experts: Params, i, cfg: ModelConfig, live, impl: str):
    """``h + RMSNorm(MoE(h))``."""
    y, counts = _moe(h, layer, experts, i, cfg, live, impl)
    return h + _norm(y.astype(h.dtype), layer["post_mlp_norm"], cfg), counts


def _layers(stacks: Params, runs, cfg: ModelConfig, inv_freq, h, positions, live, state, attend,
            moe_impl: str):
    """The layers ``runs`` names of ``stacks`` (the stack's, or the module's
    one) over ``h``.  Returns ``h``, the forwards' ``state`` and the expert
    layers' counts (summed over layers, the last kept as a maximum)."""

    window_freq = jnp.asarray(rope_frequencies(cfg.rope_dim, cfg.swa_rope_theta))

    def layer_of(kind: str, experts, first: int, cache0: int):
        window, routed = kind.startswith("window"), kind.endswith("moe")
        freq, attend_kind = ((window_freq, attend["window"]) if window
                             else (inv_freq if cfg.rope_theta else None, attend["full"]))

        def body(carry, xs):
            (h, state, counts), (layer, i) = carry, xs
            q, k, v = _qkv(layer, cfg, h, positions, freq)
            with jax.named_scope("smg.attn.kv"):
                out, state = attend_kind(q, k, v, layer, cache0 + i, state)
            with jax.named_scope("smg.attn.out"):
                o = jnp.einsum("...f,fe->...e",
                               out.astype(h.dtype).reshape(*h.shape[:-1], -1), layer["wo"])
                h = h + _norm(o, layer["post_attn_norm"], cfg)
            if routed:
                h, c = _moe_residual(h, layer, experts, first + i, cfg, live, moe_impl)
                counts = merge_counts(counts, c)
            else:
                with jax.named_scope("smg.mlp"):
                    h = h + _norm(_mlp(layer, h, cfg), layer["post_mlp_norm"], cfg)
            return (h, state, counts), None

        return body

    counts = jnp.zeros((len(ROUTED_COUNTS),), jnp.int32)
    return mimo.scan_runs(stacks, runs, (h, state, counts), layer_of)


def _stack(params, cfg, inv_freq, h, positions, live, state, attend, moe_impl):
    return _layers(params, layer_runs(cfg), cfg, inv_freq, h, positions, live, state, attend,
                   moe_impl)


@jax.named_scope("smg.mtp")
def _module(params, cfg, inv_freq, hidden, next_tokens, positions, live, state, attend, moe_impl):
    """The next-token module over positions whose next token is known:
    ``hidden`` [..., E] the stack's last hidden vectors, ``next_tokens`` [...]
    the tokens one position on.  Its attention is layer ``num_cache_layers -
    1`` of the pages.  Returns its block's output (before its norm), the state
    and the counts."""
    m = params["mtp"]
    with jax.named_scope("smg.mtp.project"):
        x = jnp.concatenate([_norm(embed_tokens(params, cfg, next_tokens), m["enorm"], cfg),
                             _norm(hidden, m["hnorm"], cfg)], axis=-1)
        u = jnp.einsum("...f,fe->...e", x, m["w_eh"])
    runs = [(MTP_KIND, 0, cfg.mtp_layers, cfg.num_cache_layers - cfg.mtp_layers)]
    return _layers(m, runs, cfg, inv_freq, u, positions, live, state, attend, moe_impl)


@jax.named_scope("smg.mtp")
def mtp_logits(params: Params, cfg: ModelConfig, u):
    """The module's logits of its block's output ``u`` [..., E]: its own
    norm, the model's head."""
    return unembed({**params, "final_norm": params["mtp"]["norm"]}, cfg, u)


# --------------------------------------------------------------------------
# prefill


def _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache, v_cache,
             page_tables, ring_k, ring_v, slots, no_ctx, moe_impl, with_hidden):
    pos, real, attend = mimo.prefill_attends(cfg, *tokens.shape, prefix_lens, t_reals,
                                             k_cache.shape[2], page_tables, slots, no_ctx)
    h = embed_tokens(params, cfg, tokens)
    h, state, _counts = _stack(params, cfg, inv_freq, h, pos, real,
                               (k_cache, v_cache, ring_k, ring_v), attend, moe_impl)
    logits = unembed(params, cfg, mimo.last_real(h, t_reals))
    return (logits, *state, h) if with_hidden else (logits, *state)


def forward_prefill(params, cfg, inv_freq, tokens, prefix_len, t_real, k_cache, v_cache,
                    page_table, ring_k, ring_v, slot, attn_impl: str = "xla",
                    moe_impl: str = "xla", with_hidden: bool = False):
    """``models/mimo.forward_prefill``; ``with_hidden`` adds the stack's last
    hidden vectors ``[1, T, E]``, which ``forward_mtp_prefill`` takes."""
    logits, *rest = _prefill(
        params, cfg, inv_freq, tokens[None], prefix_len[None], t_real[None], k_cache, v_cache,
        page_table[None], ring_k, ring_v, slot[None], False, moe_impl, with_hidden)
    return (logits[0], *rest)


def forward_prefill_batched(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache,
                            v_cache, page_tables, ring_k, ring_v, slots, no_ctx: bool = False,
                            moe_impl: str = "xla", attn_impl: str = "xla",
                            with_hidden: bool = False):
    """``models/mimo.forward_prefill_batched``; ``with_hidden`` as above."""
    return _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache, v_cache,
                    page_tables, ring_k, ring_v, slots, no_ctx, moe_impl, with_hidden)


def forward_mtp_prefill(params, cfg, inv_freq, hidden, tokens, first, prefix_lens, t_reals,
                        k_cache, v_cache, page_tables, no_ctx: bool = False,
                        moe_impl: str = "xla"):
    """The module behind a prefill: over the chunks ``tokens`` [G, T] with the
    stack's ``hidden`` [G, T, E], the next token of each row's last real
    position being ``first`` [G] (the token just sampled, or the prompt's
    next).  Its keys and values go to its layer of the pages.  Returns (its
    logits at each row's last real position [G, V], k_cache, v_cache)."""
    G, T = tokens.shape
    pos, real, attend = mimo.prefill_attends(cfg, G, T, prefix_lens, t_reals, k_cache.shape[2],
                                             page_tables, None, no_ctx)
    with jax.named_scope("smg.mtp"):
        nxt = jnp.concatenate([tokens[:, 1:], jnp.zeros((G, 1), tokens.dtype)], axis=1)
        nxt = jnp.where(jnp.arange(T)[None, :] == t_reals[:, None] - 1,
                        first[:, None].astype(tokens.dtype), nxt)
    u, (k_cache, v_cache, _, _), _counts = _module(
        params, cfg, inv_freq, hidden, nxt, pos, real, (k_cache, v_cache, None, None), attend,
        moe_impl)
    return mtp_logits(params, cfg, mimo.last_real(u, t_reals)), k_cache, v_cache


# --------------------------------------------------------------------------
# decode


def forward_decode_horizon(params, cfg, inv_freq, tokens, positions, entry_positions, step_idx,
                           k_cache, v_cache, page_tables, ring_k, ring_v, slots, side, live,
                           attn_impl: str = "xla", moe_impl: str = "xla"):
    """``models/mimo.forward_decode_horizon``: one row a lane, the module not
    run (speculation off)."""
    attend = mimo.decode_attends(cfg, step_idx, entry_positions, k_cache, v_cache, page_tables,
                                 ring_k, ring_v, slots, attn_impl)
    h = embed_tokens(params, cfg, tokens)
    h, side, counts = _stack(params, cfg, inv_freq, h, positions, live, side, attend, moe_impl)
    return unembed(params, cfg, h), side, counts


def _write_rows(pair, k, v, l, held):
    """Put a layer's new rows ``k``, ``v`` ``[B, W, K, D]`` into the side
    buffers ``[L, B, N, K*D]``, lane ``b``'s from its row ``held[b]`` on (the
    layer is part of the scatter's index, as in ``scatter_kv_rows``).  Returns
    layer ``l``'s two buffers and the updated pair."""
    B, W = k.shape[:2]
    at = (l, jnp.arange(B)[:, None], held[:, None] + jnp.arange(W)[None, :])

    def put(buf, x):
        return buf.at[at].set(x.reshape(B, W, -1).astype(buf.dtype), mode="drop")

    sk, sv = put(pair[0], k), put(pair[1], v)
    row = lambda buf: jax.lax.dynamic_index_in_dim(buf, l, 0, keepdims=False)
    return row(sk), row(sv), (sk, sv)


def verify_attends(cfg: ModelConfig, held, entry_positions, k_cache, v_cache, page_tables,
                   ring_k, ring_v, slots, attn_impl: str) -> dict:
    """The two ``attend`` closures of a verify column: ``W`` rows a lane at
    positions ``entry + held + w``, whose keys and values go to the lane's
    side rows ``held + w`` (a row the lane does not accept is written over by
    its next column and never landed)."""
    scale = _scale(cfg)
    kernel = attn_impl.startswith("pallas")
    interpret = attn_impl == "pallas_interpret"

    def full(q, k, v, layer, c, side):
        hk, hv, wk, wv = side
        hk_l, hv_l, (hk, hv) = _write_rows((hk, hv), k, v, c, held)
        if kernel:
            from smg_tpu.ops.pallas.decode_attention import paged_attention_verify_cached

            out = paged_attention_verify_cached(q, k_cache, v_cache, hk_l, hv_l, held, c,
                                                page_tables, entry_positions, scale,
                                                interpret=interpret)
        else:
            out = attention_verify_cached(q, k_cache, v_cache, hk_l, hv_l, held, c,
                                          page_tables, entry_positions, scale)
        return out, (hk, hv, wk, wv)

    def window(q, k, v, layer, c, side):
        hk, hv, wk, wv = side
        wk_l, wv_l, (wk, wv) = _write_rows((wk, wv), k, v, c, held)
        if kernel:
            from smg_tpu.ops.pallas.window_decode import window_attention_verify

            out = window_attention_verify(q, ring_k, ring_v, wk_l, wv_l, held, c, slots,
                                          entry_positions, cfg.sliding_window, None, scale,
                                          interpret=interpret)
        else:
            out = wa.window_attention_verify(q, ring_k, ring_v, wk_l, wv_l, held, c, slots,
                                             entry_positions, cfg.sliding_window, None, scale)
        return out, (hk, hv, wk, wv)

    return {"full": full, "window": window}


def forward_verify_column(params, cfg, inv_freq, tokens, held, entry_positions, k_cache,
                          v_cache, page_tables, ring_k, ring_v, slots, side, live,
                          attn_impl: str = "xla", moe_impl: str = "xla"):
    """One verify column: the stack over ``tokens`` [B, W], lane ``b``'s at
    positions ``entry[b] + held[b] + w`` (``held`` [B]: the tokens the lane
    has accepted in this frame, which are the side rows it holds).  The
    frozen pages and rings and the side buffers are read; the rows' keys and
    values go to the side buffers (``side_buffers`` of ``W`` times the
    frame's columns).  Returns (logits [B, W, V], the stack's last hidden
    vectors [B, W, E], side, counts)."""
    W = tokens.shape[1]
    positions = (entry_positions + held)[:, None] + jnp.arange(W)[None, :]
    attend = verify_attends(cfg, held, entry_positions, k_cache, v_cache, page_tables,
                            ring_k, ring_v, slots, attn_impl)
    h = embed_tokens(params, cfg, tokens)
    h, side, counts = _stack(params, cfg, inv_freq, h, positions,
                             jnp.broadcast_to(live[:, None], tokens.shape), side, attend, moe_impl)
    return unembed(params, cfg, h), h, side, counts


def forward_mtp_column(params, cfg, inv_freq, hidden, next_tokens, held, entry_positions,
                       k_cache, v_cache, page_tables, side, live, attn_impl: str = "xla",
                       moe_impl: str = "xla"):
    """The module behind a verify column, over the rows the lane accepted:
    ``hidden`` [B, W, E] the stack's, ``next_tokens`` [B, W] what each row
    emitted, ``live`` [B, W] which rows count.  Its keys and values go to its
    layer of the side buffers.  Returns (its block's output [B, W, E], side,
    counts); ``mtp_logits`` of the last accepted row drafts the next column."""
    W = next_tokens.shape[1]
    positions = (entry_positions + held)[:, None] + jnp.arange(W)[None, :]
    attend = verify_attends(cfg, held, entry_positions, k_cache, v_cache, page_tables,
                            None, None, None, attn_impl)
    return _module(params, cfg, inv_freq, hidden, next_tokens, positions, live, side, attend,
                   moe_impl)


def forward_mtp_draft(params, cfg, inv_freq, hidden, emitted, accept, held, entry_positions,
                      k_cache, v_cache, page_tables, side, holds, attn_impl: str = "xla",
                      moe_impl: str = "xla"):
    """A verify column's drafting: the module over the rows accepted
    (``emitted`` [B, 2] what the two rows gave, ``accept`` [B] whether the
    second counts), its head behind the last of them, the greedy token.  On a
    device's timeline the stretch lies between the kernels ``smg.mtp.begin``
    and ``smg.mtp.end`` (``ops/pallas/marker.py``; with the XLA forms there is
    no mark).  Returns (the next draft [B] int32, side, counts)."""
    marked = attn_impl.startswith("pallas")
    if marked:
        from smg_tpu.ops.pallas.marker import mark

        interpret = attn_impl == "pallas_interpret"
        hidden = mark(hidden, "smg.mtp.begin", interpret)
    with jax.named_scope("smg.mtp"):
        rows = jnp.stack([holds, holds & accept], axis=1)
    u, side, counts = forward_mtp_column(
        params, cfg, inv_freq, hidden, emitted, held, entry_positions, k_cache, v_cache,
        page_tables, side, rows, attn_impl=attn_impl, moe_impl=moe_impl)
    with jax.named_scope("smg.mtp"):
        u_last = jnp.where(accept[:, None], u[:, 1], u[:, 0])
        draft = jnp.argmax(mtp_logits(params, cfg, u_last), axis=-1).astype(jnp.int32)
    if marked:
        draft = mark(draft, "smg.mtp.end", interpret)
    return draft, side, counts
