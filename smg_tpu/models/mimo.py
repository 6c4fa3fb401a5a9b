"""MiMo-V2-Flash: sliding-window layers beside full-attention layers, routed
experts picked by a biased score.

``model_type: mimo_v2_flash`` (``XiaomiMiMo/MiMo-V2-Flash``).  The block is
Llama's (assumed: the catalog row gives no norm placement): ``h = h +
Attn_l(RMSNorm(h))``, ``h = h + MLP_l(RMSNorm(h))``, a final RMSNorm and an
untied head.  For a token's normed hidden vector ``x`` at position ``i``,
``H`` query heads:

*Attention* of layer ``l``, full or window by ``layer_types[l]``: ``G`` key/value
heads (``num_kv_heads`` full, ``swa_num_kv_heads`` window), ``q_h = W_q,h x``
and ``k_g = W_k,g x`` of ``head_dim`` lanes, ``v_g = attention_value_scale *
W_v,g x`` of ``v_head_dim``.  Rotary, rotate-half pairing, over the first
``qk_rope_head_dim`` lanes of ``q`` and ``k`` with base ``rope_theta`` (full)
or ``swa_rope_theta`` (window); the other lanes pass.  Scores ``q_h . k_g(h),j
/ sqrt(head_dim)`` for ``j <= i`` and, in a window layer, ``j > i -
sliding_window``.  Full: a softmax.  Window: a softmax whose denominator
carries one more term, ``exp(b_h)`` for a learned ``b_h`` a head (the sink:
it takes mass and adds no value).  ``o = W_o concat_h(sum_j p_hj v_g(h),j)``.
No bias, no q/k norm.

*MLP*: where ``moe_layer_freq[l]`` is 0 a SwiGLU MLP of ``intermediate_size``.
Else ``s = sigmoid(W_r x)`` in float32 over all ``num_experts``; the ``top_k``
picked are the largest of ``s + b`` (``b`` a parameter, used to pick and not
to weigh); ``w_e = s_e / sum_picked s``; ``MLP(x) = sum_picked w_e E_e(x)``,
every expert a SwiGLU MLP of ``moe_intermediate_size``; no shared expert.
This process holds the routed experts ``cfg.held_experts`` and adds what they
give (``ops/moe.py``); nothing stands in for the chips that hold the rest.

**What a sequence holds.**  Pages for the full layers only (K of ``G x
head_dim`` lanes, V of ``G x v_head_dim``), and for the window layers a ring
of the last tokens a layer (``ops/window_attention.py``), in the slot the
sequence is given: what it holds for them does not grow with its context.

**Departures from the equations above**: none in arithmetic.  The model's
multi-token-prediction layers are drafters its own logits do not depend on;
the configuration carries no key of theirs and nothing of the kind is loaded.

**What this module serves**: ``forward_prefill``, ``forward_prefill_batched``
and ``forward_decode_horizon`` on one device; everything in
``SERVING_LIMITS`` is refused at start, not run wrong.  Layers of one kind
(full or window, dense or experts) are one parameter stack; the stacks are
scanned run by run in the pattern's order.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from smg_tpu.models.config import ModelConfig
from smg_tpu.models.llama import _mlp_residual, _norm, _write_side, embed_tokens, unembed
from smg_tpu.models.pangu_moe import (  # noqa: F401  (the runner's, by these names)
    ROUTED_COUNTS,
    merge_counts,
)
from smg_tpu.ops import moe
from smg_tpu.ops import window_attention as wa
from smg_tpu.ops.attention import (
    attention_decode_cached,
    gather_layer_pages,
    page_slots,
    scatter_kv_pages_full,
)
from smg_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict[str, Any]

# RANDOM weights (``init_params``), drawn so that what a sequence holds shows
# in the logits (PR 29's and PR 34's lesson; the readings are in PERF.md,
# Findings, PR 37).  Queries are drawn so that a layer's scores have about
# ``*_SCORE_STD`` of standard deviation: at normal 0.02 every key weighs the
# same, and at a deviation of 1 a head still averages a third of its keys,
# which gives every position of a window much the same output (measured: the
# residual stream's neighbours then agree to 0.9 and over in direction, and
# neither 16 keys beyond the window nor one wrong page of 44 moves the logits
# by more than rounding does).  At 2 and over a few keys carry a head, so
# its output says which keys it met.  How loudly each sublayer speaks into the
# residual stream is set by its output projection: an attention's at ``gain /
# sqrt(fan-in)``, the MLPs' at normal ``scale``, chosen so that the token's
# own embedding, the attention of both kinds and the dense MLP are of one
# order.  The routed experts' output projections are drawn at
# ``ROUTED_OUT_SCALE`` times ``cfg.random_routed_out_gain`` (1 unless the
# configuration says otherwise): whoever compares these weights with a
# reference in another precision chooses how loudly a pick speaks, because a
# pick that rounding moves across a near-tie of the 8th and 9th score is no
# fault and a whole pick all the same (PERF.md, Findings, PR 37).
FULL_SCORE_STD, WINDOW_SCORE_STD = 2.5, 2.0
FULL_ATTN_GAIN, WINDOW_ATTN_GAIN = 0.11, 0.08
DENSE_MLP_SCALE = 1.5e-4
ROUTED_OUT_SCALE = 4e-4
# The sink's logit a head, normal around ``SINK_MEAN``: against a window's
# scores at ``WINDOW_SCORE_STD`` it holds a tenth to a half of a head's mass.
SINK_MEAN, SINK_STD = 6.0, 0.5
# The selection bias, normal 0.1: beside sigmoid scores spread over (0.2, 0.8)
# it changes which experts are picked for most tokens.
SELECT_BIAS_STD = 0.1

SERVING_LIMITS = {
    "speculative": "mimo_v2_flash has no verify block, and its multi-token-prediction "
                   "layers are not loaded (the configuration carries no key of theirs): "
                   "nothing drafts",
    "lora": "mimo_v2_flash has no LoRA deltas on its projections",
    "embeddings": "mimo_v2_flash has no embedding forward",
    "mesh": "mimo_v2_flash runs on one device: neither the window store nor the experts' "
            "exchange between chips that hold different experts is partitioned over a mesh",
    "kv_transfer": "mimo_v2_flash cannot export a sequence: what it holds for its window "
                   "layers is not in the pages",
    "checkpoint": "mimo_v2_flash has no safetensors key map yet: it is served with "
                  "seeded random weights (--model-preset), not from --model-path",
}


# --------------------------------------------------------------------------
# the stack's shape


def layer_kinds(cfg: ModelConfig) -> list[str]:
    """``full_dense``, ``full_moe``, ``window_dense`` or ``window_moe`` for
    every layer."""
    return [("window" if t == "sliding_attention" else "full")
            + ("_moe" if m else "_dense")
            for t, m in zip(cfg.layer_types, cfg.moe_layer_freq)]


def layer_runs(cfg: ModelConfig) -> list[tuple[str, int, int, int]]:
    """The layers as runs of one kind, in order: ``(kind, first layer of the
    run in its kind's stack, layers, first layer of the run in its cache)``;
    the cache is the pages for a full layer, the rings for a window layer."""
    runs: list[list] = []
    in_stack: dict[str, int] = {}
    in_cache = {"full": 0, "window": 0}
    for kind in layer_kinds(cfg):
        where = kind.split("_")[0]
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, in_stack.get(kind, 0), 1, in_cache[where]])
        in_stack[kind] = in_stack.get(kind, 0) + 1
        in_cache[where] += 1
    return [tuple(r) for r in runs]


def prefill_workspace_bytes(cfg: ModelConfig, tokens: int, dtype: str) -> int:
    """Bytes a prefill of ``tokens`` tokens holds beside its arguments, from
    shapes and on the high side: a layer's widest activations live together
    (the dense MLP's gate, up and their product, or the queries, keys, values
    and heads' outputs, and some hidden vectors), and two blocks of scores
    with their exponentials.  Compiled for a v5e at the published widths a
    4,096-token program holds 0.54 to 0.57 GB where this says 1.37."""
    H, D, Dv = cfg.num_heads, cfg.head_dim, cfg.v_head_dim
    kw, vw = cfg.kv_lanes(True)
    mlp = 3 * max(cfg.intermediate_size, cfg.moe_intermediate_size * cfg.num_experts_per_tok)
    attention = H * (D + Dv) + 3 * (kw + vw)
    per_token = (mlp + attention + 6 * cfg.hidden_size) * jnp.dtype(dtype).itemsize
    return tokens * per_token + 2 * wa.SCORE_BLOCK_BYTES


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random weights (tests, benchmarks), at the scales the constants above
    give; norm weights 1."""
    E, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, D, Dv = cfg.num_heads, cfg.head_dim, cfg.v_head_dim
    Fm, X, Xh = cfg.moe_intermediate_size, cfg.num_experts, cfg.held_experts[1]
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 64))

    def normal(shape, scale=0.02, dt=dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32) * scale).astype(dt)

    def stack(kind: str, n: int) -> Params:
        window, routed = kind.startswith("window"), kind.endswith("moe")
        kw, vw = cfg.kv_lanes(window)
        gain = WINDOW_ATTN_GAIN if window else FULL_ATTN_GAIN
        sharp = WINDOW_SCORE_STD if window else FULL_SCORE_STD
        p = {"attn_norm": jnp.ones((n, E), dtype), "mlp_norm": jnp.ones((n, E), dtype),
             # the three input projections are stored [out, in]: compiled for
             # a v5e, a decode column copies a window layer's [in, out]
             # matrices into that layout, 121 MB a layer and column
             "wq": normal((n, H * D, E), sharp * E ** -0.5),
             "wk": normal((n, kw, E), E ** -0.5),
             "wv": normal((n, vw, E), E ** -0.5),
             "wo": normal((n, H * Dv, E), gain * (H * Dv) ** -0.5)}
        if window and cfg.swa_sink_bias:
            p["sink"] = SINK_MEAN + normal((n, H), SINK_STD, jnp.float32)
        if routed:
            p.update(router=normal((n, E, X)),
                     select_bias=normal((n, X), SELECT_BIAS_STD, jnp.float32),
                     w_gate=normal((n, Xh, E, Fm)), w_up=normal((n, Xh, E, Fm)),
                     w_down=normal((n, Xh, Fm, E),
                                   cfg.random_routed_out_gain * ROUTED_OUT_SCALE))
        else:
            p.update(w_gate=normal((n, E, F)), w_up=normal((n, E, F)),
                     w_down=normal((n, F, E), DENSE_MLP_SCALE))
        return p

    kinds = layer_kinds(cfg)
    params: Params = {"embed": normal((V, E)), "final_norm": jnp.ones((E,), dtype)}
    for kind in sorted(set(kinds)):
        params[kind] = stack(kind, kinds.count(kind))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = normal((E, V))
    return params


def logical_axes(cfg: ModelConfig) -> Params:
    """Every array replicated: this module runs on one device."""
    return jax.tree.map(lambda x: (None,) * x.ndim,
                        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))


# --------------------------------------------------------------------------
# the layer.  What differs between prefill and decode is how the queries meet
# what the sequence holds, so each forward passes that in: ``attend[kind](q, k,
# v, layer, c, state)`` puts the new keys and values where the forward keeps
# them (``c``: the layer's place in its cache) and returns the heads' outputs
# [..., H, Dv] with the state it changed.


def _rotary(x, positions, inv_freq):
    """Rotary over the first ``2 * len(inv_freq)`` lanes of every head of
    ``x`` [..., T, heads, D]; the other lanes pass."""
    n = 2 * inv_freq.shape[0]
    if n == x.shape[-1]:
        return apply_rope(x, positions, inv_freq)
    return jnp.concatenate([apply_rope(x[..., :n], positions, inv_freq), x[..., n:]], axis=-1)


@jax.named_scope("smg.attn.qkv")
def _qkv(layer: Params, cfg: ModelConfig, x, positions, inv_freq):
    """``q`` [..., H, D] and ``k`` [..., G, D] (rotated), ``v`` [..., G, Dv]
    (scaled) of the tokens ``x`` [..., E]."""
    heads = lambda y, d: y.reshape(*y.shape[:-1], -1, d)
    q = heads(jnp.einsum("...e,fe->...f", x, layer["wq"]), cfg.head_dim)
    k = heads(jnp.einsum("...e,fe->...f", x, layer["wk"]), cfg.head_dim)
    v = heads(jnp.einsum("...e,fe->...f", x, layer["wv"]), cfg.v_head_dim)
    v = v * jnp.asarray(cfg.attention_value_scale, v.dtype)
    return _rotary(q, positions, inv_freq), _rotary(k, positions, inv_freq), v


@jax.named_scope("smg.moe.residual")
def _moe_residual(h, layer: Params, experts: Params, i, cfg: ModelConfig, live, impl: str):
    """``h + sum_i w_i E_i(RMSNorm(h))`` over the held experts.  ``experts``
    holds the routed experts' weights of the whole stack, ``i`` picks this
    layer's.  ``live`` [...] marks real tokens: a padded one picks no expert.
    Returns ``h`` and the counts ``models/pangu_moe._moe_residual`` gives."""
    x = _norm(h, layer["mlp_norm"], cfg)
    flat = x.reshape(-1, x.shape[-1])
    routing = moe.route(flat, layer["router"], top_k=cfg.num_experts_per_tok,
                        scoring=cfg.moe_scoring, norm_topk=cfg.norm_topk_prob,
                        scale=cfg.routed_scaling_factor,
                        select_bias=layer["select_bias"] if cfg.moe_select_bias else None)
    alive = live.reshape(-1)
    routing = routing._replace(experts=jnp.where(alive[:, None], routing.experts, -1))
    y, (rows, hit) = moe.expert_layer(flat, routing, experts["w_gate"], experts["w_up"],
                                      experts["w_down"], cfg.held_experts, impl, layer=i)
    picks = jnp.sum(alive).astype(jnp.int32) * cfg.num_experts_per_tok
    return h + y.astype(h.dtype).reshape(h.shape), jnp.stack([picks, rows, hit, rows])


_ROUTED = ("w_gate", "w_up", "w_down")


def _stack(params: Params, cfg: ModelConfig, inv_freq, h, positions, live, state, attend,
           moe_impl: str):
    """The runs of ``layer_runs`` in turn, each one ``lax.scan`` over its
    kind's stack.  Returns ``h``, the forwards' ``state`` and the expert
    layers' counts (summed over layers, the last kept as a maximum)."""
    window_freq = jnp.asarray(rope_frequencies(cfg.rope_dim, cfg.swa_rope_theta or 10000.0))

    def layer_of(kind: str, experts, first: int, cache0: int):
        """The scan body of a run of layers of one kind."""
        window, routed = kind.startswith("window"), kind.endswith("moe")
        freq, attend_kind = (window_freq, attend["window"]) if window else (inv_freq, attend["full"])

        def body(carry, xs):
            (h, state, counts), (layer, i) = carry, xs
            with jax.named_scope("smg.attn.qkv"):
                x = _norm(h, layer["attn_norm"], cfg)
            q, k, v = _qkv(layer, cfg, x, positions, freq)
            with jax.named_scope("smg.attn.kv"):
                out, state = attend_kind(q, k, v, layer, cache0 + i, state)
            with jax.named_scope("smg.attn.out"):
                h = h + jnp.einsum("...f,fe->...e",
                                   out.astype(h.dtype).reshape(*h.shape[:-1], -1), layer["wo"])
            if routed:
                h, c = _moe_residual(h, layer, experts, first + i, cfg, live, moe_impl)
                counts = merge_counts(counts, c)
            else:
                h = _mlp_residual(h, layer, cfg)
            return (h, state, counts), None

        return body

    counts = jnp.zeros((len(ROUTED_COUNTS),), jnp.int32)
    return scan_runs(params, layer_runs(cfg), (h, state, counts), layer_of)


def scan_runs(stacks: Params, runs, carry, layer_of):
    """``runs`` (as ``layer_runs`` gives them) in turn, each one ``lax.scan``
    over its kind's stack in ``stacks``, of the body ``layer_of(kind, routed
    experts of the whole stack or None, first, cache0)``, whose ``xs`` are a
    layer's scanned weights and its index in the run.  Returns the carry."""
    for kind, first, n, cache0 in runs:
        routed = kind.endswith("moe")
        stack = stacks[kind]
        body = layer_of(kind, {k: stack[k] for k in _ROUTED} if routed else None, first, cache0)
        scanned = {k: v for k, v in stack.items() if not (routed and k in _ROUTED)}
        if (first, n) != (0, next(iter(scanned.values())).shape[0]):
            # a run that is part of its stack (the published pattern has such):
            # a slice, which XLA may copy; a run that is its whole stack is not
            scanned = jax.tree.map(lambda x: x[first:first + n], scanned)
        carry, _ = jax.lax.scan(body, carry, (scanned, jnp.arange(n)))
    return carry


def _scale(cfg: ModelConfig) -> float:
    return 1.0 / math.sqrt(cfg.head_dim)


def _sink(layer: Params, cfg: ModelConfig):
    return layer["sink"] if cfg.swa_sink_bias else None


# --------------------------------------------------------------------------
# prefill


def prefill_attends(cfg: ModelConfig, G: int, T: int, prefix_lens, t_reals, page_size: int,
                    page_tables, slots, no_ctx: bool):
    """Of chunks ``[G, T]`` behind ``prefix_lens``: the tokens' positions,
    which of them are real, and the two ``attend`` closures of a prefill over
    the state ``(k_cache, v_cache, ring_k, ring_v)``."""
    W = cfg.sliding_window
    with jax.named_scope("smg.prefill.land"):  # where the chunks' rows stand and land
        pos = prefix_lens[:, None] + jnp.arange(T)[None, :]
        real = jnp.arange(T)[None, :] < t_reals[:, None]
        ctx_lens = prefix_lens + t_reals
        dest = page_slots(page_tables, pos, real, page_size).reshape(-1)
    scale = _scale(cfg)

    def full(q, k, v, layer, c, state):
        kc, vc, rk, rv = state
        kc, vc = scatter_kv_pages_full(kc, vc, c, k.reshape(G * T, *k.shape[2:]),
                                       v.reshape(G * T, *v.shape[2:]), dest)
        if no_ctx:  # the chunk is the whole context
            k_ctx, v_ctx = k, v
        else:  # the pages hold it, the chunk's own entries among them
            kl, vl = gather_layer_pages(kc, vc, c, page_tables)  # [G, mp, ps, lanes]
            k_ctx = kl.reshape(G, -1, *k.shape[2:])
            v_ctx = vl.reshape(G, -1, *v.shape[2:])
        out = wa.attention_prefill_blocked(q, k_ctx.astype(q.dtype), v_ctx.astype(q.dtype),
                                           pos, ctx_lens, scale)
        return out, (kc, vc, rk, rv)

    def window(q, k, v, layer, c, state):
        kc, vc, rk, rv = state
        if no_ctx:
            before = lambda x: jnp.zeros((G, W, *x.shape[2:]), x.dtype)
            prev_k, prev_v = before(k), before(v)
        else:  # the window before the chunk, from the ring, and never the context
            prev_k = wa.read_ring_tail(rk, c, slots, prefix_lens, W).reshape(G, W, *k.shape[2:])
            prev_v = wa.read_ring_tail(rv, c, slots, prefix_lens, W).reshape(G, W, *v.shape[2:])
        out = wa.window_attention_prefill(q, k, v, prev_k, prev_v, pos, W, _sink(layer, cfg), scale)
        rk, rv = wa.write_ring_chunk(rk, rv, c, k.reshape(G, T, -1), v.reshape(G, T, -1),
                                     slots, prefix_lens, t_reals)
        return out, (kc, vc, rk, rv)

    return pos, real, {"full": full, "window": window}


@jax.named_scope("smg.lm_head")
def last_real(h, t_reals):
    """``h`` [G, T, E] at each row's last real token, [G, E]."""
    return jnp.take_along_axis(
        h, jnp.maximum(t_reals - 1, 0)[:, None, None].astype(jnp.int32), axis=1)[:, 0]


def _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache, v_cache,
             page_tables, ring_k, ring_v, slots, no_ctx: bool, moe_impl: str):
    """Solo and grouped prefill: ``tokens`` [G, T], one row a sequence."""
    pos, real, attend = prefill_attends(cfg, *tokens.shape, prefix_lens, t_reals,
                                        k_cache.shape[2], page_tables, slots, no_ctx)
    h = embed_tokens(params, cfg, tokens)
    h, state, _counts = _stack(params, cfg, inv_freq, h, pos, real,
                               (k_cache, v_cache, ring_k, ring_v), attend, moe_impl)
    return (unembed(params, cfg, last_real(h, t_reals)), *state)


def forward_prefill(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [T] padded to bucket
    prefix_len: jnp.ndarray,  # scalar: tokens of the sequence before this chunk
    t_real: jnp.ndarray,  # scalar: valid new tokens (<= T)
    k_cache: jnp.ndarray,  # [full layers, P, ps, K lanes]: the full layers' pages
    v_cache: jnp.ndarray,  # [full layers, P, ps, V lanes]
    page_table: jnp.ndarray,  # [mp]
    ring_k: jnp.ndarray,  # [window layers, slots, R, K lanes]: the window layers' rings
    ring_v: jnp.ndarray,
    slot: jnp.ndarray,  # scalar: the sequence's slot
    attn_impl: str = "xla",  # prefill attention has one form; kept for the runner
    moe_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret"
):
    """One chunk of one sequence, behind the prefix its pages and its rings
    hold.  Returns (last_token_logits [V], k_cache, v_cache, ring_k, ring_v)."""
    logits, *rest = _prefill(
        params, cfg, inv_freq, tokens[None], prefix_len[None], t_real[None], k_cache, v_cache,
        page_table[None], ring_k, ring_v, slot[None], False, moe_impl)
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
    ring_k: jnp.ndarray,
    ring_v: jnp.ndarray,
    slots: jnp.ndarray,  # [G]; a padded row names slot 0
    no_ctx: bool = False,  # static: every row starts its sequence
    moe_impl: str = "xla",
    attn_impl: str = "xla",  # prefill attention has one form; kept for the runner
):
    """Several sequences' chunks in one call.  Returns (logits [G, V],
    k_cache, v_cache, ring_k, ring_v)."""
    return _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache, v_cache,
                    page_tables, ring_k, ring_v, slots, no_ctx, moe_impl)


# --------------------------------------------------------------------------
# decode


def decode_attends(cfg: ModelConfig, step_idx, entry_positions, k_cache, v_cache, page_tables,
                   ring_k, ring_v, slots, attn_impl: str) -> dict:
    """The two ``attend`` closures of decode column ``step_idx`` over the
    frame's side buffers ``(hk, hv, wk, wv)``."""
    scale = _scale(cfg)
    kernel = attn_impl.startswith("pallas")
    interpret = attn_impl == "pallas_interpret"

    def full(q, k, v, layer, c, side):
        hk, hv, wk, wv = side
        hk_l, hv_l, (hk, hv) = _write_side((hk, hv), k, v, c, step_idx)
        if kernel:
            from smg_tpu.ops.pallas.decode_attention import paged_attention_decode_cached

            out = paged_attention_decode_cached(
                q, k_cache, v_cache, hk_l, hv_l, step_idx + 1, c, page_tables,
                entry_positions, scale, interpret=interpret)
        else:
            out = attention_decode_cached(q, k_cache, v_cache, hk_l, hv_l, step_idx + 1, c,
                                          page_tables, entry_positions, scale)
        return out, (hk, hv, wk, wv)

    def window(q, k, v, layer, c, side):
        hk, hv, wk, wv = side
        wk_l, wv_l, (wk, wv) = _write_side((wk, wv), k, v, c, step_idx)
        if kernel:
            from smg_tpu.ops.pallas.window_decode import window_attention_decode

            out = window_attention_decode(
                q, ring_k, ring_v, wk_l, wv_l, step_idx + 1, c, slots, entry_positions,
                cfg.sliding_window, _sink(layer, cfg), scale, interpret=interpret)
        else:
            out = wa.window_attention_decode(
                q, ring_k, ring_v, wk_l, wv_l, step_idx + 1, c, slots, entry_positions,
                cfg.sliding_window, _sink(layer, cfg), scale)
        return out, (hk, hv, wk, wv)

    return {"full": full, "window": window}


def forward_decode_horizon(
    params: Params,
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    tokens: jnp.ndarray,  # [B] token fed this column
    positions: jnp.ndarray,  # [B] absolute position of that token
    entry_positions: jnp.ndarray,  # [B] tokens held at the frame's entry
    step_idx: jnp.ndarray,  # scalar: column within the frame
    k_cache: jnp.ndarray,  # read-only during the frame
    v_cache: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, mp]
    ring_k: jnp.ndarray,  # read-only during the frame
    ring_v: jnp.ndarray,
    slots: jnp.ndarray,  # [B]; a padded row names slot 0
    side: tuple,  # (hk, hv, wk, wv): the frame's side buffers, full [full layers,
    # B, N, lanes] and window [window layers, B, N, lanes]
    live: jnp.ndarray,  # [B] bool: the lane holds a sequence
    attn_impl: str = "xla",  # "xla" | "pallas" | "pallas_interpret"
    moe_impl: str = "xla",
):
    """One decode column.  The frozen pages and rings and the side buffers
    are read, the column's keys and values go to the side buffers (the caller
    lands them: ``ops.attention.land_side_buffers``,
    ``ops.window_attention.land_ring_side``).  Returns (logits [B, V], side,
    counts): int32 ``[picks, picks on held experts, held experts hit, most
    picks on held experts in one layer]`` of this column."""
    attend = decode_attends(cfg, step_idx, entry_positions, k_cache, v_cache, page_tables,
                            ring_k, ring_v, slots, attn_impl)
    h = embed_tokens(params, cfg, tokens)
    h, side, counts = _stack(params, cfg, inv_freq, h, positions, live, side, attend, moe_impl)
    return unembed(params, cfg, h), side, counts


def side_buffers(cfg: ModelConfig, B: int, N: int, dtype) -> tuple:
    """A frame's empty side buffers, ``(hk, hv, wk, wv)``."""
    n_window = cfg.num_window_layers
    (fk, fv), (wk, wv) = cfg.kv_lanes(False), cfg.kv_lanes(True)
    zeros = lambda layers, lanes: jnp.zeros((layers, B, N, lanes), dtype)
    return (zeros(cfg.num_cache_layers, fk), zeros(cfg.num_cache_layers, fv),
            zeros(n_window, wk), zeros(n_window, wv))
