"""GLM-5.2: latent attention that reads a learned selection of the cache, an
indexer whose selection several layers share, routed experts beside a shared one.

``model_type: glm_moe_dsa`` (``zai-org/GLM-5.2``).  ``N`` is RMSNorm, ``x =
N(h)`` the layer's normed input, ``t`` a query position, ``s <= t`` a cached one.
The block is pre-norm with no sandwich norms: ``h = h + Attn(N(h))``; ``h = h +
MLP(N(h))``.

*Attention*: ``models/pangu_moe.py``'s latent attention (``latent_attention``,
``_latent_qkv``): ``c_q = N(W_dq x_t)``, ``[q_nope | q_pe]_h = W_uq c_q``,
``[c_s | k_pe,s] = W_dkv x_s`` with ``c_s = N(c_s)``, rotary on ``q_pe`` and
``k_pe`` (published pairs interleaved), scores over ``sqrt(dn + dr)``, and what
a token leaves in the cache is ``[c_s | k_pe,s]``.  **It reads ``S_t`` alone**:
``o_{t,h} = sum_{s in S_t} softmax_{s in S_t}(q_{t,h} . k_{s,h}) v_{s,h}``.

*The indexer* of a layer whose ``indexer_types`` entry is ``full``: ``q^I_{t,j}
= W^I_q c_q`` (``index_n_heads`` heads of ``index_head_dim``, from the same
``c_q``), ``k^I_s = LayerNorm(W^I_k x_s)`` (one key for all heads, weight and
bias), rotary on the first ``qk_rope_head_dim`` lanes of both, ``w_t = W^I_w
x_t`` times ``index_n_heads^-1/2 index_head_dim^-1/2``; ``I_{t,s} = sum_j
w_{t,j} ReLU(q^I_{t,j} . k^I_s)`` in float32; ``S_t`` the ``min(t + 1,
index_topk)`` positions ``s <= t`` of largest ``I_{t,s}``, equal scores to the
lower position.  ``k^I_s`` is cached: a second paged buffer on the latent
cache's own page tables, one layer for every ``full`` layer
(``ops/sparse_attention.py``).  A ``shared`` layer has no indexer, no index keys
and no indexer weights: its ``S_t`` is that of the nearest ``full`` layer before
it, which the stack's scan carries.

*MLP*: the leading ``first_k_dense_replace`` layers a SwiGLU MLP of
``intermediate_size``; the others ``sum_i w_i E_i(x) + E_shared(x)``: ``s =
sigmoid(W_r x)`` in float32 over all the router's experts, the ``top_k``
largest of ``s + b`` (``b`` a selection bias an expert: it picks and does not
weigh), ``w_i = routed_scaling_factor * s_i / sum_topk s``, every expert a
SwiGLU MLP of ``moe_intermediate_size``.  Experts held as in
``models/pangu_moe.py``: the router keeps its width, the layer adds what the
held experts give and the shared expert, and nothing stands in for the chips
that hold the rest.

**Departures from the equations above**, none in arithmetic:

- prefill runs attention expanded under the selection as a mask, at the dense
  path's cost; decode runs it absorbed over the gathered selection
  (``ops/sparse_attention.attend_selected``);
- the index scores' products take the cached keys as the cache holds them
  (``cfg.dtype``) and accumulate in float32;
- ``W_uq`` and ``W_dkv`` are stored in ``pangu_moe``'s parts, and the rotary
  rows of ``W_uq``, ``W_dkv``, ``W^I_q`` and ``W^I_k`` (with the index key's norm
  weight and bias) de-interleaved, so that rotate-half over the stored lanes
  is the published interleaved pairing;
- the published inference code turns ``q^I`` and ``k^I`` by a Hadamard matrix
  (orthogonal: it cancels in the product) and stores ``k^I`` in 8 bits (no part
  of a bfloat16 configuration): neither is here;
- the indexer's weights are one stack over the ``full`` layers, which a layer
  reads by its place among them; the dense and the expert layers are two
  stacks, each one scan whose step takes a fresh selection or the carried one
  by the layer's switch.

The next-token module (``num_nextn_predict_layers``) is a drafter the model's
own logits do not depend on; it is neither loaded nor served.

**What this module serves**: ``forward_prefill``, ``forward_prefill_batched``
and ``forward_decode_horizon`` on one device, which are ``pangu_moe``'s over
this file's ``_stack``: the second cache buffer (``v_cache``) holds the index
keys, and the decode column's side buffer is a pair (latent entries, index
keys).  A radix prefix is reused as any latent prefix is: a page holds its
tokens' entries and index keys alike.  Everything in ``SERVING_LIMITS`` is
refused at start, not run wrong.
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
from smg_tpu.models.llama import _mlp_residual, _norm
from smg_tpu.models.longcat_flash import _swiglu_size, route_lanes
from smg_tpu.models.pangu_moe import cache_lanes  # noqa: F401  (the callers', by this name)
from smg_tpu.models.pangu_moe import latent_attention, shared_expert
from smg_tpu.ops import moe
from smg_tpu.ops.attention import page_slots
from smg_tpu.ops.latent_attention import SCORE_BLOCK_BYTES, scatter_entries
from smg_tpu.ops.rope import apply_rope
from smg_tpu.ops.sparse_attention import select_decode, select_prefill, selected_slots

Params = dict[str, Any]

# RANDOM weights (``init_params``), drawn so that every part of the block
# speaks and a comparison of logits against a float32 reference hears a wrong
# latent page, a wrong index-key page and a wrong selection.  Sizes are in
# units of a normed vector's lanes (mean square 1).
#
# *The attention* is ``models/longcat_flash.py``'s drawing (projections at 1 /
# sqrt(fan-in), ``W_o`` at ``ATTN_OUT_GAIN`` over that, no norm behind it) with
# one change.  Under independent random weights 2,048 of 16,000 cached tokens
# are a random eighth of a sum in which no token weighs much: one wrong page
# then moves a logit by thousandths of its deviation, and a selector that chose
# any other eighth would read the same.  A trained indexer is trained to find
# the tokens the attention weighs; these weights are drawn so that it does:
# every head's rotary query is ``SHARED_SCORE_STD`` of a part all heads share
# (``q_pe_h = a g + own_h``: the scores' shared part has that deviation, what is
# a head's own 1; ``SHARED_SCORE_STD_BEHIND`` in a layer that reads another
# layer's selection), the index key's rotary lanes are the attention's rotary key
# (``W^I_k``'s first lanes are ``W_dk_pe``), and the index queries' rotary lanes
# the shared part ``g``, so ``I[t, s]`` rises with the score all heads share and
# the selection holds the tokens that carry the softmax.  The other lanes of
# both, a head's own part of the index queries and the key norm's bias are
# noise of ``INDEX_NOISE`` beside 1: they speak and do not decide.  A layer that
# reads another layer's selection shares less (``SHARED_SCORE_STD_BEHIND``):
# with bfloat16 products every row flips some six of its 2,048 picks at the
# selection's edge, where the layer that chose weighs least and a layer behind
# it, whose scores are its own, may weigh most.  How the two were set, on a v5e
# (PERF.md, Findings, PR 55): at 2.0 in every layer one sound row in nine read
# 0.41-0.45 of its deviation behind 4 k-9 k tokens; at 1.75 and 0.5 all read
# 0.04-0.09 there but below ``index_topk``, where every token is selected, one
# wrong page of 44 read 0.30-1.6 by whether it held a token all heads weigh
# (the harness's own control, at its tolerance in one seed of six); at 1.5 and
# 1.0 that control reads 0.45-1.14, a sound row 0.04-0.09 at every length and
# the controls past 2,048 tokens 0.76-2.7.  Louder attention behind
# (``W_o`` at 16 or 32 there) or quieter experts pull every reading towards
# 0.3: sixteen tokens of 700 are a fifth of an even sum, whatever its size.
#
# *The head weights* ``w_t = W^I_w x_t`` have to be positive for that (a
# negative sum of weights selects the lowest scores).  A projection of a normed
# vector is positive only over lanes that are: the last ``CONST_LANES`` lanes
# before the routing lanes are ``+EMBED_STD`` in every token's embedding and
# written by nothing else (``W_o`` and every ``W_down`` have zeros in those
# columns), ``W^I_w`` reads 1 from them and ``INDEX_WEIGHT_NOISE`` from the
# routing lanes (a token's signs), and nothing from the rest of the stream.
#
# *The routers* read the routing lanes alone, as ``models/longcat_flash.py``'s
# do and for its reason (a pick is a step; routers that read the stream pick
# otherwise in bfloat16 than in float32 in one token-layer of twenty).  The
# selection bias is small and not zero.  A routed expert's result is
# ``ROUTED_OUT`` times its input's size; a token sends one pick in two layers
# to this chip's 16 of 256.
EMBED_STD = 0.02
SHARED_SCORE_STD = 1.5
SHARED_SCORE_STD_BEHIND = 1.0
ATTN_OUT_GAIN = 4.0
INDEX_NOISE = 0.3
INDEX_WEIGHT_NOISE = 0.3
CONST_LANES = 8
ROUTER_GAIN = 2.0
ROUTED_OUT = 6.0
SELECT_BIAS_STD = 2e-4


SERVING_LIMITS = {
    "speculative": "glm_moe_dsa has no verify block, and its next-token prediction module "
                   "(num_nextn_predict_layers) is not loaded: nothing drafts, and a verify "
                   "column of several rows a lane through the selector does not exist",
    "lora": "glm_moe_dsa has no LoRA deltas on its projections",
    "embeddings": "glm_moe_dsa has no embedding forward",
    "mesh": "glm_moe_dsa runs on one device: the experts' exchange between chips "
            "that hold different experts does not exist yet",
    "kv_transfer": "glm_moe_dsa cannot export a sequence: the transfer carries K "
                   "and V buffers and its cache has a latent buffer and an index-key buffer",
    "checkpoint": "glm_moe_dsa has no safetensors key map yet: it is served with "
                  "seeded random weights (--model-preset), not from --model-path",
}

#: the frame's counts: ``pangu_moe``'s four, the rows that attended (live
#: lanes, once a column), those behind more than ``index_topk`` tokens, and the
#: cached tokens the indexers scored (a lane's context, once a ``full`` layer)
ROUTED_COUNTS = (*pangu_moe.ROUTED_COUNTS, "dsa_rows", "dsa_rows_selecting",
                 "dsa_index_tokens_scored")


def index_layers(cfg: ModelConfig) -> "tuple[np.ndarray, np.ndarray]":
    """For every layer: whether it has an indexer, and the place among the
    ``full`` layers of the one whose selection it reads (its own, or the
    nearest before it)."""
    full = np.array([t == "full" for t in cfg.indexer_types])
    return full, np.cumsum(full) - 1


def prefill_workspace_bytes(cfg: ModelConfig, tokens: int, dtype: str, context: int = 0) -> int:
    """``pangu_moe.prefill_workspace_bytes`` at this model's widths, and the
    selector's: one block of the indexer's scores by head with its rectified
    copy and the row's integer image (float32, ``SCORE_BLOCK_BYTES`` each),
    the selection of ``tokens`` queries over ``context`` positions as the scan
    carries it (a byte a pair, the carried one and the fresh one), and the
    context's index keys gathered."""
    return (pangu_moe.prefill_workspace_bytes(cfg, tokens, dtype) + 3 * SCORE_BLOCK_BYTES
            + 2 * tokens * max(context, tokens)
            + max(context, tokens) * cfg.index_head_dim * jnp.dtype(dtype).itemsize)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random weights (tests, benchmarks), drawn as the comment above says;
    norm weights 1."""
    E, F, V, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
    H, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    Fm, X, Xh = cfg.moe_intermediate_size, cfg.num_experts, cfg.held_experts[1]
    Fs, Ld = cfg.n_shared_experts * Fm, cfg.first_k_dense_replace
    J, D = cfg.index_n_heads, cfg.index_head_dim
    Lm = L - Ld
    R = route_lanes(E)
    C = min(CONST_LANES, R)
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 96))
    out_scale = 0.02 / math.sqrt(2 * L)
    # the reserved lanes hear no sublayer: only the embedding writes them
    speaks = (jnp.arange(E) < E - R - C).astype(jnp.float32)
    # the scores' shared part: scale 1 / sqrt(dn + dr) over dr rotary lanes
    full, _ = index_layers(cfg)
    shared = jnp.where(jnp.asarray(full), SHARED_SCORE_STD, SHARED_SCORE_STD_BEHIND) \
        * math.sqrt((dn + dr) / dr)

    def normal(shape, scale=0.02, dtype=dtype, mask=None):
        x = jax.random.normal(next(ks), shape, jnp.float32) * scale
        return (x if mask is None else x * mask).astype(dtype)

    def attention(lo, n):
        ones = lambda *shape: jnp.ones((n, *shape), dtype)
        g = jax.random.normal(next(ks), (n, dr, 1, rq), jnp.float32) * rq ** -0.5
        own = jax.random.normal(next(ks), (n, dr, H, rq), jnp.float32) * rq ** -0.5
        return {
            "attn_norm": ones(E), "mlp_norm": ones(E),
            "w_dq": normal((n, E, rq), E ** -0.5), "q_norm": ones(rq),
            "w_uq_nope": normal((n, H * dn, rq), rq ** -0.5),
            "w_uq_pe": (shared[lo:lo + n, None, None, None] * g + own).astype(dtype),
            "w_dkv": normal((n, E, rkv), E ** -0.5), "kv_norm": ones(rkv),
            "w_dk_pe": normal((n, E, dr), E ** -0.5),
            "w_uk": normal((n, H, rkv, dn), rkv ** -0.5),
            "w_uv": normal((n, H, rkv, dv), rkv ** -0.5),
            "wo": normal((n, H * dv, E), ATTN_OUT_GAIN * (H * dv) ** -0.5, mask=speaks),
        }, g[:, :, 0]  # the shared part [n, dr, rq]

    dense, g_dense = attention(0, Ld)
    sparse, g_sparse = attention(Ld, Lm)
    at = np.flatnonzero(full)
    # the indexers read their own layers' shared query part and rotary key
    g = jnp.concatenate([g_dense, g_sparse])[at]  # [Lf, dr, rq]
    k_pe = jnp.concatenate([dense["w_dk_pe"], sparse["w_dk_pe"]]).astype(jnp.float32)[at]
    Lf = len(at)
    noise = lambda shape, scale: jax.random.normal(next(ks), shape, jnp.float32) * scale
    # by head, as XLA:TPU lays the product out (as [rq, J, D] it is copied a launch)
    wq = noise((Lf, J, rq, D), INDEX_NOISE * rq ** -0.5)
    wq = wq.at[..., :dr].add(jnp.moveaxis(g, 1, 2)[:, None, :, :])
    wk = noise((Lf, E, D), INDEX_NOISE * E ** -0.5).at[..., :dr].set(k_pe)
    reserved = jnp.arange(E) >= E - R - C
    ww = jnp.where((jnp.arange(E) < E - R)[None, :, None], 1.0 / (C * EMBED_STD),
                   noise((Lf, E, J), INDEX_WEIGHT_NOISE / (EMBED_STD * R ** 0.5)))
    ww = jnp.where(reserved[None, :, None], ww, 0.0)

    signs = jnp.where(jax.random.bernoulli(next(ks), 0.5, (V, R)), EMBED_STD, -EMBED_STD)
    embed = normal((V, E), EMBED_STD).at[:, E - R:].set(signs.astype(dtype))
    embed = embed.at[:, E - R - C:E - R].set(EMBED_STD)
    depth = jnp.sqrt(jnp.arange(Ld, L) + 1.0)[:, None, None]
    router = jnp.zeros((Lm, E, X), dtype).at[:, E - R:].set(
        normal((Lm, R, X), ROUTER_GAIN / (EMBED_STD * R ** 0.5), mask=depth))
    down = ROUTED_OUT / (_swiglu_size(0.02 * E ** 0.5) * Fm ** 0.5)
    return {
        "embed": embed,
        "dense": {**dense, "w_gate": normal((Ld, E, F)), "w_up": normal((Ld, E, F)),
                  "w_down": normal((Ld, F, E), out_scale, mask=speaks)},
        "moe": {**sparse, "router": router,
                "select_bias": normal((Lm, X), SELECT_BIAS_STD, jnp.float32),
                "ws_gate": normal((Lm, E, Fs)), "ws_up": normal((Lm, E, Fs)),
                "ws_down": normal((Lm, Fs, E), out_scale, mask=speaks)},
        "experts": {"w_gate": normal((Lm, Xh, E, Fm)), "w_up": normal((Lm, Xh, E, Fm)),
                    "w_down": normal((Lm, Xh, Fm, E), down, mask=speaks)},
        "indexer": {"wq": wq.astype(dtype), "wk": wk.astype(dtype), "ww": ww.astype(dtype),
                    "k_norm": jnp.ones((Lf, D), dtype),
                    "k_bias": normal((Lf, D), INDEX_NOISE)},
        "final_norm": jnp.ones((E,), dtype),
        "lm_head": normal((E, V)),
    }


def logical_axes(cfg: ModelConfig) -> Params:
    """Every array replicated: this module runs on one device."""
    return jax.tree.map(lambda x: (None,) * x.ndim,
                        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))


@jax.named_scope("smg.moe.counts")
def merge_counts(total, new):
    """The counts of one more layer, or column: all add up but ``rows_max``,
    which is kept as a maximum."""
    return jnp.where(np.array([name != "rows_max" for name in ROUTED_COUNTS]), total + new,
                     jnp.maximum(total, new))


# --------------------------------------------------------------------------
# the indexer


@jax.named_scope("smg.mla.index.k")
def index_key(ix, cfg: ModelConfig, x, positions, inv_freq):
    """``k^I = LayerNorm(W^I_k x)`` [..., D] of the normed tokens ``x`` [...,
    E], its first ``qk_rope_head_dim`` lanes rotated at ``positions``.
    ``ix(name)`` gives a matrix of this layer's indexer."""
    k = jnp.einsum("...e,ed->...d", x, ix("wk")).astype(jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    k = (k * ix("k_norm").astype(jnp.float32) + ix("k_bias").astype(jnp.float32)).astype(x.dtype)
    dr = cfg.qk_rope_head_dim
    turned = apply_rope(k[..., None, :dr], positions, inv_freq)[..., 0, :]
    return jnp.concatenate([turned, k[..., dr:]], axis=-1)


@jax.named_scope("smg.mla.index.q")
def index_query(ix, cfg: ModelConfig, x, c_q, positions, inv_freq):
    """``q^I`` [..., J, D] (rotary lanes turned) from ``c_q`` [..., rq] and the
    heads' weights ``w`` [..., J] (float32, both scales in) from ``x``."""
    q = jnp.einsum("...r,jrd->...jd", c_q, ix("wq"))
    dr = cfg.qk_rope_head_dim
    q = jnp.concatenate([apply_rope(q[..., :dr], positions, inv_freq), q[..., dr:]], axis=-1)
    w = jnp.einsum("...e,ej->...j", x, ix("ww"), preferred_element_type=jnp.float32)
    return q, w * (cfg.index_n_heads * cfg.index_head_dim) ** -0.5


# --------------------------------------------------------------------------
# the layers


@jax.named_scope("smg.moe.residual")
def _moe_residual(h, layer: Params, experts: Params, i, cfg: ModelConfig, live, impl: str):
    """``h + sum_i w_i E_i(x) + E_shared(x)`` over the held experts, ``x =
    N(h)``: ``pangu_moe._moe_residual`` with the selection bias and without
    the norm behind it.  Returns ``h`` and ``pangu_moe``'s four counts."""
    x = _norm(h, layer["mlp_norm"], cfg)
    flat = x.reshape(-1, x.shape[-1])
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


def _stack(params: Params, cfg: ModelConfig, inv_freq, h, positions, live, state, attend,
           moe_impl: str, *, begin, index, end):
    """Both parameter stacks in turn, each one ``lax.scan``.  ``begin(state)``
    makes what the scans carry of the forward's ``state`` (the caches or side
    buffers and an empty selection); ``index(ix, x, c_q, fresh, place,
    carried)`` gives it as a layer leaves it: with the index keys and the
    selection of the indexer ``ix`` (``ix(name)`` a matrix of it, ``place``
    among the ``full`` layers) where the layer has one (``fresh``), else as it
    came, so that a ``shared`` layer reads the selection before it; ``end``
    takes the selection off again.  Returns ``h``, the state and the counts
    (``pangu_moe``'s four; the forwards add the selector's)."""
    Ld = cfg.first_k_dense_replace
    full, place = index_layers(cfg)
    indexer = params["indexer"]

    @jax.named_scope("smg.mla.block")
    def attention(h, layer, l, fresh, at, carried):
        def choose(x, c_q, carried):
            mine = lambda name: jax.lax.dynamic_index_in_dim(indexer[name], at, 0, False)
            return index(mine, x, c_q, fresh, at, carried)

        def read(q_nope, q_pe, entry, layer, l, carried):
            *held, select = carried
            out, kept = attend(q_nope, q_pe, entry, layer, l, held[0], select=select)
            return out, (kept, *held[1:], select)

        o, carried = latent_attention(layer, cfg, _norm(h, layer["attn_norm"], cfg), positions,
                                      inv_freq, read, l, carried, index=choose)
        return h + o, carried

    def dense(carry, xs):
        (h, carried), (layer, l, fresh, at) = carry, xs
        h, carried = attention(h, layer, l, fresh, at, carried)
        return (_mlp_residual(h, layer, cfg), carried), None

    experts = params["experts"]

    def expert(carry, xs):
        (h, carried, counts), (layer, i, fresh, at) = carry, xs
        h, carried = attention(h, layer, Ld + i, fresh, at, carried)
        h, c = _moe_residual(h, layer, experts, i, cfg, live, moe_impl)
        return (h, carried, pangu_moe.merge_counts(counts, c)), None

    switch = lambda lo, hi: (jnp.asarray(full[lo:hi]), jnp.asarray(place[lo:hi], jnp.int32))
    (h, carried), _ = jax.lax.scan(
        dense, (h, begin(state)), (params["dense"], jnp.arange(Ld), *switch(0, Ld)))
    (h, carried, counts), _ = jax.lax.scan(
        expert, (h, carried, jnp.zeros((len(pangu_moe.ROUTED_COUNTS),), jnp.int32)),
        (params["moe"], jnp.arange(cfg.num_layers - Ld), *switch(Ld, cfg.num_layers)))
    return h, end(carried), counts


# --------------------------------------------------------------------------
# prefill: the selection as a mask


def _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache, v_cache, page_tables,
             no_ctx: bool, moe_impl: str):
    G, T = tokens.shape
    ps = k_cache.shape[2]
    S = T if no_ctx else page_tables.shape[1] * ps
    with jax.named_scope("smg.prefill.land"):  # as ``pangu_moe._prefill`` has them
        pos = prefix_lens[:, None] + jnp.arange(T)[None, :]
        real = jnp.arange(T)[None, :] < t_reals[:, None]
        ctx_lens = prefix_lens + t_reals
        dest = page_slots(page_tables, pos, real, ps).reshape(-1)

    def index(ix, x, c_q, fresh, place, carried):
        cache, keys, select = carried
        key = index_key(ix, cfg, x, pos, inv_freq)
        with jax.named_scope("smg.mla.index.k"):
            # a layer without an indexer writes the garbage page: the caches
            # stay out of the switch, which would copy what it passes on
            keys = scatter_entries(keys, place, key.reshape(G * T, -1), jnp.where(fresh, dest, 0))

        def choose(_):
            with jax.named_scope("smg.mla.index.k"):
                # the chunk is the whole context, or the pages hold it
                ctx = key if no_ctx else \
                    keys[place, page_tables].reshape(G, S, -1).astype(key.dtype)
            q, w = index_query(ix, cfg, x, c_q, pos, inv_freq)
            return select_prefill(q, w, ctx, pos, ctx_lens, cfg.index_topk)

        return cache, keys, jax.lax.cond(fresh, choose, lambda _: select, None)

    stack = partial(_stack, index=index,
                    begin=lambda cache: (cache, v_cache, jnp.zeros((G, T, S), jnp.bool_)),
                    end=lambda carried: carried[:2])
    return pangu_moe._prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals, k_cache,
                              page_tables, no_ctx, moe_impl, stack)


def forward_prefill(params: Params, cfg: ModelConfig, inv_freq, tokens, prefix_len, t_real,
                    k_cache, v_cache, page_table, attn_impl: str = "xla",
                    moe_impl: str = "xla", **unserved):
    """``pangu_moe.forward_prefill`` with ``v_cache`` [full layers, P, ps, D]
    the index keys, written where the entries are.  Returns (logits [V],
    k_cache, v_cache)."""
    pangu_moe._refuse(cfg, unserved)
    logits, (k_cache, v_cache) = _prefill(
        params, cfg, inv_freq, tokens[None], prefix_len[None], t_real[None], k_cache, v_cache,
        page_table[None], False, moe_impl)
    return logits[0], k_cache, v_cache


def forward_prefill_batched(params: Params, cfg: ModelConfig, inv_freq, tokens, prefix_lens,
                            t_reals, k_cache, v_cache, page_tables, no_ctx: bool = False,
                            moe_impl: str = "xla", attn_impl: str = "xla", **unserved):
    """``pangu_moe.forward_prefill_batched`` likewise; cold rows attend under
    the mask in XLA's form (the online-softmax kernel takes none)."""
    pangu_moe._refuse(cfg, unserved)
    logits, (k_cache, v_cache) = _prefill(params, cfg, inv_freq, tokens, prefix_lens, t_reals,
                                          k_cache, v_cache, page_tables, no_ctx, moe_impl)
    return logits, k_cache, v_cache


# --------------------------------------------------------------------------
# decode: the selection as a list of places


def forward_decode_horizon(params: Params, cfg: ModelConfig, inv_freq, tokens, positions,
                           entry_positions, step_idx, caches, page_tables, side, live,
                           attn_impl: str = "xla", moe_impl: str = "xla"):
    """One decode column: ``pangu_moe.forward_decode_horizon`` with ``caches``
    the pair (latent entries, index keys), read-only during the frame, and
    ``side`` the pair of side buffers ([L, B, N, W], [full layers, B, N, D]):
    a frame's fresh tokens are scored and chosen with the cached ones.
    Returns (logits [B, V], side, counts [len(ROUTED_COUNTS)])."""
    k_cache, keys = caches
    B, mp = page_tables.shape
    S = mp * k_cache.shape[2]
    N = side[0].shape[2]
    K = min(cfg.index_topk, S + N)

    def index(ix, x, c_q, fresh, place, carried):
        entries, own, select = carried

        def choose(own):  # the side buffer is small: it may pass through the switch
            key = index_key(ix, cfg, x[:, None], positions[:, None], inv_freq)  # [B, 1, D]
            with jax.named_scope("smg.mla.index.k"):
                own = jax.lax.dynamic_update_slice(own, key[None].astype(own.dtype),
                                                   (place, 0, step_idx, 0))
                mine = jax.lax.dynamic_index_in_dim(own, place, 0, keepdims=False)
                ctx = keys[place, page_tables].reshape(B, S, -1)
            q, w = index_query(ix, cfg, x, c_q, positions, inv_freq)
            ids, chosen = select_decode(q, w, ctx, mine, entry_positions, step_idx + 1,
                                        cfg.index_topk)
            return own, selected_slots(page_tables, ids, chosen, k_cache.shape[2], N)

        own, select = jax.lax.cond(fresh, choose, lambda own: (own, select), own)
        return entries, own, select

    nothing = (jnp.zeros((B, K), jnp.int32), jnp.zeros((B, K), jnp.bool_),
               jnp.zeros((B, N), jnp.bool_))
    stack = partial(_stack, index=index, begin=lambda side: (*side, nothing),
                    end=lambda carried: carried[:2])
    logits, side, counts = pangu_moe.forward_decode_horizon(
        params, cfg, inv_freq, tokens, positions, entry_positions, step_idx, k_cache,
        page_tables, side, live, attn_impl=attn_impl, moe_impl=moe_impl, stack=stack)
    with jax.named_scope("smg.moe.counts"):
        context = jnp.where(live, positions + 1, 0)
        counts = jnp.concatenate([counts, jnp.stack([
            jnp.sum(live), jnp.sum(context > cfg.index_topk),
            jnp.sum(context) * cfg.num_index_layers]).astype(jnp.int32)])
    return logits, side, counts
