"""A learned selector over the latent cache: the indexer's scores, the exact
``k`` largest of each query's row, and decode attention over the selection.

A layer with an indexer (``models/glm_moe_dsa.py`` has the equations) scores
every cached token ``s <= t`` for a query at ``t``: ``I[t, s] = sum_j w[t, j] *
ReLU(q[t, j] . k[s])`` over the indexer's heads ``j``, in float32, where
``k[s]`` is the token's **index key**, one vector a token and layer with an
indexer, cached in a paged buffer of its own on the latent cache's page tables.
Attention then reads the ``min(t + 1, k)`` tokens of largest ``I[t, s]`` and no
others; ties go to the lower position.

Two forms of the same selection:

- ``select_mask`` (prefill): the ``k``-th largest of a row is found without a
  sort, by 32 compare-and-count passes over the order-preserving integer image
  of the float32 scores (``kth_largest_key``), and the selection is ``I >
  tau``, plus the first positions with ``I == tau`` that fill it up to ``k``.
  A prefill attends under that mask at the dense path's cost
  (``ops/latent_attention.py``'s two forms take it as ``select``).
- ``select_decode``: ``lax.top_k`` of the row, which gives the positions
  themselves; they become cache slots once (``selected_slots``), and every
  layer that reads the selection gathers those entries and attends over the
  gathered block and the frame's side rows (``gather_selected``,
  ``attend_selected``).  ``lax.top_k`` puts the lower index
  first among equals on the CPU; on the TPU the order among exactly equal
  float32 scores at the ``k``-th place is XLA's to choose.

Neither is ``approx_max_k``, and neither selects by page.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from smg_tpu.ops.attention import NEG_INF
from smg_tpu.ops.latent_attention import SCORE_BLOCK_BYTES


def order_key(x):
    """The uint32 image of float32 ``x`` whose unsigned order is ``x``'s own
    (``-0.0`` is read as ``+0.0``).  Every finite value and both infinities
    map above 0, which is kept for what is not a candidate."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32) + 0.0, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def kth_largest_key(keys, k: int):
    """The ``k``-th largest of every row of ``keys`` [..., S] (uint32): the
    largest ``t`` with ``k`` or more keys ``>= t``, built from its top bit
    down, one compare-and-count pass over the rows a bit.  A row with fewer
    than ``k`` keys above 0 gives 0."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum((keys >= cand[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(n >= k, cand, t)

    return jax.lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))


@jax.named_scope("smg.mla.index.select")
def select_mask(scores, valid, k: int):
    """The ``k`` largest of each row of ``scores`` [..., S] (float32) among the
    candidates ``valid`` [..., S], as a mask; all of them where a row has no
    more than ``k``.  Equal scores go in by position, lower first."""
    if scores.shape[-1] <= k:
        return valid
    keys = jnp.where(valid, order_key(scores), jnp.uint32(0))
    tau = kth_largest_key(keys, k)[..., None]
    above, at = keys > tau, keys == tau
    room = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    return valid & (above | (at & (jnp.cumsum(at.astype(jnp.int32), axis=-1) <= room)))


@jax.named_scope("smg.mla.index.score")
def index_scores(q, w, keys):
    """``I = sum_j w_j ReLU(q_j . k)`` in float32: ``q`` [..., T, J, D], ``w``
    [..., T, J] (float32, the indexer's two scales in), ``keys`` [..., S, D]
    -> [..., T, S]."""
    s = jnp.einsum("...tjd,...sd->...tjs", q, keys, preferred_element_type=jnp.float32)
    return jnp.einsum("...tjs,...tj->...ts", jax.nn.relu(s), w.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def select_prefill(q, w, keys, q_positions, ctx_lens, k: int):
    """The selection of a chunk's queries over their context, as a mask [G, T,
    S]: ``q`` [G, T, J, D], ``w`` [G, T, J], ``keys`` [G, S, D] (position ``s``
    of the sequence at ``s``), ``q_positions`` [G, T], ``ctx_lens`` [G].  A
    candidate is ``s <= t`` inside the context.  The queries go through in
    blocks so that no score tensor passes ``SCORE_BLOCK_BYTES``."""
    G, T, J, _ = q.shape
    S = keys.shape[1]
    j = jnp.arange(S)

    def select(qb, wb, pos):
        valid = (j[None, None, :] <= pos[:, :, None]) & (j[None, None, :] < ctx_lens[:, None, None])
        return select_mask(index_scores(qb, wb, keys), valid, k)

    n = T
    while n > 16 and n % 2 == 0 and G * n * J * S * 4 > SCORE_BLOCK_BYTES:
        n //= 2
    if n == T:
        return select(q, w, q_positions)
    blocks = lambda x: jnp.moveaxis(x.reshape(G, T // n, n, *x.shape[2:]), 1, 0)
    out = jax.lax.map(lambda b: select(*b), (blocks(q), blocks(w), blocks(q_positions)))
    return jnp.moveaxis(out, 0, 1).reshape(G, T, S)


def select_decode(q, w, keys, side_keys, entry_positions, n_extra, k: int):
    """The selection of one decode column: ``q`` [B, J, D], ``w`` [B, J],
    ``keys`` [B, S, D] (what the lane's pages hold: positions below its
    ``entry``), ``side_keys`` [B, N, D] (the frame's fresh tokens, the first
    ``n_extra`` of them written, the column's own among them).  Returns ``ids``
    [B, K] int32 into the ``S + N`` places (a place ``>= S`` is side row ``id -
    S``, at position ``entry + id - S``) and ``chosen`` [B, K]: the
    ``min(context, k)`` places of largest score, ``K = min(k, S + N)``."""
    S, N = keys.shape[1], side_keys.shape[1]
    # the scores side by side, not the keys: joining those copies every lane's context
    scores = jnp.concatenate([index_scores(q[:, None], w[:, None], part.astype(q.dtype))[:, 0]
                              for part in (keys, side_keys)], axis=1)  # [B, S + N]
    place = jnp.arange(S + N)[None, :]
    valid = jnp.where(place < S, place < entry_positions[:, None], place - S < n_extra)
    with jax.named_scope("smg.mla.index.select"):
        top, ids = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), min(k, S + N))
    return ids.astype(jnp.int32), top > -jnp.inf


@jax.named_scope("smg.mla.index.select")
def selected_slots(page_tables, ids, chosen, page_size: int, n_side: int):
    """Where a selection's places lie, once for every layer that reads it:
    ``slots`` [B, K] (the flat cache slot of a chosen place below ``S = mp *
    page_size``, the garbage page's for any other), ``paged`` [B, K] (the place
    is chosen and in the pages) and ``fresh`` [B, N] (side row ``n`` is
    chosen)."""
    S = page_tables.shape[1] * page_size
    paged = chosen & (ids < S)
    at = jnp.minimum(ids, S - 1)
    slots = jnp.where(paged, jnp.take_along_axis(page_tables, at // page_size, axis=1)
                      * page_size + at % page_size, 0)
    rows = S + jnp.arange(n_side)
    fresh = jnp.any((ids[:, :, None] == rows[None, None, :]) & chosen[:, :, None], axis=1)
    return slots, paged, fresh


@jax.named_scope("smg.mla.sparse")
def gather_selected(cache, layer, slots):
    """The entries at ``slots`` [B, K] of ``cache`` [L, P, ps, W] at
    ``layer``, [B, K, W]: one gather out of the whole buffer (a layer sliced
    out first is a copy of it, a gigabyte a layer and column)."""
    L, P, ps, W = cache.shape
    return cache.reshape(L, P * ps, W)[layer, slots]


@jax.named_scope("smg.attn.decode")
def attend_selected(q, entries, side, paged, fresh, scale: float, latent: int):
    """Absorbed latent attention of ``q`` [B, H, W] over the gathered
    ``entries`` [B, K, W] (those ``paged`` [B, K]) and the frame's side rows
    ``side`` [B, N, W] (those ``fresh`` [B, N]): one softmax, values the
    entries' first ``latent`` lanes.  Returns ``sum p c`` [B, H, latent]."""
    both = jnp.concatenate([entries, side.astype(entries.dtype)], axis=1)
    chosen = jnp.concatenate([paged, fresh], axis=1)
    s = jnp.einsum("bhw,bkw->bhk", q.astype(both.dtype), both,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(chosen[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p.astype(both.dtype), both[..., :latent],
                      preferred_element_type=jnp.float32).astype(q.dtype)
