"""Attention of layers that keep a window, and of full layers beside them
whose keys are wider than their values.

**The window store.**  A sliding-window layer reads, for a query at position
``i``, the keys at ``i - window + 1 .. i`` and no others, so what a sequence
holds for such a layer is bounded by the window and not by its context: a
**ring** of ``R`` entries a layer and sequence, ``[layers, slots, R, lanes]``
for K and for V (kv-heads and head-dim fused into the lanes, as the pages
are; slot 0 is the garbage slot).  Entry ``p mod R`` holds position ``p``.
Which position an entry holds is never stored: a reader is told how many
tokens the sequence has (``length``), and entry ``s`` then holds the largest
position ``<= length - 1`` that is ``s mod R`` (``ring_positions``); the mask
is by that position.  So with ``R >= window + u``, where ``u`` is the most
columns the device may have written past ``length`` (a decode frame and its
lookahead, thrown away or rolled back), what those columns wrote reads as
positions below every later query's window until it is written again, and a
discarded frame costs nothing.  A new sequence needs no clearing either: at
``length`` 0 every entry reads as a position below 0.

**The sink.**  A window layer's softmax has one more term in its denominator,
``exp(b_h)`` for a learned ``b_h`` a head: it takes mass and adds no value.

Operands are the cache's dtype on the MXU with float32 accumulation, maxima
and sums in float32, as ``ops/attention.py`` has them.  Everything here is
XLA; the decode kernel over a ring is ``ops/pallas/window_decode.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from smg_tpu.ops.attention import (
    NEG_INF,
    SCORE_BLOCK_BYTES,  # noqa: F401  (what a prefill's workspace is sized by)
    _query_block,
    block_diagonal_query,
    own_lanes,
)


def ring_tokens(window: int, unaccepted: int) -> int:
    """Entries a ring needs: the window and the columns that may lie on the
    device unaccepted, in whole 16-row tiles."""
    return -(-(window + unaccepted) // 16) * 16


def ring_positions(length, R: int):
    """Position each of a ring's ``R`` entries holds in a sequence of
    ``length`` [...] tokens, ``[..., R]``; below 0 where it holds none."""
    s = jnp.arange(R)
    last = length[..., None] - 1
    return last - jnp.mod(last - s, R)


def _grouped(x, K: int):
    """``[..., H, D]`` -> ``[..., K, H/K, D]``."""
    return x.reshape(*x.shape[:-2], K, x.shape[-2] // K, x.shape[-1])


def _softmax_weigh(scores, mask, values_einsum, sink=None):
    """Masked softmax of float32 ``scores`` [..., S] times the values, with
    the sink's ``exp(b)`` [...] in the denominator where given."""
    scores = jnp.where(mask, scores, NEG_INF)
    m = scores.max(axis=-1)
    if sink is not None:
        m = jnp.maximum(m, sink)
    e = jnp.exp(scores - m[..., None])
    denom = e.sum(axis=-1)
    if sink is not None:
        denom = denom + jnp.exp(sink - m)
    return values_einsum(e / denom[..., None])


@jax.named_scope("smg.attn.prefill")
def attention_prefill_blocked(q, k_ctx, v_ctx, q_pos, ctx_lens, scale: float):
    """Causal attention of prefill chunks over their contexts, keys and
    values of widths of their own.  ``q`` [G, T, H, Dk]; ``k_ctx`` [G, S, K,
    Dk], ``v_ctx`` [G, S, K, Dv]: context entry ``j`` of a row is position
    ``j``; ``q_pos`` [G, T]; ``ctx_lens`` [G].  The queries go through in
    blocks, so that no ``[G, H, T, S]`` float32 array exists.  Returns
    [G, T, H, Dv]."""
    G, T, H, _ = q.shape
    S, K = k_ctx.shape[1:3]
    cd = k_ctx.dtype
    j = jnp.arange(S)

    def attend(q_blk, pos_blk):  # [G, n, H, Dk], [G, n]
        s = jnp.einsum("gtkhd,gskd->gkhts", _grouped(q_blk.astype(cd), K), k_ctx,
                       preferred_element_type=jnp.float32) * scale
        mask = (j[None, None, :] <= pos_blk[:, :, None]) & (j[None, None, :] < ctx_lens[:, None, None])
        out = _softmax_weigh(
            s, mask[:, None, None], lambda p: jnp.einsum(
                "gkhts,gskd->gtkhd", p.astype(cd), v_ctx, preferred_element_type=jnp.float32))
        return out.reshape(G, q_blk.shape[1], H, -1).astype(q.dtype)

    qb = _query_block(T, G * H, S)
    if qb == T:
        return attend(q, q_pos)
    nb = T // qb
    out = jax.lax.map(lambda blk: attend(*blk),
                      (jnp.moveaxis(q.reshape(G, nb, qb, H, -1), 1, 0),
                       jnp.moveaxis(q_pos.reshape(G, nb, qb), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(G, T, H, -1)


def read_ring_tail(ring, layer, slots, length, n: int):
    """The ``n`` entries before position ``length`` [G] of the sequences in
    ``slots`` [G], oldest first: ``[G, n, lanes]`` (an entry of a position
    below 0 is whatever the ring holds there; the caller masks it)."""
    R = ring.shape[2]
    pos = length[:, None] - n + jnp.arange(n)[None, :]
    return ring[layer, slots[:, None], jnp.mod(pos, R)]


@jax.named_scope("smg.attn.window_prefill")
def window_attention_prefill(q, k, v, prev_k, prev_v, q_pos, window: int, sink, scale: float):
    """Sliding-window attention of prefill chunks: a query at position ``i``
    meets the keys at ``i - window + 1 .. i``, which are the chunk's own and,
    for the chunk's first queries, the ``window`` entries before it
    (``prev_k``, ``prev_v`` [G, window, K, D]: positions ``q_pos[:, 0] -
    window ..``, masked where below 0).  Never the context: the queries go in
    blocks of ``window``, each against its own block of keys and the block
    before it.  ``q`` [G, T, H, Dk], ``k`` [G, T, K, Dk], ``v`` [G, T, K, Dv];
    ``sink`` [H] float32 or None.  Returns [G, T, H, Dv]."""
    G, T, H, _ = q.shape
    K = k.shape[2]
    W = window
    cd = k.dtype
    pad = -T % W
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        q_pos = jnp.concatenate([q_pos, q_pos[:, -1:] + 1 + jnp.arange(pad)[None, :]], axis=1)
    nb = (T + pad) // W

    def with_block_before(x, prev):  # [G, nb*W, K, D] -> [G, nb, 2W, K, D]
        blocks = x.reshape(G, nb, W, *x.shape[2:])
        before = jnp.concatenate([prev.astype(x.dtype)[:, None], blocks[:, :-1]], axis=1)
        return jnp.concatenate([before, blocks], axis=2)

    k2, v2 = with_block_before(k, prev_k), with_block_before(v, prev_v)
    qg = _grouped(q.astype(cd), K).reshape(G, nb, W, K, H // K, -1)
    pos = q_pos.reshape(G, nb, W)
    key_pos = pos[:, :, :1] - W + jnp.arange(2 * W)[None, None, :]  # [G, nb, 2W]
    s = jnp.einsum("gbtkhd,gbskd->gbkhts", qg, k2, preferred_element_type=jnp.float32) * scale
    mask = ((key_pos[:, :, None, :] <= pos[..., None])
            & (key_pos[:, :, None, :] > pos[..., None] - W) & (key_pos[:, :, None, :] >= 0))
    b = None if sink is None else sink.astype(jnp.float32).reshape(K, H // K)[None, None, :, :, None]
    out = _softmax_weigh(
        s, mask[:, :, None, None], lambda p: jnp.einsum(
            "gbkhts,gbskd->gbtkhd", p.astype(cd), v2, preferred_element_type=jnp.float32),
        sink=b)
    return out.reshape(G, nb * W, H, -1)[:, :T].astype(q.dtype)


@jax.named_scope("smg.attn.window_land")
def write_ring_chunk(ring_k, ring_v, layer, k, v, slots, prefix_lens, t_reals):
    """Put the last ``min(t_real, R)`` real entries of prefill chunks into
    their sequences' rings (``k`` [G, T, lanes] at positions ``prefix_lens``
    + t).  Earlier entries of a long chunk would only be overwritten, in an
    order a scatter does not promise."""
    G, T, _ = k.shape
    R = ring_k.shape[2]
    n = min(T, R)
    t = t_reals[:, None] - n + jnp.arange(n)[None, :]  # [G, n] chunk rows, the last n real
    keep = (t >= 0) & (slots[:, None] > 0)
    tc = jnp.maximum(t, 0)
    dest = jnp.where(keep, slots[:, None] * R + jnp.mod(prefix_lens[:, None] + tc, R), 0)
    rows = lambda x: jnp.take_along_axis(x, tc[:, :, None], axis=1).reshape(G * n, -1)
    return _scatter_ring(ring_k, ring_v, layer, rows(k), rows(v), dest.reshape(-1))


def _scatter_ring(ring_k, ring_v, layer, k_rows, v_rows, dest):
    """Rows into the flat entries ``dest`` (slot * R + entry) of one layer;
    the layer is part of the scatter's index (``ops.attention.scatter_kv_rows``
    says why)."""
    flat = lambda ring: ring.reshape(ring.shape[0], -1, ring.shape[3])
    rk = flat(ring_k).at[layer, dest].set(k_rows.astype(ring_k.dtype))
    rv = flat(ring_v).at[layer, dest].set(v_rows.astype(ring_v.dtype))
    return rk.reshape(ring_k.shape), rv.reshape(ring_v.shape)


@jax.named_scope("smg.attn.window_land")
def land_ring_side(ring_k, ring_v, side_k, side_v, slots, entry_positions, keep):
    """Land a decode frame's window-layer columns in the rings, all layers
    in one scatter: column ``n`` of lane ``b`` at entry ``(entry[b] + n) mod
    R`` of slot ``slots[b]``.  ``side_k`` [L, B, N, lanes]; ``keep`` [B, N]
    (or broadcastable): the column was computed.  A lane on the garbage slot
    and a column not kept go to entry 0 of slot 0."""
    L, B, N, _ = side_k.shape
    R = ring_k.shape[2]
    pos = entry_positions[:, None] + jnp.arange(N)[None, :]
    dest = jnp.where(keep & (slots[:, None] > 0), slots[:, None] * R + jnp.mod(pos, R), 0)
    layer = jnp.arange(L)[:, None]
    return _scatter_ring(ring_k, ring_v, layer, side_k.reshape(L, B * N, -1),
                         side_v.reshape(L, B * N, -1), dest.reshape(1, -1))


@jax.named_scope("smg.attn.window_decode")
def window_attention_decode(q, ring_k, ring_v, side_k, side_v, n_extra, layer, slots,
                            entry_positions, window: int, sink, scale: float):
    """One decode column of a window layer, the XLA form: each lane's ring
    (positions below ``entry``, by ``ring_positions``) and the first
    ``n_extra`` side rows (positions ``entry + n``), those inside the
    query's window, in one softmax with the sink.  ``q`` [B, H, Dk];
    ``side_k`` [B, N, K*Dk], ``side_v`` [B, N, K*Dv] (this layer's);
    ``sink`` [H] float32 or None.  Returns [B, H, Dv].  Mirrors
    ``ops/pallas/window_decode.py``."""
    B, H, Dk = q.shape
    R = ring_k.shape[2]
    K = ring_k.shape[3] // Dk
    N = side_k.shape[1]
    cd = ring_k.dtype
    rk, rv = ring_k[layer, slots], ring_v[layer, slots]  # [B, R, lanes]
    q_pos = entry_positions + n_extra - 1
    lo = (q_pos - window)[:, None]
    ring_pos = ring_positions(entry_positions, R)
    side_pos = entry_positions[:, None] + jnp.arange(N)[None, :]
    ring_mask = (ring_pos >= 0) & (ring_pos > lo)
    side_mask = (jnp.arange(N)[None, :] < n_extra) & (side_pos > lo)
    q_bd = block_diagonal_query(q.astype(cd), K)  # [B, H, K*Dk]
    score = lambda keys: jnp.einsum("bhl,bsl->bhs", q_bd, keys.astype(cd),
                                    preferred_element_type=jnp.float32) * scale
    s = jnp.concatenate([score(rk), score(side_k)], axis=-1)
    mask = jnp.concatenate([ring_mask, side_mask], axis=-1)[:, None, :]
    vals = jnp.concatenate([rv, side_v.astype(cd)], axis=1)
    out = _softmax_weigh(
        s, mask, lambda p: jnp.einsum("bhs,bsl->bhl", p.astype(cd), vals,
                                      preferred_element_type=jnp.float32),
        sink=None if sink is None else sink.astype(jnp.float32)[None, :])
    return own_lanes(out, K).astype(q.dtype)
