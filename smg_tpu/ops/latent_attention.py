"""Latent attention over a cache with no V buffer (XLA forms).

What a token leaves in the cache is one **entry** a layer: the normalised
latent ``c`` (``kv_lora_rank`` numbers) and the rotary key ``k_pe``
(``qk_rope_head_dim``), rotated at the token's absolute position, so that a
cached prefix is reusable as cached K and V are.  The entry is laid out on
whole 128-lane tiles (``entry_lanes``): 512 + 64 = 576 numbers sit in 640
lanes.  A ``[.., 576]`` array in bfloat16 is tiled ``(16, 128)`` in HBM and
padded to 640 lanes whatever its declared shape, and 512 lanes beside a
buffer of 64 pads the 64 to 128: every tile-aligned layout costs the same 640
lanes a token, and the one buffer needs one DMA a page.

Two forms, the same mathematics (``models/pangu_moe.py`` has the equations):

- **expanded**, for prefill: keys and values of every head are rebuilt from
  the entries of the context and the chunk's queries meet them, in float32
  score blocks a query block at a time here (``latent_attention_prefill``
  for cold rows up to the size at which those blocks leave the chip, and for
  every solo chunk; ``latent_attention_prefill_cached`` behind a prefix), or,
  for a larger group of cold rows, in the online-softmax kernel
  ``ops/pallas/flash_prefill.py`` (``LatentModelRunner.
  _grouped_prefill_impl_for`` chooses);
- **absorbed**, for decode: the up-projections are folded into the query and
  the output, so that all heads meet the entry itself: scores over all
  ``entry_lanes``, values the entry's first ``kv_lora_rank`` lanes.  That is
  ``ops.attention.attention_decode_cached`` with one "head" as wide as the
  entry and the cache as its own V, or the kernel
  ``ops/pallas/decode_attention.latent_attention_decode_cached``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from smg_tpu.ops.attention import NEG_INF, page_slots

# Largest float32 score tensor one block of queries makes (the probabilities
# and their bfloat16 copy live beside it): half ``ops.attention``'s, because
# this model's weights leave a prefill 1.6 GB beside the cache.
SCORE_BLOCK_BYTES = 128 * 2**20

# Most bytes of rebuilt keys and values one prefill call holds at a time; a
# group whose contexts pass it goes through a row at a time.
EXPANDED_KV_BYTES = 512 * 2**20


def entry_lanes(kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    """Lanes of one cache entry: the published numbers on whole tiles."""
    return -(-(kv_lora_rank + qk_rope_head_dim) // 128) * 128


def value_lanes(kv_lora_rank: int) -> int:
    """Leading lanes of an entry that the kernel takes as its value."""
    return -(-kv_lora_rank // 128) * 128


def scatter_entries(cache, layer, entries, dest_slots):
    """Write ``entries`` [n, W] of one layer at flat slots ``dest_slots`` [n]
    (``ops.attention.scatter_kv_pages_full`` for one buffer)."""
    L, P, ps, W = cache.shape
    flat = cache.reshape(L, P * ps, W)
    return flat.at[layer, dest_slots].set(entries.astype(cache.dtype)).reshape(cache.shape)


def land_side_buffer(cache, side, page_tables, entry_positions, keep):
    """``ops.attention.land_side_buffers`` for one buffer: column ``n`` of lane
    ``b`` of ``side`` [L, B, N, W] lands at position ``entry[b] + n``."""
    L, B, N, W = side.shape
    P, ps = cache.shape[1:3]
    pos = entry_positions[:, None] + jnp.arange(N)[None, :]
    dest = page_slots(page_tables, pos, keep, ps).reshape(-1)
    flat = cache.reshape(L, P * ps, W)
    flat = flat.at[jnp.arange(L)[:, None], dest[None, :]].set(
        side.reshape(L, B * N, W).astype(cache.dtype))
    return flat.reshape(cache.shape)


@jax.named_scope("smg.attn.prefill")
def latent_attention_prefill(q_nope, q_pe, k_nope, k_pe, v, q_positions, ctx_lens, scale,
                             select=None):
    """Causal attention of a chunk's queries over rebuilt keys and values.

    ``q_nope`` [G, T, H, dn], ``q_pe`` [G, T, H, dr]; ``k_nope`` [G, S, H, dn],
    ``k_pe`` [G, S, dr] (one rotary key a token, shared by the heads), ``v``
    [G, S, H, dv]; ``q_positions`` [G, T], ``ctx_lens`` [G].  Scores are
    ``(q_nope . k_nope + q_pe . k_pe) * scale`` in float32; the queries go
    through in blocks so that no score tensor passes ``SCORE_BLOCK_BYTES``.
    ``select`` [G, T, S] bool (``ops/sparse_attention.py``): a query reads the
    keys it marks and no others.  Returns [G, T, H, dv]."""
    G, T, H, _ = q_nope.shape
    S = k_nope.shape[1]
    f32 = jnp.float32
    j = jnp.arange(S)
    chosen = () if select is None else (select,)

    def attend(qn, qp, pos, *sel):  # [G, n, H, d], [G, n], ([G, n, S])
        s = (jnp.einsum("gthd,gshd->ghts", qn, k_nope, preferred_element_type=f32)
             + jnp.einsum("gthd,gsd->ghts", qp, k_pe, preferred_element_type=f32)) * scale
        mask = (j[None, None, :] <= pos[:, :, None]) & (j[None, None, :] < ctx_lens[:, None, None])
        for m in sel:
            mask = mask & m
        p = jax.nn.softmax(jnp.where(mask[:, None], s, NEG_INF), axis=-1)
        return jnp.einsum("ghts,gshd->gthd", p.astype(v.dtype), v,
                          preferred_element_type=f32).astype(q_nope.dtype)

    qb = T
    while qb > 16 and qb % 2 == 0 and G * qb * H * S * 4 > SCORE_BLOCK_BYTES:
        qb //= 2
    if qb == T:
        return attend(q_nope, q_pe, q_positions, *chosen)
    blocks = lambda x: jnp.moveaxis(x.reshape(G, T // qb, qb, *x.shape[2:]), 1, 0)
    out = jax.lax.map(lambda b: attend(*b), (blocks(q_nope), blocks(q_pe), blocks(q_positions),
                                             *map(blocks, chosen)))
    return jnp.moveaxis(out, 0, 1).reshape(G, T, H, -1)


# Pages of context the cached form rebuilds keys and values for at a time
# (1,024 entries at 16 a page: 67 MB of keys and values at 128 heads).
CONTEXT_BLOCK_PAGES = 64


@jax.named_scope("smg.attn.prefill")
def latent_attention_prefill_cached(q_nope, q_pe, cache, layer, page_tables, w_uk, w_uv,
                                    q_positions, ctx_lens, scale, rkv: int, dr: int,
                                    select=None):
    """The same attention over what the pages hold (the chunk's own entries
    among them), a block of ``CONTEXT_BLOCK_PAGES`` pages at a time and **as
    many blocks as the longest context of the group needs**, not as many as
    the table has: a chunk behind 1,000 tokens costs two blocks whatever the
    table's width.  Each block's keys and values are rebuilt once (``w_uk``,
    ``w_uv`` [H, rkv, d]) and met by the queries in blocks, with a running
    maximum and sum (float32).  ``select`` [G, T, mp * page_size] bool
    (``ops/sparse_attention.py``): a query reads the positions it marks and
    no others.  Returns [G, T, H, dv]."""
    G, T, H, _ = q_nope.shape
    ps, W = cache.shape[2], cache.shape[3]
    mp = page_tables.shape[1]
    bp = min(CONTEXT_BLOCK_PAGES, mp)
    if mp % bp:
        page_tables = jnp.pad(page_tables, ((0, 0), (0, bp - mp % bp)))  # the garbage page
        if select is not None:
            select = jnp.pad(select, ((0, 0), (0, 0), (0, (bp - mp % bp) * ps)))
    S = bp * ps
    dv = w_uv.shape[-1]
    f32 = jnp.float32
    qb = T
    while qb > 16 and qb % 2 == 0 and G * qb * H * S * 4 > SCORE_BLOCK_BYTES:
        qb //= 2
    nq = T // qb
    blocks = lambda x: jnp.moveaxis(x.reshape(G, nq, qb, *x.shape[2:]), 1, 0)  # [nq, G, qb, ..]
    whole = lambda x: jnp.moveaxis(x, 0, 1).reshape(G, T, *x.shape[3:])
    qn, qp, pos = blocks(q_nope), blocks(q_pe), blocks(q_positions)

    def chosen(b):
        """The block's stretch of ``select``, by query block: ([nq, G, qb, S],)."""
        if select is None:
            return ()
        return (blocks(jax.lax.dynamic_slice_in_dim(select, b * S, S, axis=2)),)

    def block(b, carry):
        pages = jax.lax.dynamic_slice_in_dim(page_tables, b * bp, bp, axis=1)
        ent = cache[layer, pages].reshape(G, S, W).astype(q_nope.dtype)
        c, k_pe = ent[..., :rkv], ent[..., rkv:rkv + dr]
        with jax.named_scope("smg.mla.kv"):
            k_nope = jnp.einsum("gsc,hcd->gshd", c, w_uk)
            v = jnp.einsum("gsc,hcd->gshd", c, w_uv)
        j = b * S + jnp.arange(S)

        def meet(x):  # one block of queries against this block of context
            qn, qp, pos, m, l, acc, *sel = x
            s = (jnp.einsum("gthd,gshd->ghts", qn, k_nope, preferred_element_type=f32)
                 + jnp.einsum("gthd,gsd->ghts", qp, k_pe, preferred_element_type=f32)) * scale
            seen = (j[None, None, :] <= pos[:, :, None]) & (j[None, None, :] < ctx_lens[:, None, None])
            for marked in sel:
                seen = seen & marked
            s = jnp.where(seen[:, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(seen[:, None], jnp.exp(s - m_new[..., None]), 0.0)
            a = jnp.exp(m - m_new)
            acc = acc * jnp.moveaxis(a, 1, 2)[..., None] + jnp.einsum(
                "ghts,gshd->gthd", p.astype(v.dtype), v, preferred_element_type=f32)
            return m_new, l * a + jnp.sum(p, axis=-1), acc

        return jax.lax.map(meet, (qn, qp, pos, *carry, *chosen(b)))

    n = jnp.minimum(-(-jnp.max(ctx_lens) // S), page_tables.shape[1] // bp)
    init = (jnp.full((nq, G, H, qb), NEG_INF, f32), jnp.zeros((nq, G, H, qb), f32),
            jnp.zeros((nq, G, qb, H, dv), f32))
    _m, l, acc = jax.lax.fori_loop(0, n, block, init)
    out = acc / jnp.maximum(jnp.moveaxis(l, 2, 3), 1e-30)[..., None]  # a padded row met nothing
    return whole(out).astype(q_nope.dtype)
