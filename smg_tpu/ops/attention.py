"""Attention over the paged KV cache — XLA reference implementations.

Layout (per layer): ``k_pages, v_pages: [num_pages, page_size, kv_heads*head_dim]``
— the kv-head and head-dim axes are FUSED into the lane dimension (>= 512
lanes for standard configs).  This keeps the trailing dim a multiple of the
TPU 128-lane tile for any head_dim, so the Pallas kernels DMA pages without
relayout copies (head_dim 64 unfused would lane-pad 64->128).  Only views
that KEEP the fused lane axis are bitcasts (``[B, mp, ps, K*D]`` to
``[B, mp*ps, K*D]``: whole sublane tiles); splitting it into ``[.., K, D]``
for a per-head product is a copy of the operand on the chip's (8, 128)
tiles.  So XLA decode and verify attention keep K and V on the fused lanes
between the page gather and the matmuls (``_attend_cache_and_side``).
Sequences own an ordered list of pages (``page_table``); the radix prefix cache
shares page prefixes between sequences (``smg_tpu/engine/radix_cache.py``).
Page 0 is reserved as a garbage page: padded/inactive tokens scatter there.

Pallas TPU kernels for solo prefill and horizon decode live in
``smg_tpu/ops/pallas/``; ``ModelRunner._prefill_impl_for`` and
``_attn_impl_for`` (``smg_tpu/engine/runner.py``) choose between them and
these XLA versions per compiled program.  The XLA versions are the
correctness reference, the only path for grouped prefill, verify blocks and
meshes, and the CPU-test path (SURVEY.md §4 takeaway — the whole engine must
run without TPU hardware).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
# Largest float32 score tensor one prefill attention call may materialize.
# Past it the queries go through in blocks (``lax.map``): at the serving
# defaults (4096-token chunk, 8192-slot table, 32 heads) the one-shot
# ``[T, H, S]`` scores are 4 GiB, more than a 16 GB chip has left beside its
# weights and cache.  256 MiB keeps every tier-1 shape on the one-shot path.
SCORE_BLOCK_BYTES = 256 * 2**20


def scatter_kv_pages_full(
    k_cache: jnp.ndarray,  # [L, P, ps, KD] — FULL stacked cache
    v_cache: jnp.ndarray,
    layer: jnp.ndarray,  # scalar layer index
    k_new: jnp.ndarray,  # [T, K, D]
    v_new: jnp.ndarray,
    dest_slots: jnp.ndarray,  # [T]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter into the full cache with the layer index folded into the
    scatter — no per-layer slice-out/slice-in, so when the cache is a loop
    carry the write stays in place (the slice/stack dance costs a full layer
    copy per layer per step)."""
    L, P, ps, KD = k_cache.shape
    VD = v_cache.shape[3]  # V's lanes: KD but where values are narrower than keys
    T = k_new.shape[0]
    k_flat = k_cache.reshape(L, P * ps, KD)
    v_flat = v_cache.reshape(L, P * ps, VD)
    k_flat = k_flat.at[layer, dest_slots].set(k_new.reshape(T, KD).astype(k_flat.dtype))
    v_flat = v_flat.at[layer, dest_slots].set(v_new.reshape(T, VD).astype(v_flat.dtype))
    return k_flat.reshape(k_cache.shape), v_flat.reshape(v_cache.shape)


def scatter_kv_rows(
    k_cache: jnp.ndarray,  # [L, P, ps, KD] — FULL stacked cache
    v_cache: jnp.ndarray,
    k_rows: jnp.ndarray,  # [L, n, KD] side-buffer rows, every layer
    v_rows: jnp.ndarray,
    dest_slots: jnp.ndarray,  # [n] flat slot per row (the same in every layer)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Land a decode horizon's (or verify block's) side-buffer rows in the
    cache, all layers in one scatter.  The layer is part of the scatter
    INDEX here too: written as ``cache.at[:, dest]`` (layer as a window
    dimension) XLA:TPU moves the whole cache into a scatter-friendly layout
    and back, two full copies a call and one buffer's worth of temporaries,
    which at an auto-sized cache does not fit the chip."""
    L, P, ps, KD = k_cache.shape
    layer = jnp.arange(L)[:, None]
    dest = dest_slots[None, :]
    k_flat = k_cache.reshape(L, P * ps, KD).at[layer, dest].set(
        k_rows.astype(k_cache.dtype)
    )
    v_flat = v_cache.reshape(L, P * ps, v_cache.shape[3]).at[layer, dest].set(
        v_rows.astype(v_cache.dtype)
    )
    return k_flat.reshape(k_cache.shape), v_flat.reshape(v_cache.shape)


def page_slots(
    page_tables: jnp.ndarray,  # [..., mp]
    pos: jnp.ndarray,  # [..., n] token positions in each table's sequence
    keep: jnp.ndarray,  # [..., n] bool
    page_size: int,
) -> jnp.ndarray:
    """Flat cache slot (page*ps + offset) of each position.  Rows not kept
    (padding, columns never computed, rejected drafts) and positions past the
    table go to the garbage page (slot 0); clamping instead would clobber a
    real slot."""
    total = page_tables.shape[-1] * page_size
    pos_c = jnp.minimum(pos, total - 1)
    page = jnp.take_along_axis(page_tables, pos_c // page_size, axis=-1)
    return jnp.where(keep & (pos < total), page * page_size + pos_c % page_size, 0)


def land_side_buffers(
    k_cache: jnp.ndarray,  # [L, P, ps, KD]
    v_cache: jnp.ndarray,
    side_k: jnp.ndarray,  # [L, B, N, KD] a frame's side buffers
    side_v: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, mp]
    entry_positions: jnp.ndarray,  # [B] position of each lane's column 0
    keep: jnp.ndarray,  # [B, N] (or broadcastable) bool: the column is kept
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Land a decode horizon's (or verify block's) columns in the cache in
    one scatter, column n of lane b at position ``entry[b] + n``."""
    L, B, N, KD = side_k.shape
    pos = entry_positions[:, None] + jnp.arange(N)[None, :]
    dest = page_slots(page_tables, pos, keep, k_cache.shape[2]).reshape(-1)
    return scatter_kv_rows(k_cache, v_cache, side_k.reshape(L, B * N, KD),
                           side_v.reshape(L, B * N, side_v.shape[3]), dest)


@jax.named_scope("smg.attn.kv_read")
def gather_layer_pages(
    k_cache: jnp.ndarray,  # [L, P, ps, K*D]
    v_cache: jnp.ndarray,
    layer,  # scalar layer index
    page_tables: jnp.ndarray,  # [..., mp]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One layer's pages for every lane, ``[..., mp, ps, K*D]`` each, in ONE
    gather: the layer is part of the gather's index, as it is of the
    scatters' above.  Written ``k_cache[layer][page_tables]`` XLA:TPU copies
    the whole layer out of the cache (155 MB at 4,725 pages of 1,024 lanes)
    before it gathers from the copy, per layer, per decode column."""
    return k_cache[layer, page_tables], v_cache[layer, page_tables]


def gather_seq_kv(
    k_cache: jnp.ndarray,  # [L, P, ps, K*D]
    v_cache: jnp.ndarray,
    layer,  # scalar layer index
    page_table: jnp.ndarray,  # [max_pages] page ids for one sequence
    num_kv_heads: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize one sequence's KV contiguously: [max_pages*ps, K, D]."""
    k, v = gather_layer_pages(k_cache, v_cache, layer, page_table)
    mp, ps, KD = k.shape
    K = num_kv_heads
    return (
        k.reshape(mp * ps, K, KD // K),
        v.reshape(mp * ps, K, KD // K),
    )


def _query_block(T: int, H: int, S: int) -> int:
    """Queries per block: ``T`` halved until a ``[block, H, S]`` float32
    score tensor fits ``SCORE_BLOCK_BYTES`` (or the block reaches 16 rows,
    or stops dividing evenly).  Always divides ``T``."""
    qb = T
    while qb > 16 and qb % 2 == 0 and qb * H * S * 4 > SCORE_BLOCK_BYTES:
        qb //= 2
    return qb


@jax.named_scope("smg.attn.prefill")
def attention_prefill(
    q: jnp.ndarray,  # [T, H, D] (new tokens, post-rope)
    k_ctx: jnp.ndarray,  # [S, K, D] contiguous KV incl. prefix and new tokens
    v_ctx: jnp.ndarray,
    q_positions: jnp.ndarray,  # [T] global positions of the new tokens
    ctx_len: jnp.ndarray,  # scalar: total valid tokens in k_ctx
    scale: float,
    softcap: float | None = None,  # tanh softcap on attention logits (Gemma-2)
    window: jnp.ndarray | None = None,  # scalar sliding window (<=0 = global)
) -> jnp.ndarray:
    """Causal attention for one sequence's prefill chunk. GQA-aware.
    Query blocks bound the score tensor (``SCORE_BLOCK_BYTES``)."""
    T, H, D = q.shape
    S, K, _ = k_ctx.shape
    G = H // K
    kf = k_ctx.astype(jnp.float32)
    vf = v_ctx.astype(jnp.float32)
    j = jnp.arange(S)

    def attend(q_blk, pos_blk):
        n = q_blk.shape[0]
        qf = q_blk.astype(jnp.float32).reshape(n, K, G, D)
        scores = jnp.einsum("tkgd,skd->tkgs", qf, kf) * scale  # [n, K, G, S]
        if softcap:
            scores = softcap * jnp.tanh(scores / softcap)
        mask = (j[None, :] <= pos_blk[:, None]) & (j[None, :] < ctx_len)  # [n, S]
        if window is not None:
            mask = mask & (
                (window <= 0) | (j[None, :] > pos_blk[:, None] - window)
            )
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("tkgs,skd->tkgd", probs, vf)
        return out.reshape(n, H, D).astype(q.dtype)

    qb = _query_block(T, H, S)
    if qb == T:
        return attend(q, q_positions)
    out = jax.lax.map(
        lambda blk: attend(*blk),
        (q.reshape(T // qb, qb, H, D), q_positions.reshape(T // qb, qb)),
    )
    return out.reshape(T, H, D)


@jax.named_scope("smg.attn.prefill")
def attention_prefill_batched(
    q: jnp.ndarray,  # [G, T, H, D] (new tokens per sequence, post-rope)
    k_ctx: jnp.ndarray,  # [G, S, K, D] per-sequence contiguous KV
    v_ctx: jnp.ndarray,
    q_positions: jnp.ndarray,  # [G, T] global positions
    ctx_lens: jnp.ndarray,  # [G] valid tokens per row
    scale: float,
    softcap: float | None = None,
    window: jnp.ndarray | None = None,  # scalar sliding window (<=0 = global)
) -> jnp.ndarray:
    """Batched multi-sequence prefill attention (one row per sequence).
    When the ``[G, T, H, S]`` scores would pass ``SCORE_BLOCK_BYTES`` the
    rows go one at a time through ``attention_prefill``'s query blocks."""
    G_, T, H, D = q.shape
    S = k_ctx.shape[1]
    K = k_ctx.shape[2]
    Gq = H // K
    if G_ * T * H * S * 4 > SCORE_BLOCK_BYTES:
        return jax.lax.map(
            lambda row: attention_prefill(*row, scale, softcap=softcap,
                                          window=window),
            (q, k_ctx, v_ctx, q_positions, ctx_lens),
        )
    qf = q.astype(jnp.float32).reshape(G_, T, K, Gq, D)
    kf = k_ctx.astype(jnp.float32)
    vf = v_ctx.astype(jnp.float32)
    scores = jnp.einsum("gtkhd,gskd->gtkhs", qf, kf) * scale  # [G, T, K, Gq, S]
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    j = jnp.arange(S)
    mask = (j[None, None, :] <= q_positions[:, :, None]) & (
        j[None, None, :] < ctx_lens[:, None, None]
    )  # [G, T, S]
    if window is not None:
        mask = mask & (
            (window <= 0) | (j[None, None, :] > q_positions[:, :, None] - window)
        )
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("gtkhs,gskd->gtkhd", probs, vf)
    return out.reshape(G_, T, H, D).astype(q.dtype)


def _owns(H: int, K: int) -> jnp.ndarray:
    """[H, K] bool: query head ``h`` reads KV head ``k`` (GQA groups of H/K)."""
    return jnp.arange(H)[:, None] // (H // K) == jnp.arange(K)[None, :]


def block_diagonal_query(q: jnp.ndarray, K: int) -> jnp.ndarray:
    """``[..., H, D]`` queries on the cache's fused lanes, ``[..., H, K*D]``:
    head ``h`` on the ``D`` lanes of its KV head, zeros elsewhere, so one
    product with ``[.., K*D]`` keys serves all heads (the XLA decode form
    below and ``ops/pallas/decode_attention.py``)."""
    H, D = q.shape[-2:]
    q_bd = jnp.where(_owns(H, K)[:, :, None], q[..., None, :], 0)
    return q_bd.reshape(*q.shape[:-1], K * D)


def own_lanes(out: jnp.ndarray, K: int) -> jnp.ndarray:
    """The other way: of a product with V on the fused lanes,
    ``[..., H, K*D]``, each head keeps the ``D`` lanes of its own KV head."""
    H, KD = out.shape[-2:]
    out = out.reshape(*out.shape[:-1], K, KD // K)
    return jnp.where(_owns(H, K)[:, :, None], out, 0).sum(axis=-2)


def _attend_cache_and_side(
    q: jnp.ndarray,  # [B, W, H, D] W query tokens per lane (post-rope)
    k_cache: jnp.ndarray,  # [L, P, ps, K*D] read-only cache (fused lanes)
    v_cache: jnp.ndarray,
    sk: jnp.ndarray,  # [B, N, K*D] side-buffer rows (this layer)
    sv: jnp.ndarray,
    layer,  # scalar layer index
    page_tables: jnp.ndarray,  # [B, mp]
    entry_positions: jnp.ndarray,  # [B] cache token count at entry
    q_pos: jnp.ndarray,  # [B, W] absolute position of each query
    side_visible: jnp.ndarray,  # [W, N] bool: side row n is a key of query w
    scale: float,
    softcap: float | None,
    window: jnp.ndarray | None,
    lanes_sharded: bool,
) -> jnp.ndarray:
    """Attention of each lane's queries over its cache pages (slots below
    ``entry``) and its side rows, joined in ONE softmax (one maximum, one
    denominator).  The cache part and the side part are scored by products
    of their own, so nothing is concatenated onto the gathered pages, and
    between the page gather and the matmuls there is no other copy of K or V:

    - lane axis whole on the device (``lanes_sharded`` false): the gathered
      pages stay ``[B, mp*ps, K*D]`` and the query is the block-diagonal
      ``[.., H, K*D]`` the Pallas kernel builds (head ``h`` on the lanes of
      its KV head, zeros elsewhere).  ``K`` times the multiply-adds of the
      per-head product on an operand read once; the zeros add exactly
      nothing.  The product with V is ``[.., H, K*D]`` and each head keeps
      its own ``D`` lanes.
    - lane axis sharded over a mesh axis (tp): per-head products on
      ``[B, S, K, D]`` views.  A contraction over the sharded lanes of a
      block-diagonal query would have GSPMD all-reduce the scores; this form
      contracts ``D`` inside each shard's own heads and needs no collective.

    Cache dtype through both products, float32 accumulation: converting the
    gather to float32 doubles its HBM traffic, and decode is bandwidth-bound.
    """
    B, W, H, D = q.shape
    ps, KD = k_cache.shape[2:]
    K = KD // D
    G = H // K
    N = sk.shape[1]
    cd = k_cache.dtype
    kl, vl = gather_layer_pages(k_cache, v_cache, layer, page_tables)
    S = page_tables.shape[1] * ps
    kl = kl.reshape(B, S, KD)
    vl = vl.reshape(B, S, vl.shape[-1])
    sk = sk.astype(cd)
    sv = sv.astype(cd)
    f32 = jnp.float32

    if lanes_sharded:
        qh = q.astype(cd).reshape(B, W, K, G, D)

        def score(keys):  # [B, n, KD] -> [B, W, H, n]
            n = keys.shape[1]
            return jnp.einsum("bwkgd,bskd->bwkgs", qh, keys.reshape(B, n, K, D),
                              preferred_element_type=f32).reshape(B, W, H, n)

        def weigh(p, vals):  # [B, W, H, n], [B, n, KD] -> [B, W, H, D]
            n = vals.shape[1]
            return jnp.einsum("bwkgs,bskd->bwkgd", p.reshape(B, W, K, G, n),
                              vals.reshape(B, n, K, -1),
                              preferred_element_type=f32).reshape(B, W, H, -1)
    else:
        q_bd = block_diagonal_query(q.astype(cd), K)  # [B, W, H, KD]

        def score(keys):
            return jnp.einsum("bwhl,bsl->bwhs", q_bd, keys,
                              preferred_element_type=f32)

        def weigh(p, vals):
            return own_lanes(jnp.einsum("bwhs,bsl->bwhl", p, vals,
                                        preferred_element_type=f32), K)

    # masks broadcast against [B, W, keys]
    j = jnp.arange(S)
    cache_mask = j < entry_positions[:, None, None]
    side_mask = side_visible[None]
    if window is not None:
        # absolute key positions: the slot index in the cache, entry + row
        # in the side buffer
        lo = (q_pos - window)[:, :, None]
        side_pos = entry_positions[:, None, None] + jnp.arange(N)
        cache_mask = cache_mask & ((window <= 0) | (j > lo))
        side_mask = side_mask & ((window <= 0) | (side_pos > lo))

    def masked_scores(keys, mask):
        s = score(keys) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        return jnp.where(mask[:, :, None, :], s, NEG_INF)

    sc = masked_scores(kl, cache_mask)  # [B, W, H, S]
    ss = masked_scores(sk, side_mask)  # [B, W, H, N]
    m = jnp.maximum(sc.max(axis=-1), ss.max(axis=-1))[..., None]
    ec = jnp.exp(sc - m)
    es = jnp.exp(ss - m)
    denom = (ec.sum(axis=-1) + es.sum(axis=-1))[..., None]
    out = weigh((ec / denom).astype(cd), vl) + weigh((es / denom).astype(cd), sv)
    return out.astype(q.dtype)


@jax.named_scope("smg.attn.decode")
def attention_decode_cached(
    q: jnp.ndarray,  # [B, H, D]
    k_cache: jnp.ndarray,  # [L, P, ps, K*D] read-only cache (fused lanes)
    v_cache: jnp.ndarray,
    hk: jnp.ndarray,  # [B, N, K*D] horizon side buffer (this layer)
    hv: jnp.ndarray,
    n_extra,  # scalar: valid side rows (current token included)
    layer,  # scalar layer index
    page_tables: jnp.ndarray,  # [B, mp]
    entry_positions: jnp.ndarray,  # [B] cache token count at horizon entry
    scale: float,
    softcap: float | None = None,
    window: jnp.ndarray | None = None,  # scalar sliding window (<=0 = global)
    lanes_sharded: bool = False,  # the cache's lane axis is split over a mesh
) -> jnp.ndarray:
    """XLA fallback for the horizon-decode attention: cache pages (tokens <
    entry) plus the first n_extra side-buffer rows, one joint softmax.
    Mirrors ``smg_tpu/ops/pallas/decode_attention.py``."""
    N = hk.shape[1]
    # the query sits at entry + n_extra - 1
    q_pos = (entry_positions + n_extra - 1)[:, None]
    side_visible = (jnp.arange(N) < n_extra)[None, :]
    return _attend_cache_and_side(
        q[:, None], k_cache, v_cache, hk, hv, layer, page_tables,
        entry_positions, q_pos, side_visible, scale, softcap, window,
        lanes_sharded,
    )[:, 0]


@jax.named_scope("smg.attn.decode")
def attention_verify_block(
    q: jnp.ndarray,  # [B, W, H, D] one verify block per lane (post-rope)
    k_cache: jnp.ndarray,  # [L, P, ps, K*D] read-only cache (fused lanes)
    v_cache: jnp.ndarray,
    bk: jnp.ndarray,  # [B, W, K*D] block side buffer (this layer)
    bv: jnp.ndarray,
    layer,  # scalar layer index
    page_tables: jnp.ndarray,  # [B, mp]
    entry_positions: jnp.ndarray,  # [B] cache token count at block entry
    scale: float,
    softcap: float | None = None,
    window: jnp.ndarray | None = None,  # scalar sliding window (<=0 = global)
    lanes_sharded: bool = False,  # the cache's lane axis is split over a mesh
) -> jnp.ndarray:
    """Attention for a speculative verify block: W query tokens per lane
    (the last committed token plus the drafted columns) against the lane's
    frozen cache pages (positions < entry) PLUS the block's own K/V rows,
    causal within the block.  The block K/V lives in side buffers, NOT the
    cache — the caller scatters only the ACCEPTED columns after the
    acceptance decision, which is how rejected drafts' KV ends up on the
    garbage page instead of poisoning real slots.  The multi-query cousin of
    ``attention_decode_cached`` (same gather, same joint softmax)."""
    w = jnp.arange(q.shape[1])
    # side row i is visible to query column w iff i <= w (causal in the block)
    return _attend_cache_and_side(
        q, k_cache, v_cache, bk, bv, layer, page_tables, entry_positions,
        entry_positions[:, None] + w[None, :], w[None, :] <= w[:, None],
        scale, softcap, window, lanes_sharded,
    )
