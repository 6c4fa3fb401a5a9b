"""Paged decode attention over a read-only cache + in-flight side buffer.

TPU-native decode structure (multi-step horizon, ``runner.decode_multi``):

- The paged KV cache is **read-only** during the horizon's ``lax.scan``; each
  step's new K/V rows accumulate in a small per-layer side buffer carried
  through the scan ([L, B, N, K*D] — a few MB).  After the scan, one
  top-level scatter lands the whole horizon into the donated cache buffers,
  which XLA performs in place (``ops.attention.scatter_kv_rows``; written
  with the layer as a scatter window it is not in place, see there).  Designs
  that update the big cache *inside* the loop (functional scatters,
  layer-sliced scans, aliased kernel writes) risk a copy of the whole cache
  every step, and single-row in-kernel DMA writes violate sublane tiling; what
  such a copy costs has not been measured (ROADMAP D4).

- Attention therefore covers two ranges: cache pages (tokens < entry
  position, streamed HBM→VMEM) and the first ``n_extra`` side-buffer rows
  (tokens fed during this horizon), merged in one online softmax.

What is streamed: for each lane, the pages that lane holds and no others, in
**blocks** of ``pages_per_block`` pages (``_pages_per_block``: 256 tokens,
128 where a page is wide, so that the double buffer stays within a few MiB of
VMEM).  A block's page DMAs signal one semaphore per buffer and slot; the
next block's are in flight while the current block is multiplied, and a
lane's last block starts the next lane's first, so the kernel waits for HBM
with nothing behind it only at the very first block of the call.  A lane's
last block fetches the pages it has and masks the rest by ``entry``.  The
page loops are loops, not unrolled code: timed on a v5e the two run alike
(as do one wait a block and one a page), and a decode program is traced in
a third of the time (``PERF.md`` section 6, PR 30).  Per block there is one score product
``[H, K*D] x [K*D, block tokens]``, one softmax update and one rescale of the
``[H, K*D]`` accumulator; the side rows are scored first, while the first
block is on its way, and seed the running maximum and sum.

Operands are the XLA form's (``ops.attention._attend_cache_and_side``): K, V
and the query in the cache's dtype on the MXU with float32 accumulation, the
probabilities cast to the cache's dtype for the product with V, maxima and
sums in float32.  No float32 copy of a page is made.

Tiling: pages are viewed as fused ``[ps, K*D]`` tiles (K*D >= 512 lanes,
always 128-aligned).  GQA is folded into the matmuls with block-diagonal
queries (``ops.attention.block_diagonal_query``) so one MXU matmul serves
all heads; the ``p @ v`` product is ``[H, K*D]`` and each head keeps its own
D lanes afterwards (``ops.attention.own_lanes``).

Grid: one program per sequence, in order (the DMA pipeline runs across
them); page tables, entry positions, step count and layer index arrive via
scalar prefetch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from smg_tpu.ops.attention import block_diagonal_query, own_lanes

NEG_INF = -1e30
BLOCK_TOKENS = 256  # tokens a compute step, where the buffers allow
BLOCK_BUFFER_BYTES = 2**20  # one of the four (K, V) x (two slots) buffers


def _pages_per_block(ps: int, lanes: int, itemsize: int, mp: int) -> int:
    """Pages a compute step: ``BLOCK_TOKENS`` tokens, fewer where a page is
    so wide (30 heads of 128: 122,880 B) that a buffer of them would pass
    ``BLOCK_BUFFER_BYTES``, never more than the table has."""
    by_bytes = BLOCK_BUFFER_BYTES // (ps * lanes * itemsize)
    return max(1, min(BLOCK_TOKENS // ps, by_bytes, mp))


def _decode_kernel(
    # scalar prefetch
    page_tables_ref,  # [B, mp] int32 (SMEM)
    entry_pos_ref,  # [B] int32 (SMEM) — tokens in cache (exclusive bound)
    meta_ref,  # [3] int32 (SMEM): [n_extra, layer, window] (window<=0 = global)
    *refs,
    ps: int,
    n: int,  # pages a block
    scale: float,
    softcap: float,
    latent: int = 0,  # > 0: one latent buffer, whose first ``latent`` lanes are the values
):
    if latent:
        # a latent cache has no V: side rows, pages and block buffers are the
        # keys', and a value is the first ``latent`` lanes of its key
        q_ref, hk_ref, k_hbm, out_ref, k_buf, acc_ref, slot_ref, sems = refs
        hv_ref = v_hbm = v_buf = None
    else:
        (q_ref,  # [1, H, KD] VMEM (block-diagonal query for this sequence)
         hk_ref,  # [1, N, KD] VMEM (horizon side buffer, rows 0..n_extra-1 valid)
         hv_ref,  # [1, N, VD] VMEM (VD: V's lanes, KD unless values are narrower than keys)
         k_hbm,  # [L, P*ps, KD] HBM (read-only cache)
         v_hbm,
         out_ref,  # [1, H, VD] VMEM
         k_buf,  # [2, n*ps, KD] VMEM: two slots of one block each
         v_buf,
         acc_ref,  # [H, VD] f32
         slot_ref,  # [1] int32 SMEM: the slot this lane's first block is in
         sems,  # DMA sems [2 (K, V), 2 slots]
         ) = refs
    streams = (((k_hbm, k_buf, 0),) if latent
               else ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)))
    b = pl.program_id(0)
    B = pl.num_programs(0)
    H = q_ref.shape[1]
    N = hk_ref.shape[1]
    mp = page_tables_ref.shape[1]
    S = n * ps
    n_extra = meta_ref[0]
    layer = meta_ref[1]
    window = meta_ref[2]

    div = jax.lax.div  # of non-negative ints (``//`` lowers through sign())

    def lane_pages(lane):
        """(entry, lo, first live page, pages held, blocks) of a lane.  The
        cache holds tokens 0..entry-1; a padded row (``entry`` at or past the
        table's capacity) holds none.  Sliding window: the query sits at
        ``entry + n_extra - 1`` and keys below ``lo`` are outside it, so
        whole pages below it are SKIPPED: blocks are counted from the
        window's first live page, which is the point of sliding-window
        attention at long contexts (Mistral W=4096)."""
        entry = entry_pos_ref[lane]
        n_pages = jnp.where(entry >= mp * ps, 0, div(entry + ps - 1, ps))
        q_pos = entry + n_extra - 1
        lo = jnp.where(window > 0, jnp.maximum(q_pos - window + 1, 0), 0)
        first = jnp.minimum(div(lo, ps), n_pages)
        return entry, lo, first, n_pages, div(n_pages - first + n - 1, n)

    entry, lo, first, n_pages, blocks = lane_pages(b)
    nxt = jnp.minimum(b + 1, B - 1)
    _, _, next_first, next_pages, next_blocks = lane_pages(nxt)
    next_has_blocks = (b + 1 < B) & (next_blocks > 0)

    def fetch(own, j, slot, wait=False):
        """Start, or wait for, block ``j`` of this lane (``own``) or of the
        next one into a slot: the pages the lane has there, a loop over them
        (the lane that starts a block and the lane that waits for it work
        its bounds out from the same scalars)."""
        lane = jnp.where(own, b, nxt)
        page0 = jnp.where(own, first, next_first) + j * n
        count = jnp.minimum(jnp.where(own, n_pages, next_pages) - page0, n)

        def page(i, _):
            row0 = pl.multiple_of(page_tables_ref[lane, page0 + i] * ps, ps)
            dst = pl.ds(pl.multiple_of(i * ps, ps), ps)
            for hbm, buf, s in streams:
                copy = pltpu.make_async_copy(hbm.at[layer, pl.ds(row0, ps)],
                                             buf.at[slot, dst], sems.at[s, slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return 0

        jax.lax.fori_loop(0, count, page, 0)

    @pl.when(b == 0)
    def _first_lane():
        # rows of a slot past a short block keep what the block before left
        # there, and before the first block that is whatever VMEM held: a
        # masked probability of 0 times a NaN is a NaN (a masked score is
        # replaced, so K needs no such care)
        vals = k_buf if latent else v_buf
        vals[...] = jnp.zeros_like(vals)

    # the slot this lane's first block is in; lane 0 starts its own, and a
    # lane without blocks hands the next lane's first block on at once
    slot0 = jnp.where(b == 0, 0, slot_ref[0])
    own_first = (b == 0) & (blocks > 0)

    @pl.when(own_first | ((blocks == 0) & next_has_blocks))
    def _start():
        fetch(own_first, 0, slot0)

    q = q_ref[0]  # [H, KD] block-diagonal, cache dtype

    def scores_of(keys, pos):
        """Masked float32 scores [H, n_keys] of ``keys`` [n_keys, KD] at
        absolute positions ``pos`` [H, n_keys] (valid where below the bound
        the caller folds into ``pos``: a masked key is given position -1)."""
        s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        return jnp.where(pos >= lo, s, NEG_INF)

    def weigh(p, vals):
        return jax.lax.dot_general(p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    # in-flight horizon tokens first (side rows sit at positions entry + col;
    # the current token's own row is among them, so the maximum is finite
    # from here on), while the first block is on its way
    col = jax.lax.broadcasted_iota(jnp.int32, (H, N), 1)
    s_side = scores_of(hk_ref[0], jnp.where(col < n_extra, entry + col, -1))
    m0 = jnp.max(s_side, axis=1, keepdims=True)
    p_side = jnp.exp(s_side - m0)
    l0 = jnp.sum(p_side, axis=1, keepdims=True)
    values = (lambda ref, i: ref[i][:, :latent]) if latent else (lambda ref, i: ref[i])
    acc_ref[...] = weigh(p_side, values(hk_ref if latent else hv_ref, 0))

    def body(j, carry):
        m_prev, l_prev = carry
        slot = (slot0 + j) & 1

        more = j + 1 < blocks  # else the next lane's first block

        @pl.when(more | next_has_blocks)
        def _prefetch():
            fetch(more, jnp.where(more, j + 1, 0), 1 - slot)

        fetch(True, j, slot, wait=True)
        pos = (first + j * n) * ps + jax.lax.broadcasted_iota(jnp.int32, (H, S), 1)
        s = scores_of(k_buf[slot], jnp.where(pos < entry, pos, -1))
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[...] = acc_ref[...] * alpha + weigh(p, values(k_buf if latent else v_buf, slot))
        return m_new, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

    _, l = jax.lax.fori_loop(0, blocks, body, (m0, l0))

    slot_ref[0] = (slot0 + blocks) & 1
    out_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret",
                                             "pages_per_block"))
@jax.named_scope("smg.attn.decode")
def paged_attention_decode_cached(
    q: jax.Array,  # [B, H, D] post-rope queries
    k_cache: jax.Array,  # [L, P, ps, K*D] read-only cache (fused lanes)
    v_cache: jax.Array,
    hk: jax.Array,  # [B, N, K*D] horizon side buffer (this layer)
    hv: jax.Array,
    n_extra,  # scalar int32: valid side-buffer rows (current token included)
    layer,  # scalar int32
    page_tables: jax.Array,  # [B, mp] int32
    entry_positions: jax.Array,  # [B] int32: cache token count at horizon entry
    scale: float,
    softcap: float | None = None,  # tanh softcap on attn logits (Gemma-2)
    window=None,  # scalar int32 sliding window (None/<=0 = global)
    interpret: bool = False,
    pages_per_block: int | None = None,  # None: ``_pages_per_block`` (tests set it)
) -> jax.Array:
    B, H, D = q.shape
    L, P, ps, KD = k_cache.shape
    VD = v_cache.shape[3]  # KD, but where values are narrower than keys
    K = KD // D
    N = hk.shape[1]
    mp = page_tables.shape[1]
    cd = k_cache.dtype
    if KD % 128 != 0 or VD % 128 != 0:
        raise ValueError(f"kv_heads*head_dim={KD} must be a multiple of 128 for the "
                         "pallas decode kernel; use the XLA fallback")
    n = pages_per_block or _pages_per_block(ps, KD, cd.itemsize, mp)
    if N == 1:
        # Mosaic lowers a product over one row to a broadcast and trips on
        # its dtypes (bfloat16 operands, float32 result); a second row is
        # masked like any row past ``n_extra``
        hk, hv = (jnp.pad(x, ((0, 0), (0, 1), (0, 0))) for x in (hk, hv))
        N = 2

    meta = jnp.stack([
        jnp.asarray(n_extra, jnp.int32),
        jnp.asarray(layer, jnp.int32),
        jnp.asarray(0 if window is None else window, jnp.int32),
    ])

    kernel = functools.partial(_decode_kernel, ps=ps, n=n, scale=scale,
                               softcap=float(softcap or 0.0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, KD), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, N, KD), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, N, VD), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, VD), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n * ps, KD), cd),
            pltpu.VMEM((2, n * ps, VD), cd),
            pltpu.VMEM((H, VD), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out_kd = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, VD), q.dtype),
        # lanes in order: a lane's last block starts the next lane's first
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        page_tables.astype(jnp.int32),
        entry_positions.astype(jnp.int32),
        meta,
        block_diagonal_query(q.astype(cd), K),
        hk.astype(cd),
        hv.astype(cd),
        k_cache.reshape(L, P * ps, KD),
        v_cache.reshape(L, P * ps, VD),
    )
    return own_lanes(out_kd, K).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("latent", "scale", "interpret", "pages_per_block"))
@jax.named_scope("smg.attn.decode")
def latent_attention_decode_cached(
    q: jax.Array,  # [B, H, W] absorbed queries on the cache's lanes
    cache: jax.Array,  # [L, P, ps, W] read-only latent cache
    side: jax.Array,  # [B, N, W] horizon side buffer (this layer)
    n_extra,  # scalar int32: valid side-buffer rows (current token included)
    layer,  # scalar int32
    page_tables: jax.Array,  # [B, mp] int32
    entry_positions: jax.Array,  # [B] int32
    latent: int,  # the entry's first ``latent`` lanes are its value (a multiple of 128)
    scale: float,
    interpret: bool = False,
    pages_per_block: int | None = None,
) -> jax.Array:
    """Absorbed latent attention over a cache with no V buffer: the kernel
    above with one stream of pages.  All ``H`` query heads meet one "head" of
    ``W`` lanes (the latent and the rotary key, padded to whole 128-lane
    tiles); a key's first ``latent`` lanes are its value.  Returns ``sum p c``
    [B, H, latent] in ``q``'s dtype."""
    B, H, W = q.shape
    L, P, ps, _ = cache.shape
    N = side.shape[1]
    mp = page_tables.shape[1]
    cd = cache.dtype
    if W % 128 or latent % 128 or cache.shape[3] != W:
        raise ValueError(f"latent entries of {cache.shape[3]} lanes (values {latent}) "
                         "are not whole 128-lane tiles")
    n = pages_per_block or _pages_per_block(ps, W, cd.itemsize, mp)
    if N == 1:  # see ``paged_attention_decode_cached``
        side = jnp.pad(side, ((0, 0), (0, 1), (0, 0)))
        N = 2
    meta = jnp.stack([jnp.asarray(n_extra, jnp.int32), jnp.asarray(layer, jnp.int32),
                      jnp.int32(0)])
    kernel = functools.partial(_decode_kernel, ps=ps, n=n, scale=scale, softcap=0.0,
                               latent=latent)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, N, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, latent), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n * ps, W), cd),
            pltpu.VMEM((H, latent), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((1, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_tables.astype(jnp.int32), entry_positions.astype(jnp.int32), meta,
      q.astype(cd), side.astype(cd), cache.reshape(L, P * ps, W))
