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
**blocks** of ``pages_per_block`` pages (``_pages_per_block``: 512 tokens,
128 where a page is wide, so that the double buffers stay within a few MiB of
VMEM).  A block's page copies signal one semaphore a stream and slot; the
next block's are in flight while the current block is multiplied, and a
lane's last block starts the next lane's first, so the kernel waits for HBM
with nothing behind it only at the very first block of the call.  A lane's
last block fetches the pages it has and masks the rest by ``entry``.  Per
block there is one score product ``[H, K*D] x [K*D, block tokens]``, one
softmax update and one rescale of the ``[H, K*D]`` accumulator; the side rows
are scored first, while the first two blocks are on their way, and seed the
running maximum and sum.

**What a block costs beyond its bytes is built out of the loop**, in both
bodies.  As loops over a count, a page an iteration, the starts and the
waits of a block were regions of their own that nothing else is scheduled
into: 55 bundles a page to start K and V (4 bounds checks among them) and 6
to wait for them, about 1,520 bundles (1.2 us) a block of 16 pages at
``mimo-v2-flash``'s 768 + 512 lanes whose bytes take 0.80 us, 1,150 (0.9 us)
at ``nemotron-3-super-120b-a12b``'s 256 lanes whose bytes take 0.32
(``scripts/kernel_schedule.py`` counts them off the compiler's schedule).
PR 30 timed loops and unrolled code at ``eval``'s and ``gen``'s shapes
(16-30 heads on 1,024-3,840 lanes) and found them alike; that holds where a
block's bytes (1 MB, 1.28 us) take as long as its loops and nowhere else
(``PERF.md`` section 6, PR 30, PR 45 and PR 48).  So ``_start_pages``,
``_wait_pages`` and ``_zero_slots`` serve the paged body (``_decode_kernel``:
two streams, K and V, a window's first live page, a verify column's rows) and
the latent one (``_latent_decode_kernel``: one buffer whose entries are keys
and, in their first lanes, values; 64-128 heads on 640 lanes), and both
(a) start a block's pages with one predicated copy a page and stream and no
loop, straight-line code in the block of the loop that holds the products,
with whether anything follows folded into the page count (the paged body
traces a page's code once and has it unrolled when the kernel is lowered,
and leaves the start that only lane 0 and a lane behind an empty one take a
loop: the same schedule, and a third of the tracing a decode program's
set-up pays; ``_start_pages``);
(b) wait once a stream for a full block: the semaphore counts bytes, so one
wait a set bit of the block's page count takes a short block too;
(c) read the tables as one row and a cache as ``[L * P, ps, lanes]``, a page
a row of the leading axis, which is a third less address arithmetic a copy,
and leave the copies' bounds checks out (13 of a start's 27 scalar
operations);
(d) start a lane's second block at the lane's top, into the slot the lane
before has left, so that its copies are issued beside the side rows'
products and HBM works through a lane's fixed part (the loop then starts
this lane's blocks from the third on, and the next lane's first);
(e) zero the value buffer once in a loop over a count, where a ``pl.when``
round the stores became predicated stores in every lane's code.
A block is ``BLOCK_TOKENS`` (512) tokens in both, so that every per-block part
halves: timed at 256, 384 and 512 on the latent cache (PR 45) and on the paged
one (PR 48: at ``mimo-v2-flash``'s widths a block of 256 tokens takes 0.97 us
in blocks of 256 and 0.87 in blocks of 512, which is what its copies alone
take, 757 GB/s; at 1,024 lanes the bytes bound either).  A lane's last block
is multiplied whole whatever it holds.
The operand roles are the same in both: a block of the cache is the MXU's
stationary operand in both products; the block as the streamed operand
(``s^T = keys . q^T``) was timed on the latent cache and is slower at 64 and
at 128 heads (the second product then wants the values transposed).  What
the plain interpreter cannot see of this loop: a block started twice leaves
its semaphore above zero, which the chip refuses at the kernel's exit;
``tests/test_pallas_decode.py`` walks the rule in plain Python and runs the
kernels under the interpreter that models the semaphores.

Operands are the XLA form's (``ops.attention._attend_cache_and_side``): K, V
and the query in the cache's dtype on the MXU with float32 accumulation, the
probabilities cast to the cache's dtype for the product with V, maxima and
sums in float32.  No float32 copy of a page is made.

Tiling: pages are viewed as fused ``[ps, K*D]`` tiles (whole 128-lane tiles:
256 lanes at ``nemotron-3-super-120b-a12b``'s 2 KV heads, 3,840 at
``olmo-hybrid-7b``'s 30).  GQA is folded into the matmuls with block-diagonal
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
BLOCK_TOKENS = 512  # tokens a compute step, where the buffers allow
BLOCK_BUFFER_BYTES = 2**20  # one slot of one stream (K, V or the latent entries)


def _pages_per_block(ps: int, lanes: int, itemsize: int, mp: int) -> int:
    """Pages a compute step: ``BLOCK_TOKENS`` tokens (32 pages of 16: a slot
    of 262,144 B at 2 KV heads of 128, 655,360 B of latent entries, 786,432
    B of ``mimo-v2-flash``'s keys, the limit itself at 8 heads of 128), fewer
    where a page is so wide (30 heads of 128: 122,880 B, 8 pages) that a
    slot of them would pass ``BLOCK_BUFFER_BYTES``, never more than the
    table has."""
    by_bytes = BLOCK_BUFFER_BYTES // (ps * lanes * itemsize)
    return max(1, min(BLOCK_TOKENS // ps, by_bytes, mp))


_div = jax.lax.div  # of non-negative ints (``//`` lowers through sign())


def _lane_pages(entry_pos_ref, lane, *, mp: int, ps: int, n: int, n_extra=None, window=None):
    """(entry, lo, first live page, pages held, blocks) of a lane.  The
    cache holds tokens 0..entry-1; a padded row (``entry`` at or past the
    table's capacity) holds none.  Sliding window: the query sits at
    ``entry + n_extra - 1`` and keys below ``lo`` are outside it, so
    whole pages below it are SKIPPED: blocks are counted from the
    window's first live page, which is the point of sliding-window
    attention at long contexts (Mistral W=4096).  Without a window
    (``None``: the latent cache has none) ``lo`` and ``first`` are 0."""
    entry = entry_pos_ref[lane]
    n_pages = jnp.where(entry >= mp * ps, 0, _div(entry + ps - 1, ps))
    if window is None:
        return entry, 0, 0, n_pages, _div(n_pages + n - 1, n)
    q_pos = entry + n_extra - 1
    lo = jnp.where(window > 0, jnp.maximum(q_pos - window + 1, 0), 0)
    first = jnp.minimum(_div(lo, ps), n_pages)
    return entry, lo, first, n_pages, _div(n_pages - first + n - 1, n)


def _start_pages(tables_ref, streams, layer_row, lane, page0, held, slot, *, n: int, ps: int,
                 mp: int, unroll: bool | None = None):
    """Start the pages ``page0 .. min(page0 + n, held) - 1`` of a lane (the
    tables are one row, ``mp`` entries a lane) into a slot of each stream
    ``(cache [L * P, ps, lanes], buffer, semaphores [2])``, the layer's pages
    from row ``layer_row`` of a cache on: one predicated copy a page and
    stream and no loop, so that they are straight-line code (a loop over a
    count is a region of its own, 40 bundles a page copy with nothing else in
    them).  What a program's set-up pays is the tracing and the lowering of
    ``n`` predicated regions a call of this, so ``unroll`` says how the
    page's code is written out: ``True`` traces it once and unrolls it when
    it is lowered (a loop of ``n`` steps unrolled ``n`` times is the same
    straight-line code, a third of a second less tracing a kernel at 32
    pages); ``False`` leaves it a loop at run time, for a start that few
    lanes take; ``None`` unrolls it in Python, which the latent body keeps
    only because its traced text is held to what it was
    (``tests/test_pallas_decode.py``)."""
    def start_page(i, _=None):
        @pl.when(page0 + i < held)
        def _():
            page = tables_ref[lane * mp + page0 + i]
            row0 = i * ps if isinstance(i, int) else pl.multiple_of(i * ps, ps)
            for hbm, buf, sems in streams:
                pltpu.make_async_copy(hbm.at[layer_row + page],
                                      buf.at[slot, pl.ds(row0, ps)], sems.at[slot]).start()

    if unroll is None:
        for i in range(n):
            start_page(i)
    else:
        jax.lax.fori_loop(0, n, start_page, None, unroll=unroll)


def _wait_pages(streams, count, slot, *, n: int, ps: int):
    """Wait for the ``count`` pages of a slot's block, in each stream.  A
    semaphore counts bytes, so a descriptor of k pages waits for any k of
    them: one wait a set bit of ``count``, one in all for a full block."""
    for bit in range(n.bit_length()):
        k = 1 << bit

        @pl.when((count & k) != 0)
        def _():
            rows = pl.ds(0, k * ps)
            for _, buf, sems in streams:
                pltpu.make_async_copy(buf.at[1 - slot, rows], buf.at[slot, rows],
                                      sems.at[slot]).wait()


def _zero_slots(buf, when, *, n: int, ps: int):
    """Zero both slots of a buffer where ``when`` holds.  Rows of a slot past
    a short block keep what the block before left there, and before the first
    block that is whatever VMEM held: a masked probability of 0 times a NaN is
    a NaN (a masked score is replaced, so keys need no such care).  A loop
    over a count, because a ``pl.when`` round plain stores becomes predicated
    stores, which every lane would pay for."""
    def zero_page(i, _):
        rows = pl.ds(pl.multiple_of((i % n) * ps, ps), ps)
        buf[i // n, rows] = jnp.zeros((ps, buf.shape[2]), buf.dtype)
        return 0

    jax.lax.fori_loop(0, jnp.where(when, 2 * n, 0), zero_page, 0)


def _decode_kernel(
    # scalar prefetch
    page_tables_ref,  # [B * mp] int32 (SMEM): the tables, one row after another
    entry_pos_ref,  # [B] int32 (SMEM) — tokens in cache (exclusive bound)
    meta_ref,  # [3] int32 (SMEM): [n_extra, layer, window] (window<=0 = global)
    *refs,
    ps: int,
    n: int,  # pages a block
    mp: int,
    pages: int,  # P: pages a layer
    scale: float,
    softcap: float,
    rows: int = 1,  # > 1: a verify column, ``rows`` query rows a lane (``held_ref`` first)
):
    """The paged cache's body: two streams (K and V) through one loop over a
    lane's blocks, built as the latent body's is (module docstring)."""
    if rows > 1:
        # [B] int32 (SMEM): side rows a lane holds before this column's; row w
        # of the lane sees ``held + w + 1`` side rows
        held_ref, *refs = refs
    (q_ref,  # [1, H, KD] VMEM (block-diagonal query for this sequence)
     hk_ref,  # [1, N, KD] VMEM (horizon side buffer, rows 0..n_extra-1 valid)
     hv_ref,  # [1, N, VD] VMEM (VD: V's lanes, KD unless values are narrower than keys)
     k_hbm,  # [L * P, ps, KD] HBM (read-only cache): a page is one row of the leading axis
     v_hbm,
     out_ref,  # [1, H, VD] VMEM
     k_buf,  # [2, n*ps, KD] VMEM: two slots of one block each
     v_buf,
     acc_ref,  # [H, VD] f32
     slot_ref,  # [1] int32 SMEM: the slot this lane's first block is in
     k_sems,  # DMA sems [2 slots], one set a stream
     v_sems,
     ) = refs
    b = pl.program_id(0)
    B = pl.num_programs(0)
    H = q_ref.shape[1]
    N = hk_ref.shape[1]
    S = n * ps
    n_extra = held_ref[b] + 1 if rows > 1 else meta_ref[0]
    layer_row = meta_ref[1] * pages
    window = meta_ref[2]  # a verify column has none (``paged_attention_verify_cached``)
    streams = ((k_hbm, k_buf, k_sems), (v_hbm, v_buf, v_sems))
    lane_pages = functools.partial(_lane_pages, entry_pos_ref, mp=mp, ps=ps, n=n,
                                   n_extra=n_extra, window=window)
    start = functools.partial(_start_pages, page_tables_ref, streams, layer_row, n=n, ps=ps,
                              mp=mp)

    entry, lo, first, n_pages, blocks = lane_pages(b)
    nxt = jnp.minimum(b + 1, B - 1)
    _, _, next_first, next_pages, next_blocks = lane_pages(nxt)
    next_has_blocks = (b + 1 < B) & (next_blocks > 0)

    _zero_slots(v_buf, b == 0, n=n, ps=ps)

    # the slot this lane's first block is in; lane 0 starts its own, and a
    # lane without blocks hands the next lane's first block on at once
    slot0 = jnp.where(b == 0, 0, slot_ref[0])
    own_first = (b == 0) & (blocks > 0)

    @pl.when(own_first | ((blocks == 0) & next_has_blocks))
    def _start():
        # few lanes pass here (lane 0, and a lane behind one without blocks):
        # a loop at run time, which a program's set-up traces and lowers once
        start(jnp.where(own_first, b, nxt), jnp.where(own_first, first, next_first),
              jnp.where(own_first, n_pages, next_pages), slot0, unroll=False)

    # this lane's second block goes into the other slot now (the lane before
    # is done with it), so that its copies are started beside the side rows'
    # products and HBM works while a lane's fixed part runs
    start(b, first + n, jnp.where(blocks > 1, n_pages, 0), 1 - slot0, unroll=True)

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
    # from here on), while the first two blocks are on their way
    col = jax.lax.broadcasted_iota(jnp.int32, (H, N), 1)
    seen = n_extra
    if rows > 1:  # each row its own count: H is rows x heads here
        seen = n_extra + _div(jax.lax.broadcasted_iota(jnp.int32, (H, N), 0), H // rows)
    s_side = scores_of(hk_ref[0], jnp.where(col < seen, entry + col, -1))
    m0 = jnp.max(s_side, axis=1, keepdims=True)
    p_side = jnp.exp(s_side - m0)
    l0 = jnp.sum(p_side, axis=1, keepdims=True)
    acc_ref[...] = weigh(p_side, hv_ref[0])

    def body(j, carry):
        m_prev, l_prev = carry
        slot = (slot0 + j) & 1
        more = j + 1 < blocks  # else the next lane's first block
        # block j + 1 of this lane from its third on (the second was started
        # above), or the next lane's first.  Whether there is one is folded
        # into the count, not put round the starts: a branch is a region of
        # its own
        held = jnp.where(more, jnp.where(j > 0, n_pages, 0),
                         jnp.where(next_has_blocks, next_pages, 0))
        page0 = first + j * n
        start(jnp.where(more, b, nxt), jnp.where(more, page0 + n, next_first), held, 1 - slot,
              unroll=True)
        _wait_pages(streams, jnp.minimum(n_pages - page0, n), slot, n=n, ps=ps)
        pos = page0 * ps + jax.lax.broadcasted_iota(jnp.int32, (H, S), 1)
        s = scores_of(k_buf[slot], jnp.where(pos < entry, pos, -1))
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[...] = acc_ref[...] * alpha + weigh(p, v_buf[slot])
        return m_new, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

    _, l = jax.lax.fori_loop(0, blocks, body, (m0, l0))

    slot_ref[0] = (slot0 + blocks) & 1
    out_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)


def _paged_call(q_bd, hk, hv, k_cache, v_cache, prefetch, *, scale, softcap, rows,
                interpret, pages_per_block):
    """``_decode_kernel`` over a lane's ``q_bd`` [B, rows * H, KD]
    block-diagonal query rows: [B, rows * H, VD] in ``q_bd``'s dtype.
    ``prefetch``: the tables, the entry positions, the meta row and, for a
    verify column, the side rows held."""
    B, HR, KD = q_bd.shape
    L, P, ps, _ = k_cache.shape
    VD = v_cache.shape[3]  # KD, but where values are narrower than keys
    N = hk.shape[1]
    mp = prefetch[0].shape[1]
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
    tables, *scalars = (x.astype(jnp.int32) for x in prefetch)
    kernel = functools.partial(_decode_kernel, ps=ps, n=n, mp=mp, pages=P, scale=scale,
                               softcap=softcap, rows=rows)
    lane = lambda b, *_: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, HR, KD), lane),
            pl.BlockSpec((1, N, KD), lane),
            pl.BlockSpec((1, N, VD), lane),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, HR, VD), lane),
        scratch_shapes=[
            pltpu.VMEM((2, n * ps, KD), cd),
            pltpu.VMEM((2, n * ps, VD), cd),
            pltpu.VMEM((HR, VD), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, HR, VD), q_bd.dtype),
        # lanes in order: a lane's last block starts the next lane's first.
        # No bounds checks on the copies (13 of a start's 27 scalar
        # operations, ``PERF.md`` section 6, PR 45 and PR 48): a page index
        # comes from the table of a lane that holds the page, as in every
        # kernel that reads one
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             disable_bounds_checks=True),
        interpret=interpret,
    )(tables.reshape(B * mp), *scalars, q_bd, hk.astype(cd), hv.astype(cd),
      k_cache.reshape(L * P, ps, KD), v_cache.reshape(L * P, ps, VD))


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
    K = k_cache.shape[3] // q.shape[2]
    meta = jnp.stack([
        jnp.asarray(n_extra, jnp.int32),
        jnp.asarray(layer, jnp.int32),
        jnp.asarray(0 if window is None else window, jnp.int32),
    ])
    out_kd = _paged_call(block_diagonal_query(q.astype(k_cache.dtype), K), hk, hv, k_cache,
                         v_cache, (page_tables, entry_positions, meta), scale=scale,
                         softcap=float(softcap or 0.0), rows=1, interpret=interpret,
                         pages_per_block=pages_per_block)
    return own_lanes(out_kd, K).astype(q.dtype)


def _latent_decode_kernel(
    # scalar prefetch
    page_tables_ref,  # [B * mp] int32 (SMEM): the tables, one row after another
    entry_pos_ref,  # [B] int32 (SMEM)
    meta_ref,  # [2] int32 (SMEM): [n_extra, layer]
    q_ref,  # [1, H, W] VMEM: the absorbed queries on the entry's lanes
    side_ref,  # [1, N, W] VMEM
    cache_hbm,  # [L * P, ps, W] HBM: a page is one row of the leading axis
    out_ref,  # [1, H, latent] VMEM
    buf,  # [2, n * ps, W] VMEM: two slots of one block each
    acc_ref,  # [H, latent] f32
    slot_ref,  # [1] int32 SMEM: the slot this lane's first block is in
    sems,  # DMA sems [2 slots]
    *,
    ps: int,
    n: int,  # pages a block
    mp: int,
    pages: int,  # P: pages a layer
    scale: float,
    latent: int,
):
    """The latent cache's own body: one buffer whose entries are keys and,
    in their first ``latent`` lanes, values.  64-128 heads meet 640 lanes, so a
    block's bytes do not hide what the loop does beside them, and the loop is
    built round that (module docstring)."""
    b = pl.program_id(0)
    B = pl.num_programs(0)
    H = q_ref.shape[1]
    N = side_ref.shape[1]
    S = n * ps
    n_extra = meta_ref[0]
    layer_row = meta_ref[1] * pages
    lane_pages = functools.partial(_lane_pages, entry_pos_ref, mp=mp, ps=ps, n=n)

    entry, _, _, n_pages, blocks = lane_pages(b)
    nxt = jnp.minimum(b + 1, B - 1)
    _, _, _, next_pages, next_blocks = lane_pages(nxt)
    next_has_blocks = (b + 1 < B) & (next_blocks > 0)

    streams = ((cache_hbm, buf, sems),)
    start = functools.partial(_start_pages, page_tables_ref, streams, layer_row, n=n, ps=ps,
                              mp=mp)

    _zero_slots(buf, b == 0, n=n, ps=ps)

    # the slot this lane's first block is in; lane 0 starts its own, and a
    # lane without blocks hands the next lane's first block on at once
    slot0 = jnp.where(b == 0, 0, slot_ref[0])
    own_first = (b == 0) & (blocks > 0)

    @pl.when(own_first | ((blocks == 0) & next_has_blocks))
    def _start():
        start(jnp.where(own_first, b, nxt), 0, jnp.where(own_first, n_pages, next_pages), slot0)

    # this lane's second block goes into the other slot now (the lane before
    # is done with it), so that its copies are started beside the side rows'
    # products and HBM works while a lane's fixed part runs
    start(b, n, jnp.where(blocks > 1, n_pages, 0), 1 - slot0)

    def scores_of(keys, valid):
        s = jax.lax.dot_general(q_ref[0], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        return jnp.where(valid, s, NEG_INF)

    def weigh(p, vals):
        return jax.lax.dot_general(p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    # the side rows first (the current token's own row is among them, so the
    # maximum is finite from here on), while the first two blocks are on their way
    col = jax.lax.broadcasted_iota(jnp.int32, (H, N), 1)
    s_side = scores_of(side_ref[0], col < n_extra)
    m0 = jnp.max(s_side, axis=1, keepdims=True)
    p_side = jnp.exp(s_side - m0)
    l0 = jnp.sum(p_side, axis=1, keepdims=True)
    acc_ref[...] = weigh(p_side, side_ref[0, :, :latent])

    def body(j, carry):
        m_prev, l_prev = carry
        slot = (slot0 + j) & 1
        more = j + 1 < blocks  # else the next lane's first block
        # block j + 1 of this lane from its third on (the second was started
        # above), or the next lane's first.  Whether there is one is folded
        # into the count, not put round the starts: a branch is a region of
        # its own
        held = jnp.where(more, jnp.where(j > 0, n_pages, 0),
                         jnp.where(next_has_blocks, next_pages, 0))
        start(jnp.where(more, b, nxt), jnp.where(more, (j + 1) * n, 0), held, 1 - slot)
        _wait_pages(streams, jnp.minimum(n_pages - j * n, n), slot, n=n, ps=ps)
        pos = j * S + jax.lax.broadcasted_iota(jnp.int32, (H, S), 1)
        s = scores_of(buf[slot], pos < entry)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[...] = acc_ref[...] * alpha + weigh(p, buf[slot, :, :latent])
        return m_new, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

    _, l = jax.lax.fori_loop(0, blocks, body, (m0, l0))

    slot_ref[0] = (slot0 + blocks) & 1
    out_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)


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
    pages_per_block: int | None = None,  # None: ``_pages_per_block`` (tests set it)
) -> jax.Array:
    """Absorbed latent attention over a cache with no V buffer
    (``_latent_decode_kernel``).  All ``H`` query heads meet one "head" of
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
    meta = jnp.stack([jnp.asarray(n_extra, jnp.int32), jnp.asarray(layer, jnp.int32)])
    kernel = functools.partial(_latent_decode_kernel, ps=ps, n=n, mp=mp, pages=P, scale=scale,
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
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, latent), q.dtype),
        # lanes in order, as in the paged kernel.  No bounds checks on the
        # copies: 13 of a start's 27 scalar operations, a fifth of the call
        # (``PERF.md`` section 6, PR 45); a page index comes from the table of
        # a lane that holds the page, as in every kernel that reads one
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             disable_bounds_checks=True),
        interpret=interpret,
    )(page_tables.astype(jnp.int32).reshape(B * mp), entry_positions.astype(jnp.int32), meta,
      q.astype(cd), side.astype(cd), cache.reshape(L * P, ps, W))


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "pages_per_block"))
@jax.named_scope("smg.attn.decode")
def paged_attention_verify_cached(
    q: jax.Array,  # [B, W, H, D]: the W rows a lane verifies this column
    k_cache: jax.Array,
    v_cache: jax.Array,
    hk: jax.Array,  # [B, N, K*D] the frame's side buffer (this layer)
    hv: jax.Array,
    held: jax.Array,  # [B] int32: side rows a lane holds before this column's
    layer,
    page_tables: jax.Array,
    entry_positions: jax.Array,
    scale: float,
    interpret: bool = False,
    pages_per_block: int | None = None,
) -> jax.Array:
    """[B, W, H, D]: what ``ops.attention.attention_verify_cached`` computes.
    The kernel above with the W rows of a lane stacked on the query's head
    axis: a lane's pages are streamed once for all its rows, every row sees
    all of them, and row ``w`` sees the side rows up to ``held + w``."""
    B, W, H, D = q.shape
    KD, VD = k_cache.shape[3], v_cache.shape[3]
    K = KD // D
    meta = jnp.stack([jnp.int32(0), jnp.asarray(layer, jnp.int32), jnp.int32(0)])
    q_bd = block_diagonal_query(q.astype(k_cache.dtype), K).reshape(B, W * H, KD)
    out_kd = _paged_call(q_bd, hk, hv, k_cache, v_cache,
                         (page_tables, entry_positions, meta, held), scale=scale, softcap=0.0,
                         rows=W, interpret=interpret, pages_per_block=pages_per_block)
    return own_lanes(out_kd.reshape(B, W, H, VD), K).astype(q.dtype)
