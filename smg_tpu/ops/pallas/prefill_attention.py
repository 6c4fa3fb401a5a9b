"""Paged prefill attention: prefix-aware chunked prefill kernel.

Replaces the XLA ``gather_seq_kv`` + dense ``attention_prefill`` path for
long contexts (SURVEY.md §7 hard part (b)).  The gather path materializes
``mp*ps`` tokens per layer — the WORST-CASE context — so a short chunk
extending a long cached prefix pays for the whole page table.  This kernel
streams only the ``ceil(prefix_len/ps)`` pages that actually hold tokens
(HBM→VMEM, double-buffered DMA, same structure as
``decode_attention.py``), and keeps the chunk's own K/V in VMEM — they
never round-trip through the cache for attention.

Two attention ranges, merged in one online softmax:
  * cached prefix (tokens < prefix_len): full attention, streamed by page
    blocks of ``BT = max(ps, 128)`` tokens so score matmuls hit the MXU
    with a 128-deep N dim;
  * the chunk itself: causal within the chunk (query t attends chunk cols
    j <= t, j < t_real), read directly from VMEM.

GQA/head mapping: grid axis 0 is one program per group of ``C = max(1,
128//D)`` KV heads, so each program's lane slice of the fused ``[ps, K*D]``
cache page layout is 128-aligned even for D=64 models (Llama-3.2-1B).
Within a program the C heads are folded block-diagonally into the queries
(``q_bd[(t,c,g), c*D:(c+1)*D] = q[t, (c,g)]``) — one MXU matmul serves all
of them; the caller extracts each head's diagonal D-lane band afterwards.

Query tiling: grid axis 1 walks the chunk in tiles of ``TQ`` tokens
(``RQ = TQ*C*G`` query rows).  VMEM then holds one ``[RQ, max(BT, TQ)]``
score block whatever ``T`` is; holding all ``T*C*G`` rows and an ``[R, T]``
score block in one program needed 149 MB of VMEM at T=2048 on a v5e (128 MB)
and did not compile.  The price is that each tile streams the prefix again
(``T/TQ`` passes).  The chunk's own K/V stay in VMEM for every tile (their
block index does not change along axis 1); tile ``i`` attends chunk key
blocks ``0..i`` and tiles wholly past ``t_real`` do nothing.

Masking note: chunk tokens past the page-table capacity (``prefix_len + t >=
mp*ps``) are still attended here, while the XLA path drops them (they never
land in the gathered context).  The scheduler never admits such sequences;
documented for parity-test hygiene.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Query-tile width in tokens.  At the llama3.2-1b widths (C*G = 8) a tile is
# 2048 query rows: 1 MiB each for the f32 queries and accumulator, 2 MiB of
# statistics and 2 MiB per score block.  Not tuned: no time has been measured.
Q_TILE = 256


def _prefill_kernel(
    # scalar prefetch
    page_table_ref,  # [mp] int32 (SMEM)
    meta_ref,  # [4] int32 (SMEM): [prefix_len, t_real, layer, window]
    # inputs
    q_ref,  # [1, RQ, CD] VMEM — block-diagonal queries of this tile (RQ = TQ*C*G)
    ck_ref,  # [1, T, CD] VMEM — chunk keys (this program's lane slice)
    cv_ref,  # [1, T, CD] VMEM
    k_hbm,  # [L, P*ps, KD] HBM (read-only cache)
    v_hbm,
    # outputs
    out_ref,  # [1, RQ, CD] VMEM
    # scratch
    k_buf,  # [2, BT, CD] VMEM
    v_buf,
    acc_ref,  # [RQ, CD] f32
    stat_ref,  # [RQ, 256] f32 (col 0 = m, col 128 = l)
    sems,  # DMA sems [2, PPB, 2]
    *,
    ps: int,
    ppb: int,
    cg: int,  # C*G: query rows per chunk token
    scale: float,
    softcap: float,
):
    prog = pl.program_id(0)
    tile = pl.program_id(1)
    R = q_ref.shape[1]
    CD = q_ref.shape[2]
    tq = R // cg
    mp = page_table_ref.shape[0]
    bt = ppb * ps
    prefix_len = meta_ref[0]
    t_real = meta_ref[1]
    layer = meta_ref[2]
    window = meta_ref[3]
    lane0 = pl.multiple_of(prog * CD, CD)
    q0 = tile * tq  # chunk index of this tile's first query

    n_blocks = (prefix_len + bt - 1) // bt
    # sliding window: the EARLIEST query of the tile sits at prefix_len + q0,
    # so prefix blocks wholly below its window are skipped — the DMA loop
    # starts at the first block any query of the tile can still see
    lo_min = jnp.where(window > 0, jnp.maximum(prefix_len + q0 - window + 1, 0), 0)
    start_block = jnp.minimum(lo_min // bt, n_blocks)

    def dma(i, g, slot):
        idx = jnp.minimum(i * ppb + g, mp - 1)
        row0 = pl.multiple_of(page_table_ref[idx] * ps, ps)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, pl.ds(row0, ps), pl.ds(lane0, CD)],
                k_buf.at[slot, pl.ds(g * ps, ps)],
                sems.at[slot, g, 0],
            ),
            pltpu.make_async_copy(
                v_hbm.at[layer, pl.ds(row0, ps), pl.ds(lane0, CD)],
                v_buf.at[slot, pl.ds(g * ps, ps)],
                sems.at[slot, g, 1],
            ),
        )

    def start_dma(i, slot):
        for g in range(ppb):
            for c in dma(i, g, slot):
                c.start()

    def wait_dma(i, slot):
        for g in range(ppb):
            for c in dma(i, g, slot):
                c.wait()

    def cap(scores):
        if softcap:
            return softcap * jnp.tanh(scores / softcap)
        return scores

    def merge(scores, v_block):
        """Online-softmax merge of scores [R, S] with values [S, CD]."""
        m_prev = stat_ref[:, 0:1]
        l_prev = stat_ref[:, 128:129]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_block, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        stat_ref[:, 0:1] = m_new
        stat_ref[:, 128:129] = l_new

    @pl.when(q0 >= t_real)
    def _padding_tile():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    @pl.when(q0 < t_real)
    def _live_tile():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        stat_ref[:, 0:128] = jnp.full((R, 128), NEG_INF, jnp.float32)
        stat_ref[:, 128:256] = jnp.zeros((R, 128), jnp.float32)

        @pl.when(n_blocks > start_block)
        def _prologue():
            start_dma(start_block, jax.lax.rem(start_block, 2))

        q = q_ref[0].astype(jnp.float32)  # [R, CD]

        def score(keys):  # [S, CD] -> [R, S]
            return cap(jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale)

        def prefix_body(i, _):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start_dma(i + 1, jax.lax.rem(i + 1, 2))

            wait_dma(i, slot)
            scores = score(k_buf[slot].astype(jnp.float32))  # [R, BT]
            slot_pos = i * bt + jax.lax.broadcasted_iota(jnp.int32, (R, bt), 1)
            keep = slot_pos < prefix_len
            # per-row window cut: query row r sits at prefix_len + q0 + r//cg
            qpos_row = (prefix_len + q0
                        + jax.lax.broadcasted_iota(jnp.int32, (R, bt), 0) // cg)
            keep &= (window <= 0) | (slot_pos > qpos_row - window)
            merge(jnp.where(keep, scores, NEG_INF), v_buf[slot].astype(jnp.float32))
            return 0

        jax.lax.fori_loop(start_block, n_blocks, prefix_body, 0)

        # the chunk itself: causal, straight from VMEM, key blocks 0..tile.
        # The diagonal block comes last and holds every valid row's own key,
        # so a row whose earlier blocks were all masked still ends on a real
        # maximum (alpha = 0 wipes what the masked blocks accumulated).
        def chunk_body(j, _):
            c0 = pl.multiple_of(j * tq, tq)
            scores = score(ck_ref[0, pl.ds(c0, tq), :].astype(jnp.float32))  # [R, TQ]
            t_row = q0 + jax.lax.broadcasted_iota(jnp.int32, (R, tq), 0) // cg
            col = c0 + jax.lax.broadcasted_iota(jnp.int32, (R, tq), 1)
            keep = (col <= t_row) & (col < t_real)
            # both query and key sit at prefix_len + {t_row, col}: offsets cancel
            keep &= (window <= 0) | (col > t_row - window)
            merge(jnp.where(keep, scores, NEG_INF),
                  cv_ref[0, pl.ds(c0, tq), :].astype(jnp.float32))
            return 0

        jax.lax.fori_loop(0, tile + 1, chunk_body, 0)

        l = stat_ref[:, 128:129]
        out_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "softcap", "interpret", "q_tile")
)
@jax.named_scope("smg.attn.prefill")
def paged_attention_prefill(
    q: jax.Array,  # [T, H, D] post-rope chunk queries
    chunk_k: jax.Array,  # [T, K*D] post-rope chunk keys (fused lanes)
    chunk_v: jax.Array,  # [T, K*D]
    k_cache: jax.Array,  # [L, P, ps, K*D] cache (chunk already scattered — unused here)
    v_cache: jax.Array,
    layer,  # scalar int32
    page_table: jax.Array,  # [mp] int32
    prefix_len,  # scalar int32: cached tokens before this chunk
    t_real,  # scalar int32: valid chunk rows
    scale: float,
    softcap: float | None = None,  # tanh softcap on attn logits (Gemma-2)
    window=None,  # scalar int32 sliding window (None/<=0 = global)
    interpret: bool = False,
    q_tile: int = Q_TILE,  # tokens per query tile (tests shrink it)
) -> jax.Array:
    """Prefix-aware chunked-prefill attention for ONE sequence.
    Returns [T, H, D]; rows past ``t_real`` are unspecified."""
    T, H, D = q.shape
    L, P, ps, KD = k_cache.shape
    K = KD // D
    G = H // K
    C = max(1, min(K, 128 // D)) if D < 128 else 1
    if K % C != 0 or (not interpret and (C * D) % 128 != 0):
        raise ValueError(
            f"prefill kernel needs lane-sliceable heads: K={K}, D={D} "
            "(C*D must be a multiple of 128 and divide K*D); use the XLA fallback"
        )
    KC = K // C
    CD = C * D
    R = T * C * G
    ppb = max(1, 128 // ps)
    tq = min(T, q_tile)
    if T % tq:
        raise ValueError(f"chunk length {T} is not a multiple of the query tile {tq}")
    rq = tq * C * G

    # [T, H, D] -> [KC, T, C, G, D], then fold C block-diagonally into lanes
    q5 = q.reshape(T, KC, C, G, D).transpose(1, 0, 2, 3, 4)
    eye = jnp.eye(C, dtype=q.dtype)
    q_bd = (q5[:, :, :, :, None, :] * eye[None, None, :, None, :, None]).reshape(
        KC, R, CD
    )
    ck = chunk_k.reshape(T, KC, CD).transpose(1, 0, 2).astype(k_cache.dtype)
    cv = chunk_v.reshape(T, KC, CD).transpose(1, 0, 2).astype(v_cache.dtype)

    k2 = k_cache.reshape(L, P * ps, KD)
    v2 = v_cache.reshape(L, P * ps, KD)
    meta = jnp.stack([
        jnp.asarray(prefix_len, jnp.int32),
        jnp.asarray(t_real, jnp.int32),
        jnp.asarray(layer, jnp.int32),
        jnp.asarray(0 if window is None else window, jnp.int32),
    ])

    kernel = functools.partial(_prefill_kernel, ps=ps, ppb=ppb, cg=C * G,
                               scale=scale, softcap=float(softcap or 0.0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(KC, T // tq),
        in_specs=[
            pl.BlockSpec((1, rq, CD), lambda p, i, *_: (p, i, 0)),
            pl.BlockSpec((1, T, CD), lambda p, i, *_: (p, 0, 0)),
            pl.BlockSpec((1, T, CD), lambda p, i, *_: (p, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rq, CD), lambda p, i, *_: (p, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * ps, CD), k_cache.dtype),
            pltpu.VMEM((2, ppb * ps, CD), v_cache.dtype),
            pltpu.VMEM((rq, CD), jnp.float32),
            pltpu.VMEM((rq, 256), jnp.float32),
            pltpu.SemaphoreType.DMA((2, ppb, 2)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KC, R, CD), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32),
        meta,
        q_bd,
        ck,
        cv,
        k2,
        v2,
    )

    # [KC, R, CD] -> [KC, T, C, G, C', D]: head (c, g)'s output lives in its
    # own diagonal band c' == c
    out6 = out.reshape(KC, T, C, G, C, D)
    idx = jnp.arange(C)[None, None, :, None, None, None]
    diag = jnp.take_along_axis(out6, jnp.broadcast_to(idx, (KC, T, C, G, 1, D)),
                               axis=4)[:, :, :, :, 0]
    return diag.transpose(1, 0, 2, 3, 4).reshape(T, H, D)
