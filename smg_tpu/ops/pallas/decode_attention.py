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
  position, streamed HBM→VMEM with double-buffered DMA) and the first
  ``n_extra`` side-buffer rows (tokens fed during this horizon), merged in
  one online softmax.

Tiling: pages are viewed as fused ``[ps, K*D]`` tiles (K*D >= 512 lanes,
always 128-aligned).  GQA is folded into the matmuls with block-diagonal
queries (``q_bd[h, kh*D:(kh+1)*D] = q[h]``) so one MXU matmul serves all
heads; the ``p @ v`` product is ``[H, K*D]`` and the caller gathers each
head's D lanes afterwards.

Grid: one program per sequence; page tables, entry positions, step count and
layer index arrive via scalar prefetch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch
    page_tables_ref,  # [B, mp] int32 (SMEM)
    entry_pos_ref,  # [B] int32 (SMEM) — tokens in cache (exclusive bound)
    meta_ref,  # [3] int32 (SMEM): [n_extra, layer, window] (window<=0 = global)
    # inputs
    q_ref,  # [1, H, KD] VMEM (block-diagonal query for this sequence)
    hk_ref,  # [1, N, KD] VMEM (horizon side buffer, rows 0..n_extra-1 valid)
    hv_ref,  # [1, N, KD] VMEM
    k_hbm,  # [L, PS, KD] HBM (read-only cache)
    v_hbm,
    # outputs
    out_ref,  # [1, H, KD] VMEM
    # scratch
    k_buf,  # [2, ps, KD] VMEM
    v_buf,
    acc_ref,  # [H, KD] f32
    stat_ref,  # [H, 256] f32 (col 0 = m, col 128 = l)
    sems,  # DMA sems [2, 2]
    *,
    ps: int,
    scale: float,
    softcap: float,
):
    b = pl.program_id(0)
    H = q_ref.shape[1]
    N = hk_ref.shape[1]
    mp = page_tables_ref.shape[1]
    n_extra = meta_ref[0]
    layer = meta_ref[1]
    window = meta_ref[2]

    entry = entry_pos_ref[b]
    total_slots = mp * ps
    is_pad = entry >= total_slots
    # cache holds tokens 0..entry-1
    n_pages = jnp.where(is_pad, 0, (entry + ps - 1) // ps)
    # sliding window: the query sits at entry + n_extra - 1; keys below
    # ``lo`` are outside the window, so whole pages below it are SKIPPED —
    # the DMA loop starts at the window's first live page, which is the
    # point of sliding-window attention at long contexts (Mistral W=4096)
    q_pos = entry + n_extra - 1
    lo = jnp.where(window > 0, jnp.maximum(q_pos - window + 1, 0), 0)
    start_page = jnp.minimum(lo // ps, n_pages)

    def dma(i, slot):
        row0 = pl.multiple_of(page_tables_ref[b, i] * ps, ps)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, pl.ds(row0, ps)], k_buf.at[slot], sems.at[slot, 0]
            ),
            pltpu.make_async_copy(
                v_hbm.at[layer, pl.ds(row0, ps)], v_buf.at[slot], sems.at[slot, 1]
            ),
        )

    def start_dma(i, slot):
        for c in dma(i, slot):
            c.start()

    def wait_dma(i, slot):
        for c in dma(i, slot):
            c.wait()

    acc_ref[:] = jnp.zeros_like(acc_ref)
    stat_ref[:, 0:128] = jnp.full((H, 128), NEG_INF, jnp.float32)
    stat_ref[:, 128:256] = jnp.zeros((H, 128), jnp.float32)

    @pl.when(n_pages > start_page)
    def _prologue():
        start_dma(start_page, jax.lax.rem(start_page, 2))

    q = q_ref[0].astype(jnp.float32)  # [H, KD] block-diagonal

    def cap(scores):
        if softcap:
            return softcap * jnp.tanh(scores / softcap)
        return scores

    def merge(scores, v_block):
        """Online-softmax merge of one score block [H, S] with values [S, KD]."""
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

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_pages)
        def _prefetch():
            start_dma(i + 1, jax.lax.rem(i + 1, 2))

        wait_dma(i, slot)
        k = k_buf[slot].astype(jnp.float32)  # [ps, KD]
        v = v_buf[slot].astype(jnp.float32)
        scores = cap(jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale)  # [H, ps]
        slot_pos = i * ps + jax.lax.broadcasted_iota(jnp.int32, (H, ps), 1)
        scores = jnp.where((slot_pos < entry) & (slot_pos >= lo), scores, NEG_INF)
        merge(scores, v)
        return 0

    jax.lax.fori_loop(start_page, n_pages, body, 0)

    # in-flight horizon tokens (side rows sit at positions entry + col)
    hk = hk_ref[0].astype(jnp.float32)  # [N, KD]
    hv = hv_ref[0].astype(jnp.float32)
    s_extra = cap(jax.lax.dot_general(
        q, hk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale)  # [H, N]
    col = jax.lax.broadcasted_iota(jnp.int32, (H, N), 1)
    s_extra = jnp.where((col < n_extra) & (entry + col >= lo), s_extra, NEG_INF)
    merge(s_extra, hv)

    l = stat_ref[:, 128:129]
    out_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-20)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
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
) -> jax.Array:
    B, H, D = q.shape
    L, P, ps, KD = k_cache.shape
    K = KD // D
    N = hk.shape[1]
    G = H // K
    if KD % 128 != 0:
        raise ValueError(f"kv_heads*head_dim={KD} must be a multiple of 128 for the "
                         "pallas decode kernel; use the XLA fallback")

    head_kv = (jnp.arange(H) // G)[:, None]
    lane_kv = (jnp.arange(KD) // D)[None, :]
    mask = (head_kv == lane_kv).astype(q.dtype)
    q_bd = jnp.tile(q, (1, 1, K)) * mask[None]  # [B, H, KD]

    k2 = k_cache.reshape(L, P * ps, KD)
    v2 = v_cache.reshape(L, P * ps, KD)
    meta = jnp.stack([
        jnp.asarray(n_extra, jnp.int32),
        jnp.asarray(layer, jnp.int32),
        jnp.asarray(0 if window is None else window, jnp.int32),
    ])

    kernel = functools.partial(_decode_kernel, ps=ps, scale=scale,
                               softcap=float(softcap or 0.0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, KD), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, N, KD), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, N, KD), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, KD), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ps, KD), k_cache.dtype),
            pltpu.VMEM((2, ps, KD), v_cache.dtype),
            pltpu.VMEM((H, KD), jnp.float32),
            pltpu.VMEM((H, 256), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out_kd = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, KD), q.dtype),
        interpret=interpret,
    )(
        page_tables.astype(jnp.int32),
        entry_positions.astype(jnp.int32),
        meta,
        q_bd,
        hk.astype(k_cache.dtype),
        hv.astype(v_cache.dtype),
        k2,
        v2,
    )

    out4 = out_kd.reshape(B, H, K, D)
    idx = (jnp.arange(H) // G)[None, :, None, None]
    return jnp.take_along_axis(out4, jnp.broadcast_to(idx, (B, H, 1, D)), axis=2)[:, :, 0]
