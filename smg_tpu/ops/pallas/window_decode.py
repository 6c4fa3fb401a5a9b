"""Decode attention of a sliding-window layer over a lane's ring.

What a sequence holds for a window layer is a ring of ``R`` entries
(``ops/window_attention.py``), ``[layers, slots, R, K*D]``: one block a lane,
fetched by the pipeline from the lane's slot (scalar prefetch), the next
lane's on its way while this one is multiplied.  No page table, no loop over
blocks: ``R`` is the window and a frame or two, a few hundred rows.

One program a lane: the side rows (this frame's columns, positions ``entry +
n``) and the ring (positions below ``entry``: entry ``s`` holds the largest
position ``<= entry - 1`` that is ``s mod R``) are scored by one product each
with the block-diagonal query (``ops.attention.block_diagonal_query``), masked
to the query's window by position, and joined in one softmax whose
denominator carries the sink's ``exp(b_h)``; the maximum starts from ``b_h``,
so it is finite whatever the masks leave.  Operands in the cache's dtype,
float32 accumulation, as the paged kernel has them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from smg_tpu.ops.attention import block_diagonal_query, own_lanes

NEG_INF = -1e30


def _window_kernel(slots_ref, entry_ref, meta_ref, q_ref, sink_ref, hk_ref, hv_ref,
                   rk_ref, rv_ref, out_ref, *, R: int, window: int, scale: float):
    del slots_ref  # read by the index maps
    b = pl.program_id(0)
    H = q_ref.shape[1]
    N = hk_ref.shape[1]
    entry = entry_ref[b]
    n_extra = meta_ref[0]
    lo = entry + n_extra - 1 - window  # keys at positions above ``lo`` are in the window
    q = q_ref[0]  # [H, K*Dk] block-diagonal, cache dtype

    def scores_of(keys, pos, live):
        s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        return jnp.where(live & (pos > lo), s, NEG_INF)

    def weigh(p, vals):
        return jax.lax.dot_general(p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    col = jax.lax.broadcasted_iota(jnp.int32, (H, N), 1)
    s_side = scores_of(hk_ref[0], entry + col, col < n_extra)
    e = jax.lax.broadcasted_iota(jnp.int32, (H, R), 1)
    # the largest position <= entry - 1 that is e mod R (``lax.rem`` of
    # non-negative operands: entry - 1 - e + R >= 0 for entry >= 0)
    ring_pos = entry - 1 - jax.lax.rem(entry - 1 - e + R, R)
    s_ring = scores_of(rk_ref[...], ring_pos, ring_pos >= 0)
    sink = sink_ref[...]  # [H, 1] float32 (NEG_INF: no sink)
    m = jnp.maximum(jnp.maximum(jnp.max(s_side, axis=1, keepdims=True),
                                jnp.max(s_ring, axis=1, keepdims=True)), sink)
    p_side, p_ring = jnp.exp(s_side - m), jnp.exp(s_ring - m)
    denom = (jnp.sum(p_side, axis=1, keepdims=True) + jnp.sum(p_ring, axis=1, keepdims=True)
             + jnp.exp(sink - m))
    acc = weigh(p_side, hv_ref[0]) + weigh(p_ring, rv_ref[...])
    out_ref[0] = (acc / denom).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
@jax.named_scope("smg.attn.window_decode")
def window_attention_decode(
    q: jax.Array,  # [B, H, Dk] post-rope queries
    ring_k: jax.Array,  # [L, slots, R, K*Dk] read-only rings
    ring_v: jax.Array,  # [L, slots, R, K*Dv]
    side_k: jax.Array,  # [B, N, K*Dk] the frame's side rows (this layer)
    side_v: jax.Array,  # [B, N, K*Dv]
    n_extra,  # scalar int32: valid side rows (the current token's among them)
    layer,  # scalar int32
    slots: jax.Array,  # [B] int32: each lane's slot (0: the garbage slot)
    entry_positions: jax.Array,  # [B] int32: tokens in the ring at the frame's entry
    window: int,
    sink,  # [H] float32 or None
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """[B, H, Dv]: what ``ops.window_attention.window_attention_decode``
    computes."""
    B, H, Dk = q.shape
    L, S, R, KD = ring_k.shape
    VD = ring_v.shape[3]
    K = KD // Dk
    N = side_k.shape[1]
    cd = ring_k.dtype
    if KD % 128 or VD % 128 or R % 8:
        raise ValueError(f"ring of {R} entries, {KD} and {VD} lanes: not whole tiles; "
                         "use the XLA form")
    if N == 1:  # see ``paged_attention_decode_cached``
        side_k, side_v = (jnp.pad(x, ((0, 0), (0, 1), (0, 0))) for x in (side_k, side_v))
        N = 2
    meta = jnp.stack([jnp.asarray(n_extra, jnp.int32), jnp.asarray(layer, jnp.int32)])
    sink = (jnp.full((H, 1), NEG_INF, jnp.float32) if sink is None
            else sink.astype(jnp.float32).reshape(H, 1))
    lane = lambda b, *_: (b, 0, 0)
    ring = lambda b, slots, entry, meta: (meta[1], slots[b], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, KD), lane),
            pl.BlockSpec((H, 1), lambda b, *_: (0, 0)),
            pl.BlockSpec((1, N, KD), lane),
            pl.BlockSpec((1, N, VD), lane),
            pl.BlockSpec((None, None, R, KD), ring),
            pl.BlockSpec((None, None, R, VD), ring),
        ],
        out_specs=pl.BlockSpec((1, H, VD), lane),
    )
    out = pl.pallas_call(
        functools.partial(_window_kernel, R=R, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, VD), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slots.astype(jnp.int32), entry_positions.astype(jnp.int32), meta,
      block_diagonal_query(q.astype(cd), K), sink, side_k.astype(cd), side_v.astype(cd),
      ring_k, ring_v)
    return own_lanes(out, K).astype(q.dtype)
