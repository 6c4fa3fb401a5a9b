"""Grouped matrix product for routed experts: rows sorted by expert, each
group of rows against its own expert's weights.

The structure is the public megablox grouped matmul's.  ``rows`` [R, K] holds
the groups back to back (``group_sizes`` [G]); ``weights`` is [G, K, N], or
the whole stack of a model's layers [L, G, K, N] with the layer's index: a
layer sliced out of the stack first would be copied, because a kernel's
operand has to exist in memory (1.4 GB a layer at the benchmark's widths).  The
row axis is cut into tiles of ``tm`` rows.  A **visit** is one (group, row
tile) pair in which the group has rows: a group's rows may start and end
inside a tile, so a tile is visited once for every group that reaches into it,
and each visit writes only its own group's rows of the tile.  The list of
visits is worked out from ``group_sizes`` on the device and reaches the kernel
by scalar prefetch; the grid is ``(N tiles, most visits there can be, K
tiles)``.  Past the last real visit the index maps repeat it, so nothing is
fetched, and the body does nothing.  **A group without rows has no visit: its
weights are never read**, which is what makes a decode column cost the bytes
of the experts hit and not of the experts held.

Operands stay in the weights' dtype on the MXU with float32 accumulation.
Rows past the groups' total come back as zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tile(n: int, want: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most ``want``
    (``n`` itself where it is smaller or has no such divisor)."""
    if n <= want:
        return n
    for t in range(want - want % 128, 0, -128):
        if n % t == 0:
            return t
    return n


def tiling(R: int, K: int, N: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)``.  Set from a timing on a v5e at the benchmark's two
    regimes (``scripts/time_moe_experts.py``; PERF.md, Findings, PR 34): a
    decode column (a few rows an expert, bound by the bytes of the experts
    hit) wants weight blocks of a few MB and does not care for ``tm``; a
    prefill (a hundred rows an expert and more, bound by the MXU) wants
    ``tm`` 256."""
    tm = 128 if R <= 1024 else 256
    while R % tm:
        tm //= 2
    return tm, _tile(K, 1536 if K > 2048 else 2048), _tile(N, 1024 if N <= 2048 else 1536)


def visits(group_sizes, R: int, tm: int):
    """``(group of each visit, row tile of each visit, group starts [G + 1],
    number of visits)``; the lists are as long as the most visits there can
    be, ``R // tm + G - 1``, and repeat the last real visit past its end."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first_tile = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    n = visit_ends[-1]
    v = jnp.minimum(jnp.arange(R // tm + G - 1, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    group = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"), G - 1).astype(jnp.int32)
    tile = first_tile[group] + v - (visit_ends[group] - tiles[group])
    tile = jnp.clip(tile, 0, R // tm - 1).astype(jnp.int32)
    bounds = jnp.concatenate([starts[:1], ends]).astype(jnp.int32)
    return group, tile, bounds, n.astype(jnp.int32).reshape(1)


def _kernel(group_ref, tile_ref, bounds_ref, n_ref, _layer_ref, rows_ref, w_ref, out_ref,
            acc_ref, *, tm):
    v, kk = pl.program_id(1), pl.program_id(2)
    real = v < n_ref[0]

    @pl.when(real & (kk == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(real)
    def _multiply():
        acc_ref[...] += jnp.dot(rows_ref[...], w_ref[0, 0], preferred_element_type=jnp.float32)

    @pl.when(real & (kk == pl.num_programs(2) - 1))
    def _store():
        # only this group's rows of the tile; the others belong to the visits
        # before and after this one, which share the output block
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        own = (row >= bounds_ref[g]) & (row < bounds_ref[g + 1])
        out_ref[...] = jnp.where(own, acc_ref[...].astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret", "tiles"))
@jax.named_scope("smg.moe.experts")
def grouped_matmul(rows, weights, group_sizes, layer=0, interpret: bool = False,
                   tiles: tuple[int, int, int] | None = None):
    """``rows`` [R, K] x ``weights`` [G, K, N] (or layer ``layer`` of a stack
    [L, G, K, N]) by ``group_sizes`` [G] -> [R, N] in ``rows``' dtype."""
    R, K = rows.shape
    if weights.ndim == 3:
        weights = weights[None]
    _, G, _, N = weights.shape
    tm, tk, tn = tiles or tiling(R, K, N)
    if R % tm or K % tk or N % tn:
        raise ValueError(f"tiles {(tm, tk, tn)} do not divide {(R, K, N)}")
    group, tile, bounds, n = visits(group_sizes.astype(jnp.int32), R, tm)
    last = K // tk - 1

    def k_of(v, kk, n):  # past the last real visit: the block it ended on
        return jnp.where(v < n[0], kk, last)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N // tn, R // tm + G - 1, K // tk),
        in_specs=[
            pl.BlockSpec((tm, tk),
                         lambda j, v, kk, group, tile, _b, n, _l: (tile[v], k_of(v, kk, n))),
            pl.BlockSpec((1, 1, tk, tn),
                         lambda j, v, kk, group, tile, _b, n, l: (l[0], group[v], k_of(v, kk, n), j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, kk, group, tile, *_: (tile[v], j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(group, tile, bounds, n, jnp.asarray(layer, jnp.int32).reshape(1),
      rows.astype(weights.dtype), weights)
    # a tile no visit reached, and the rows past the last group, hold whatever
    # the buffer held
    return jnp.where((jnp.arange(R) < bounds[G])[:, None], out, 0)
