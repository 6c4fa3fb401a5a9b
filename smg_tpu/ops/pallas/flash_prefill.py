"""Prefill attention of cold rows as an online softmax over key blocks.

A grouped prefill whose rows all start their sequence (``no_ctx``) has its
keys and values on hand, ``[G, T, K, D]``, the chunk's own.  The XLA form
(``ops.attention.attention_prefill_batched``) scores the whole square in
float32, ``[G, T, K, H/K, T]``: up to 96 MiB of it XLA:TPU keeps on the chip,
and past that it goes to HBM and comes back for the maximum, the sum and the
product with V (1.15 ms a layer for one row of 2,048 tokens and 16 heads
where 1,024 tokens take 0.08; ``PERF.md``, Findings, PR 38).  Here nothing of
shape ``[T, T]`` leaves VMEM at any size, and ``ModelRunner.
_grouped_prefill_impl_for`` sends a program here from that size on.

Grid (row, KV head, query block).  A program holds the ``block_q`` queries
of the ``H/K`` heads that share the KV head, stacked ``[H/K * block_q, D]``,
and that head's keys and values of the whole row, which the pipeline fetched
once for all the row's query blocks (the index does not change along the
innermost axis) while the head before was being multiplied.  It walks the key
blocks up to the diagonal and stops there, or at the row's ``t_real`` if that
comes first: blocks below the diagonal are scored unmasked, the one on it is
masked by position, the ones above it are not touched.  A query block wholly
past ``t_real`` writes zeros, so a padded row (``t_real`` 0) multiplies
nothing, and its keys and values are not fetched either (their index map
answers row 0's first head for every one of them).  What is fetched and not
used: the keys of a real row between its ``t_real`` and ``T``, once a KV
head.

Operands are the XLA form's inputs as they come (bfloat16 in serving) on the
MXU with float32 accumulation; the running maximum, the running sum and the
``[H/K * block_q, D]`` accumulator are float32 in VMEM; the probabilities are
cast to V's dtype for the product with V, as ``decode_attention.py`` does.

GQA without a copy of K or V: ``q`` is viewed ``[G, T, H*D]`` and a program's
block is the ``H/K * D`` lanes of its KV head's query heads, ``k`` and ``v``
are viewed ``[G, T, K*D]`` and the block is the head's ``D`` lanes: every
lane slice a multiple of 128 when ``D`` is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Timed on a v5e (``scripts/time_prefill_attention.py``; the table is in
# ``PERF.md``, Findings, PR 38): keys in steps of 1,024 run a full 4,096-token
# row a fifth faster than steps of 512 (the rescale of the accumulator and the
# loop's own cost are paid half as often), and queries in blocks of 512 keep
# a row that ends just past a block's edge cheaper than blocks of 1,024 do.
BLOCK_Q = 512  # queries a program, a head
BLOCK_K = 1024  # keys a step of the walk
VMEM_LIMIT_BYTES = 64 * 2**20


def _flash_kernel(t_real_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, bq: int, bk: int, Gq: int, D: int, scale: float):
    t_real = t_real_ref[pl.program_id(0)]
    q0 = pl.program_id(2) * bq
    div = jax.lax.div  # of non-negative ints (``//`` lowers through sign())

    @pl.when(q0 >= t_real)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(q0 < t_real)
    def _attend():
        # the heads of the group under one another: one product a key block
        q = jnp.concatenate([q_ref[0, :, j * D:(j + 1) * D] for j in range(Gq)], axis=0)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def step(i, masked: bool):
            k0 = pl.multiple_of(i * bk, bk)
            keys = k_ref[0, pl.ds(k0, bk), :]
            s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if masked:
                row = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                col = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                live = (col <= row) & (col < t_real)
                s = jnp.where(jnp.concatenate([live] * Gq, axis=0), s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            vals = v_ref[0, pl.ds(k0, bk), :]
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[...] = m_new

        # key blocks wholly at or below the block's first query need no mask
        # (q0 < t_real, so they are below t_real too); the walk ends with the
        # block that holds the last key any of its queries may see
        whole = div(q0 + 1, bk)
        last = div(jnp.minimum(q0 + bq, t_real) + bk - 1, bk)
        jax.lax.fori_loop(0, whole, lambda i, _: step(i, False), None)
        jax.lax.fori_loop(whole, last, lambda i, _: step(i, True), None)

        # every query of the block met key q0 <= its own position, so l > 0
        out = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        for j in range(Gq):
            o_ref[0, :, j * D:(j + 1) * D] = out[j * bq:(j + 1) * bq]


def _block(T: int, want: int) -> int:
    """``want`` halved until it divides ``T`` (``T`` itself below ``want``)."""
    b = min(T, want)
    while T % b:
        b //= 2
    return b


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "block_q", "block_k"))
@jax.named_scope("smg.attn.prefill")
def flash_attention_prefill(
    q: jax.Array,  # [G, T, H, D] post-rope queries of cold rows (position = index)
    k: jax.Array,  # [G, T, K, D] the chunk's own keys
    v: jax.Array,  # [G, T, K, D]
    t_reals: jax.Array,  # [G] int32: real tokens a row (0: a padded row)
    scale: float,
    interpret: bool = False,
    block_q: int | None = None,  # None: ``BLOCK_Q`` (tests and the sweep set them)
    block_k: int | None = None,
) -> jax.Array:
    """[G, T, H, D]: what ``attention_prefill_batched`` computes for rows at
    prefix 0 with ``ctx_lens = t_reals``, on every query below its row's
    ``t_real`` and on the padded queries of the block that holds ``t_real``;
    query blocks wholly past it are zeros."""
    G, T, H, D = q.shape
    K = k.shape[2]
    Gq = H // K
    if D % 128 or H % K:
        raise ValueError(f"{H}/{K} heads of {D}: the kernel slices lanes by whole "
                         "128-lane tiles; use the XLA form")
    bq, bk = _block(T, block_q or BLOCK_Q), _block(T, block_k or BLOCK_K)
    if bq % 8 or bk % 8:
        raise ValueError(f"{T} tokens in blocks of {bq} queries and {bk} keys: "
                         "not whole tiles; use the XLA form")

    def kv_block(g, h, i, t_real):
        live = t_real[g] > 0
        return jnp.where(live, g, 0), 0, jnp.where(live, h, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, K, T // bq),
        in_specs=[
            pl.BlockSpec((1, bq, Gq * D), lambda g, h, i, _: (g, i, h)),
            pl.BlockSpec((1, T, D), kv_block),
            pl.BlockSpec((1, T, D), kv_block),
        ],
        out_specs=pl.BlockSpec((1, bq, Gq * D), lambda g, h, i, _: (g, i, h)),
        scratch_shapes=[
            pltpu.VMEM((Gq * bq, 1), jnp.float32),
            pltpu.VMEM((Gq * bq, 1), jnp.float32),
            pltpu.VMEM((Gq * bq, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, Gq=Gq, D=D, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, T, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(t_reals.astype(jnp.int32), q.reshape(G, T, H * D), k.reshape(G, T, K * D),
      v.reshape(G, T, K * D))
    return out.reshape(G, T, H, D)
