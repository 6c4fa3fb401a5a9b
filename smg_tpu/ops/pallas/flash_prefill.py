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

Who calls it, at which widths.  ``models/llama.py`` and
``models/olmo_hybrid.py``: queries, keys and values of one width ``D`` (128).
``models/pangu_moe._prefill`` (openPangu-Ultra-MoE and both sublayers of a
LongCat-Flash layer; ``LatentModelRunner._grouped_prefill_impl_for``): the
expanded latent attention, ``K = H``, whose key is wider than its value and
in two parts: ``D`` lanes a head (128, ``k``) against values of ``Dv`` (128,
the accumulator's width), and ``dr`` rotary lanes (64) that every head of a
row shares, ``k_pe`` ``[G, T, dr]``.  The shared part is an operand of its
own, on one 128-lane tile with zeros behind it, whose block index ignores the
head, so the pipeline fetches it once a row; a block's score is
``q . k + q_pe . k_pe``, two products into one float32 tile.  Writing the
rotary key into every head's key instead (``[G, T, H, 256]``, no shared
operand: the kernel takes that too, a key wider than its value) timed 1.4 to
1.6 times slower on the chip (``PERF.md``, Findings, PR 44).  That caller
also passes ``k`` and ``v`` with the heads first, ``[G, H, T, d]``
(``kv_heads_first``), which is how a product batched by head leaves them
when the weights are stored by head: a head's block is one contiguous
``[T, d]``.  A caller that passes neither traces the program it traced before
the two existed.
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
# rows a program stacks at most (the query heads that share a KV head under one
# another, ``H/K * block_q``): its float32 scores and probabilities against a
# key block are two arrays of that many rows.  512 queries of 16 heads a KV
# head (``models/nemotron_h.py``, 32/2) are 8,192 rows and do not fit VMEM;
# up to 4 heads a KV head ``BLOCK_Q`` stands as it was timed.
STACKED_ROWS = 2048
BLOCK_K = 1024  # keys a step of the walk
VMEM_LIMIT_BYTES = 64 * 2**20


def _flash_kernel(t_real_ref, *refs, bq: int, bk: int, Gq: int, D: int, Dv: int, Dp: int,
                  scale: float):
    if Dp:  # a key part of ``Dp`` lanes that the row's heads share
        q_ref, qp_ref, k_ref, kp_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
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
        if Dp:
            qp = jnp.concatenate([qp_ref[0, :, j * Dp:(j + 1) * Dp] for j in range(Gq)], axis=0)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def rows(ref, k0):
            # ``bk`` keys or values of the head: the block is [1, T, d], or
            # [1, 1, T, d] where the heads lead (``kv_heads_first``)
            return ref[(0,) * (len(ref.shape) - 2) + (pl.ds(k0, bk), slice(None))]

        def step(i, masked: bool):
            k0 = pl.multiple_of(i * bk, bk)
            keys = rows(k_ref, k0)
            s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if Dp:
                s += jax.lax.dot_general(qp, kp_ref[0, pl.ds(k0, bk), :],
                                         (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
            s = s * scale
            if masked:
                row = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                col = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                live = (col <= row) & (col < t_real)
                s = jnp.where(jnp.concatenate([live] * Gq, axis=0), s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            vals = rows(v_ref, k0)
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
            o_ref[0, :, j * Dv:(j + 1) * Dv] = out[j * bq:(j + 1) * bq]


def _block(T: int, want: int) -> int:
    """``want`` halved until it divides ``T`` (``T`` itself below ``want``)."""
    b = min(T, want)
    while T % b:
        b //= 2
    return b


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "block_q", "block_k",
                                             "kv_heads_first"))
@jax.named_scope("smg.attn.prefill")
def flash_attention_prefill(
    q: jax.Array,  # [G, T, H, D] post-rope queries of cold rows (position = index)
    k: jax.Array,  # [G, T, K, D] the chunk's own keys
    v: jax.Array,  # [G, T, K, Dv]
    t_reals: jax.Array,  # [G] int32: real tokens a row (0: a padded row)
    scale: float,
    interpret: bool = False,
    block_q: int | None = None,  # None: ``BLOCK_Q`` (tests and the sweep set them)
    block_k: int | None = None,
    q_pe: jax.Array | None = None,  # [G, T, H, dr] the queries' part for ``k_pe``
    k_pe: jax.Array | None = None,  # [G, T, dr] a key part all heads of a row share
    kv_heads_first: bool = False,  # ``k`` [G, K, T, D] and ``v`` [G, K, T, Dv]
) -> jax.Array:
    """[G, T, H, Dv]: what ``attention_prefill_batched`` computes for rows at
    prefix 0 with ``ctx_lens = t_reals``, on every query below its row's
    ``t_real`` and on the padded queries of the block that holds ``t_real``;
    query blocks wholly past it are zeros.  With ``q_pe`` and ``k_pe`` a score
    is ``(q . k + q_pe . k_pe) * scale``: what
    ``ops.latent_attention.latent_attention_prefill`` computes.
    ``kv_heads_first``: keys and values with the heads before the tokens, as
    a product batched by head leaves them (the latent models' up-projections,
    whose weights are stored by head: asked for flat, XLA slices each layer's
    two weights out of their stack and copies them into another layout, 64
    MiB a layer and launch at 128 heads); a head's block is then one
    contiguous ``[T, d]``."""
    G, T, H, D = q.shape
    K, Dv = k.shape[1 if kv_heads_first else 2], v.shape[3]
    Gq = H // K
    if D % 128 or Dv % 128 or H % K:
        raise ValueError(f"{H}/{K} heads of {D} and {Dv}: the kernel slices lanes by whole "
                         "128-lane tiles; use the XLA form")
    bq = _block(T, block_q or min(BLOCK_Q, max(STACKED_ROWS // Gq, 8)))
    bk = _block(T, block_k or BLOCK_K)
    if bq % 8 or bk % 8:
        raise ValueError(f"{T} tokens in blocks of {bq} queries and {bk} keys: "
                         "not whole tiles; use the XLA form")

    def kv_block(g, h, i, t_real):
        live = t_real[g] > 0
        g, h = jnp.where(live, g, 0), jnp.where(live, h, 0)
        return (g, h, 0, 0) if kv_heads_first else (g, 0, h)

    def q_block(g, h, i, _):
        return g, i, h

    def kv(x, d):  # a head's keys or values of the whole row: [T, d] of it
        if kv_heads_first:
            return x, pl.BlockSpec((1, 1, T, d), kv_block)
        return x.reshape(G, T, K * d), pl.BlockSpec((1, T, d), kv_block)

    ins = [(q.reshape(G, T, H * D), pl.BlockSpec((1, bq, Gq * D), q_block)), kv(k, D), kv(v, Dv)]
    Dp = 0
    if k_pe is not None:
        # the shared part on whole tiles, zero lanes behind it: a query's are
        # written once here, the key's once a row and fetched once a row (its
        # block index ignores the head)
        Dp = -(-k_pe.shape[-1] // 128) * 128
        lanes = [(0, 0)] * (k_pe.ndim - 1) + [(0, Dp - k_pe.shape[-1])]
        ins.insert(1, (jnp.pad(q_pe, [(0, 0)] + lanes).reshape(G, T, H * Dp),
                       pl.BlockSpec((1, bq, Gq * Dp), q_block)))
        ins.insert(3, (jnp.pad(k_pe, lanes), pl.BlockSpec(
            (1, T, Dp), lambda g, h, i, t_real: (jnp.where(t_real[g] > 0, g, 0), 0, 0))))
    operands, in_specs = zip(*ins)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, K, T // bq),
        in_specs=list(in_specs),
        out_specs=pl.BlockSpec((1, bq, Gq * Dv), q_block),
        scratch_shapes=[
            pltpu.VMEM((Gq * bq, 1), jnp.float32),
            pltpu.VMEM((Gq * bq, 1), jnp.float32),
            pltpu.VMEM((Gq * bq, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, Gq=Gq, D=D, Dv=Dv, Dp=Dp, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, T, H * Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(t_reals.astype(jnp.int32), *operands)
    return out.reshape(G, T, H, Dv)
