"""``smg.linattn.decode`` and ``smg.kda.decode``: one decode token of the delta
rule with a decay as one pass over the state pool, in place.

For a lane and a block of heads the kernel reads the block of the state,
decays it, applies the delta update, takes the output and writes the block
back: the state crosses HBM once in each direction, which is all the
algorithm needs (``ops.linear_attention.gated_delta_step`` is the
specification, and what the CPU runs).

The pool is ``[layers, slots, dk, H * dv]`` float32 (heads fused on the minor
axis; ``ops/linear_attention.py`` says why), so a block is ``[dk, W]`` with
``W`` a whole number of heads and of 128-lane tiles.  Per head the math needs
``k`` and ``q`` spread over that head's ``dv`` lanes; the kernel builds those
``[dk, W]`` operands itself from the ``[dk, H]`` vectors with a 0/1 expansion
matrix on the MXU (three bfloat16 pieces, so the float32 values arrive
exactly) instead of reading them from HBM at the state's own size.  The rest
is elementwise work on the block and two reductions over ``dk``.

The slot of every lane and the layer arrive as scalar prefetch and pick the
block in the index map; the pool is aliased to the output, so blocks no lane
names are left as they were.

**Two bodies, one call.**  Where the decay is a number a head (``linattn_decode``;
``models/olmo_hybrid.py``) it arrives spread over the head's lanes as ``beta``
and ``v`` do, ``[1, W]``.  Where it is a number a key channel (``kda_decode``;
``models/kimi_linear.py``, ``ops.linear_attention.kda_step`` the
specification) it is one more ``[dk, Hp]`` operand that goes through the
expansion ``k`` and ``q`` go through, and the block is decayed before the
delta is taken.  The two are bodies of their own under one builder
(``_decode``): one body with the decay always expanded would have the other
rule pay a third expansion and a ``[dk, W]`` temporary for a number it has
once a head, and would change the program ``olmo-hybrid-7b`` compiles
(``scripts/time_kimi_linear.py`` times both at one shape; ``PERF.md``,
Findings, PR 50).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the most bytes of state one grid step holds (in, out, double buffered, and
# about four temporaries of the same size must fit the 16 MiB of scoped VMEM;
# the decay a channel is a fifth, the expanded ``alpha``, which still fits:
# it costs no block size)
_BLOCK_BYTES = 1 << 20


def heads_per_block(H: int, dk: int, dv: int) -> int | None:
    """Heads a block holds: the most that divide ``H``, keep the block's
    lanes a multiple of 128 and its bytes under ``_BLOCK_BYTES``; None where
    no count does (the caller then takes the XLA form)."""
    best = None
    for g in range(1, H + 1):
        if H % g == 0 and (g * dv) % 128 == 0 and dk * g * dv * 4 <= _BLOCK_BYTES:
            best = g
    return best


def supported(H: int, dk: int, dv: int) -> bool:
    """Whether a block of heads fits this shape, under either rule: the decay
    a channel adds one ``[dk, Hp]`` operand (``dk x 16`` floats a lane) and one
    expanded temporary of the block's size in VMEM, inside what
    ``_BLOCK_BYTES`` leaves."""
    return dk % 8 == 0 and heads_per_block(H, dk, dv) is not None


def _expand(xT, e):
    """``xT [dk, Hp] @ e [Hp, W]`` with ``e`` 0/1, exact for float32 ``xT``:
    three bfloat16 pieces, each product exact and one term an output."""
    out = None
    rest = xT
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        rest = rest - piece.astype(jnp.float32)
        part = jnp.dot(piece, e, preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def _kernel(slots_ref, layer_ref, kT_ref, qT_ref, e_ref, v_ref, a_ref, b_ref,
            s_ref, o_ref, s_out_ref):
    del slots_ref, layer_ref  # used by the index maps
    e = e_ref[...]
    kmat = _expand(kT_ref[0], e)  # [dk, W]: k of the lane's head on every lane
    qmat = _expand(qT_ref[0], e)
    S = s_ref[0, 0]  # [dk, W]
    alpha, beta, v = a_ref[0], b_ref[0], v_ref[0]  # [1, W]
    Sk = jnp.sum(S * kmat, axis=0, keepdims=True)
    u = beta * (v - alpha * Sk)
    S = alpha * S + kmat * u
    o_ref[0] = jnp.sum(S * qmat, axis=0, keepdims=True)
    s_out_ref[0, 0] = S


def _kernel_channel(slots_ref, layer_ref, kT_ref, qT_ref, aT_ref, e_ref, v_ref, b_ref,
                    s_ref, o_ref, s_out_ref):
    del slots_ref, layer_ref  # used by the index maps
    e = e_ref[...]
    kmat = _expand(kT_ref[0], e)  # [dk, W]
    qmat = _expand(qT_ref[0], e)
    S = _expand(aT_ref[0], e) * s_ref[0, 0]  # the decay of the row's channel, first
    beta, v = b_ref[0], v_ref[0]  # [1, W]
    u = beta * (v - jnp.sum(S * kmat, axis=0, keepdims=True))
    S = S + kmat * u
    o_ref[0] = jnp.sum(S * qmat, axis=0, keepdims=True)
    s_out_ref[0, 0] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
@jax.named_scope("smg.linattn.decode")
def linattn_decode(pool, layer, slots, q, k, v, alpha, beta, interpret: bool = False):
    """Same contract as ``ops.linear_attention.gated_delta_step``."""
    return _decode(pool, layer, slots, q, k, v, alpha, beta, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
@jax.named_scope("smg.kda.decode")
def kda_decode(pool, layer, slots, q, k, v, alpha, beta, interpret: bool = False):
    """Same contract as ``ops.linear_attention.kda_step``: ``alpha`` [B, H, dk]."""
    return _decode(pool, layer, slots, q, k, v, alpha, beta, interpret)


def _decode(pool, layer, slots, q, k, v, alpha, beta, interpret: bool):
    """The call of either body: ``alpha`` [B, H] picks the decay a head,
    [B, H, dk] the decay a channel."""
    channel = alpha.ndim == 3
    B, H, dk = q.shape
    dv = v.shape[-1]
    HV = H * dv
    g = heads_per_block(H, dk, dv)
    if g is None or dk % 8:
        raise ValueError(f"no block of heads fits H={H} dk={dk} dv={dv}; use the XLA form")
    W = g * dv
    Hp = -(-H // 16) * 16  # a whole bfloat16 tile of rows for the expansion
    f32 = jnp.float32
    pad = lambda x: jnp.pad(jnp.swapaxes(x.astype(f32), 1, 2), ((0, 0), (0, 0), (0, Hp - H)))
    lanes = lambda x: jnp.repeat(x.astype(f32), dv, axis=-1)[:, None, :]  # [B, 1, HV]
    expand = (jnp.arange(Hp)[:, None] == (jnp.arange(HV) // dv)[None, :]).astype(jnp.bfloat16)
    row = lambda b, c, *_: (b, 0, c)
    state = lambda b, c, slots_ref, layer_ref: (layer_ref[0], slots_ref[b], 0, c)
    heads = pl.BlockSpec((1, dk, Hp), lambda b, c, *_: (b, 0, 0))
    lane_row = pl.BlockSpec((1, 1, W), row)
    spread = pl.BlockSpec((Hp, W), lambda b, c, *_: (0, c))
    # the decay: one more operand to expand (before ``e``), or a row of lanes
    # (behind ``v``); the pool stays the seventh operand either way
    operands = ([heads, heads, heads, spread, lane_row, lane_row] if channel
                else [heads, heads, spread, lane_row, lane_row, lane_row])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, HV // W),
        in_specs=[*operands, pl.BlockSpec((1, 1, dk, W), state)],
        out_specs=[
            pl.BlockSpec((1, 1, W), row),
            pl.BlockSpec((1, 1, dk, W), state),
        ],
    )
    vrow = v.astype(f32).reshape(B, 1, HV)
    args = ((pad(k), pad(q), pad(alpha), expand, vrow, lanes(beta)) if channel
            else (pad(k), pad(q), expand, vrow, lanes(alpha), lanes(beta)))
    o, pool = pl.pallas_call(
        _kernel_channel if channel else _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, HV), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},  # the pool, counting the two prefetched
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(
        slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1), *args, pool,
    )
    return o.reshape(B, H, dv), pool
