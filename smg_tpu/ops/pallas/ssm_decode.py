"""``smg.ssm.decode``: one decode token of the Mamba-2 recurrence as one pass
over the state pool, in place.

For a lane and a group of heads the kernel reads the group's block of the
state, decays it, adds the token's outer product, takes the output and writes
the block back: the state crosses HBM once in each direction, which is all the
algorithm needs (``ops.ssm.ssd_step`` is the specification, and what the CPU
runs).

The pool is ``[layers, slots, N, H * P]`` float32 (``ops/ssm.py`` says why), so
a group's block is ``[N, W]`` with ``W = (H / R) * P`` lanes.  **Why a body of
its own beside ``linattn_decode``'s**, which runs the same rule with a delta
term: there every head has a key and a query of its own, and the kernel spreads
them over the head's lanes with a 0/1 matrix on the MXU, six products a block.
Here the ``H / R`` heads of a group share ``B`` and ``C``, so a block of one
group needs each as one column, the same on every lane: the ``[1, N]`` row is
stood on end once a block (a 128 x 128 transpose) and the block's arithmetic is
four multiplies, two adds and a reduction over the sublanes, with no product at
all.  The trace's readers also tell the two kernels apart by their names.

The slot of every lane and the layer arrive as scalar prefetch and pick the
block in the index map; the pool is aliased to the output, so blocks no lane
names are left as they were.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the most bytes of state one grid step holds (in, out, double buffered, and a
# few temporaries of the same size must fit the 16 MiB of scoped VMEM)
_BLOCK_BYTES = 1 << 20


def supported(H: int, P: int, N: int, R: int) -> bool:
    """Whether the kernel's blocks fit the state's shape on a TPU: a group's
    lanes whole 128-lane tiles, the state size a whole number of 128 x 128
    transposes, a block inside ``_BLOCK_BYTES``."""
    W = (H // R) * P
    return H % R == 0 and W % 128 == 0 and N % 128 == 0 and N * W * 4 <= _BLOCK_BYTES


def _kernel(slots_ref, layer_ref, b_ref, c_ref, u_ref, a_ref, s_ref, y_ref, s_out_ref):
    del slots_ref, layer_ref  # used by the index maps
    r = pl.program_id(1)
    N, W = s_ref.shape[2:]
    tile = min(W, 128)

    def column(ref):
        """Row ``r`` of ``ref`` [1, R, N] stood on end and spread over the
        block's lanes: ``[N, W]``, the same column on every lane."""
        row = ref[0, pl.ds(r, 1), :]  # [1, N]
        col = jnp.broadcast_to(row, (tile, N)).T  # [N, tile]
        return col if W == tile else jnp.tile(col, (1, W // tile))

    S = a_ref[0] * s_ref[0, 0] + column(b_ref) * u_ref[0]
    y_ref[0] = jnp.sum(S * column(c_ref), axis=0, keepdims=True)
    s_out_ref[0, 0] = S


@functools.partial(jax.jit, static_argnames=("interpret",))
@jax.named_scope("smg.ssm.decode")
def ssm_decode(pool, layer, slots, x, dt, decay, B, C, interpret: bool = False):
    """Same contract as ``ops.ssm.ssd_step``."""
    nb, H, P = x.shape
    R, N = B.shape[1:]
    HP = H * P
    W = (H // R) * P
    if not interpret and not supported(H, P, N, R):
        raise ValueError(f"no block fits H={H} P={P} N={N} groups={R}; use the XLA form")
    f32 = jnp.float32
    lanes = lambda a: jnp.repeat(a.astype(f32), P, axis=-1)[:, None, :]  # [B, 1, HP]
    u = (x.astype(f32) * dt.astype(f32)[..., None]).reshape(nb, 1, HP)
    row = lambda b, r, *_: (b, 0, r)
    group = lambda b, r, *_: (b, 0, 0)
    state = lambda b, r, slots_ref, layer_ref: (layer_ref[0], slots_ref[b], 0, r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, R),
        in_specs=[
            pl.BlockSpec((1, R, N), group),
            pl.BlockSpec((1, R, N), group),
            pl.BlockSpec((1, 1, W), row),
            pl.BlockSpec((1, 1, W), row),
            pl.BlockSpec((1, 1, N, W), state),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, W), row),
            pl.BlockSpec((1, 1, N, W), state),
        ],
    )
    y, pool = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nb, 1, HP), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},  # the pool, counting the two prefetched
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(
        slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        B.astype(f32), C.astype(f32), u, lanes(decay), pool,
    )
    return y.reshape(nb, H, P), pool
