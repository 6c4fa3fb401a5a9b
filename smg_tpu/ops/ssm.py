"""The state-space recurrence of Mamba-2 and its causal depthwise convolution.

One head keeps a state ``S`` of ``[P, N]`` floats for every sequence (``P`` the
head's width, ``N`` the state size), rewritten at every token ``t``::

    S_t = a_t S_{t-1} + (dt_t x_t) B_t^T        y_t = S_t C_t

with ``a_t = exp(dt_t A)`` in (0, 1] the head's decay, ``dt_t > 0`` its step,
``x_t`` [P] its input and ``B_t``, ``C_t`` [N] the input and output vectors,
which the ``H / G`` heads of a group share.  It is the gated delta rule of
``ops/linear_attention.py`` without the delta term (nothing is read back out of
the state before a write), with ``B`` in the key's place and ``C`` in the
query's.  Three forms of it live here, as there:

- ``ssd_chunked``: prefill.  Chunks of ``chunk`` tokens (the model's
  ``chunk_size``, 128); inside a chunk the masked ``C B^T`` product weighted by
  the decay between the two positions, between chunks the carried state, so a
  2,048-token prompt is 16 dependent steps.  The chunks are one ``lax.scan``
  and everything a chunk needs is made inside its step: the decay weights of
  all chunks at once would be ``[G, H, T / chunk, chunk, chunk]`` float32, a
  gigabyte at 4,096 tokens and 128 heads.
- ``ssd_step``: decode in plain XLA (the CPU, interpret-free tests).
- ``ops/pallas/ssm_decode.py``: decode as one pass over the state on the chip;
  ``ssd_step`` is its specification.

**State layout.**  A pool holds the state transposed and with the heads fused
on the minor axis, ``[layers, slots, N, H * P]`` float32, as the gated delta
rule's pool is ``[layers, slots, dk, H * dv]``: ``B`` and ``C`` then run down
the sublanes and everything a head has of its own (``x``, ``dt``, the decay,
the output) lies along the lanes.  Slot 0 is the garbage slot.  The
convolution's tails lie beside it in a pool of their own in the model's dtype
(``models/nemotron_h.state_shapes`` says which layout and why).  Prefill reads
and writes the pools with ``ops/linear_attention.py``'s ``read_state``,
``write_state``, ``read_tail`` and ``write_tail``, and decode steps the tails
with them too (``conv_decode_step``).

**Prefill relays a row and never the pool.**  ``ssd_chunked`` carries the state
as ``[G, R, M, N, P]``, and where a head is narrower than a tile's 128 lanes
(``P`` 64 at the published widths, ``N`` 128) the TPU's layout assignment lays
that carry out with ``N`` on the lanes, whatever order the axes are written in
(the carry restated ``[G, R, M, P, N]`` compiles to the same program).  Left to
itself it carries the preference back through ``pool_to_heads`` and
``read_state``'s slice to the pool, whose layout as a parameter is fixed: the
copy then lands on the pool and not on the row, and every launch copied 1.5 GB
into the other layout, wrote its rows there and copied the pool home
(``PERF.md``, Findings, PR 56).  So ``models/nemotron_h._prefill`` puts a
``lax.optimization_barrier`` round the rows it reads and round the rows it
writes: a row, ``[N, H * P]`` and 4 MB, crosses it in the layout the pool lies
in, the transposition the scan wants is a copy of that row, and the pool is
updated where it lies.  A head of 128 or 192 lanes
(``models/kimi_linear.py``, ``models/olmo_hybrid.py``) makes the compiler want
no such thing of a one-row launch.

Everything here computes in float32 at ``highest`` matmul precision: the
products are under a percent of a layer's arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from smg_tpu.ops.linear_attention import (
    _mm,
    conv_chunk,
    conv_decode_step,
    heads_to_pool,
    pool_to_heads,
    read_state,
    write_state,
)

causal_conv = jax.named_scope("smg.ssm.conv")(conv_chunk)
conv_decode = jax.named_scope("smg.ssm.conv")(conv_decode_step)


@jax.named_scope("smg.ssm.scan")
def ssd_chunked(x, dt, g, B, C, S0, chunk: int):
    """The recurrence over a whole chunk of a prompt.

    ``x`` [G, T, H, P]; ``dt`` [G, T, H] the step and ``g`` [G, T, H] the log
    of the decay (``dt A``, <= 0), both 0 at a padded position, which then
    writes nothing and decays nothing; ``B``, ``C`` [G, T, R, N] (``R`` groups
    of ``H / R`` heads); ``S0`` [G, H, N, P] the state before the first token.
    Returns ``(y [G, T, H, P], S [G, H, N, P])``, float32."""
    f32 = jnp.float32
    G, T, H, P = x.shape
    R, N = B.shape[2:]
    M = H // R
    L = min(chunk, T)
    pad = (-T) % L
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, g, B, C = (widen(a) for a in (x, dt, g, B, C))
    n = (T + pad) // L

    def chunks(a, *lead):  # [G, T, prod(lead), ...] -> [n, G, *lead, L, ...]
        a = a.astype(f32).reshape(G, n, L, *lead, *a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 2 + len(lead))

    i = jnp.arange(L)
    lower = i[:, None] >= i[None, :]

    def step(S, xs):
        x_n, dt_n, g_n, B_n, C_n = xs  # [G, R, M, L, P], [G, R, M, L] x 2, [G, R, L, N] x 2
        gc = jnp.cumsum(g_n, axis=-1)  # log of the decay from the chunk's start to each position
        u = x_n * dt_n[..., None]
        diff = gc[..., :, None] - gc[..., None, :]  # [G, R, M, i, j]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        cb = _mm("grin,grjn->grij", C_n, B_n)
        y = _mm("grmij,grmjp->grmip", cb[:, :, None] * decay, u)
        y = y + _mm("grin,grmnp->grmip", C_n, S) * jnp.exp(gc)[..., None]
        last = gc[..., -1:]  # [G, R, M, 1]
        S = S * jnp.exp(last)[..., None] + _mm(
            "grjn,grmjp->grmnp", B_n, u * jnp.exp(last - gc)[..., None])
        return S, y

    S, y = lax.scan(step, S0.astype(f32).reshape(G, R, M, N, P),
                    (chunks(x, R, M), chunks(dt, R, M), chunks(g, R, M), chunks(B, R), chunks(C, R)))
    # [n, G, R, M, L, P] -> [G, T, H, P]
    y = jnp.moveaxis(y.reshape(n, G, H, L, P), (0, 3), (1, 2)).reshape(G, n * L, H, P)
    return y[:, :T], S.reshape(G, H, N, P)


@jax.named_scope("smg.ssm.decode")
def ssd_step(pool, layer, slots, x, dt, decay, B, C):
    """One token for every lane, reading and writing the pool's slots.

    ``pool`` [layers, slots, N, H * P] float32; ``slots`` [B]; ``x`` [B, H, P];
    ``dt``, ``decay`` [B, H] (a lane that does not run has ``dt`` 0 and
    ``decay`` 1, which leaves its state bit for bit); ``B``, ``C`` [B, R, N].
    Returns ``(y [B, H, P] float32, pool)``."""
    H = x.shape[1]
    M = H // B.shape[1]
    S = pool_to_heads(read_state(pool, layer, slots), H)  # [B, H, N, P]
    per_head = lambda a: jnp.repeat(a.astype(jnp.float32), M, axis=1)  # [B, H, N]
    u = x.astype(jnp.float32) * dt[..., None]
    S = decay[..., None, None] * S + per_head(B)[..., :, None] * u[..., None, :]
    y = _mm("bhnp,bhn->bhp", S, per_head(C))
    return y, write_state(pool, layer, slots, heads_to_pool(S))
