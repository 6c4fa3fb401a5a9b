"""The delta rule with a decay (Gated DeltaNet, Kimi Delta Attention) and its
causal depthwise convolution.

One head keeps a state ``S`` of ``[dv, dk]`` floats for every sequence,
rewritten at every token ``t``.  **Two rules** live here, which differ in what
the decay is:

- *a number a head* (the gated delta rule; ``models/olmo_hybrid.py``)::

      S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T      o_t = S_t q_t

  with ``a_t`` in (0, 1] and ``b_t`` in [0, 2] the write strength:
  ``gated_delta_chunked`` (``g`` [G, T, H]), ``gated_delta_step`` (``alpha``
  [B, H]), and the kernel ``linattn_decode``;
- *a number a key channel* (Kimi Delta Attention; ``models/kimi_linear.py``):
  ``a_t`` is a vector of ``dk`` and stands where the number stood, as
  ``Diag(a_t)`` on the state's key axis::

      S_t = (S_{t-1} Diag(a_t)) + b_t (v_t - S_{t-1} Diag(a_t) k_t) k_t^T

  ``kda_chunked`` (``g`` [G, T, H, dk]), ``kda_step`` (``alpha`` [B, H, dk])
  and the kernel ``kda_decode``.  With every channel of a head at one decay it
  is the first rule.

Three forms of each:

- chunked, for prefill.  Chunks of ``CHUNK`` tokens; inside a chunk the
  updates are solved together (the WY form: one unit-triangular system a
  chunk) and the state moves once a chunk, so a 4,096-token prompt is 64
  dependent steps and not 4,096.  The two rules share the solve and the scan
  over chunks (``_solve_and_scan``) and differ in how a chunk's decay weights
  are made: a head's ``[C, C]`` table of ``exp(G_i - G_j)`` factors out of
  the keys' products, a channel's does not, and the factored form ``(k_i
  exp(G_i)) . (k_j exp(-G_j))`` leaves float32 once a channel has fallen by
  e^-88 inside a chunk.  ``kda_chunked`` therefore takes every exponent of a
  difference ``<= 0``.  A chunk is worked in sub-blocks of ``SUB`` rows: what
  is serial (the solve's substitution) or a channel at a time (the
  ``[SUB, SUB, dk]`` decay weights) happens inside the diagonal blocks alone,
  and everything between blocks is a matmul.
- one token in plain XLA (the CPU, interpret-free tests);
- ``ops/pallas/linattn_decode.py``: decode as one pass over the state on the
  chip; the XLA steps are its specification.

**State layout.**  A pool holds the state transposed and with the heads fused
on the minor axis: ``[layers, slots, dk, H * dv]`` float32.  A ``[dv, dk]``
block per head would put ``dk`` (96) on the 128 lanes of a TPU tile, which pads
every row to 128 in HBM (a third more bytes to hold and to move); ``H * dv`` is
a multiple of 128 and ``dk`` a multiple of 8, so this form holds exactly the
floats the model has.  Slot 0 is the garbage slot, as page 0 is the garbage
page: padded rows of a batch point at it.  The convolution's tails lie beside
it in a pool of their own, ``[layers, slots, R, W]`` in the model's dtype
(``tail_block``): a slot's block is whole tiles there too.

Everything here computes in float32 at ``highest`` matmul precision: the
products are a few percent of a layer's arithmetic and the triangular solve
amplifies rounding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
# Rows of a sub-block of a chunk (``_sub_blocks``): four of them a chunk.
SUB = 16
_HI = lax.Precision.HIGHEST


def _mm(spec: str, *xs):
    return jnp.einsum(spec, *xs, precision=_HI, preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# the convolution


def conv_chunk(x: jnp.ndarray, tail: jnp.ndarray, weight: jnp.ndarray,
               t_real: jnp.ndarray, bias=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Causal depthwise convolution over time with SiLU, for a chunk.

    ``x`` [G, T, C] the chunk's inputs, ``tail`` [G, K-1, C] the last K-1
    inputs before the chunk (zeros at a sequence's start), ``weight`` [K, C],
    ``bias`` [C] or None, ``t_real`` [G] the real rows of each chunk.  Returns
    ``(y [G, T, C] float32, new tail)``: the new tail is the last K-1 inputs
    up to the last REAL row, so padded rows never enter it."""
    K = weight.shape[0]
    T = x.shape[1]
    xf = jnp.concatenate([tail.astype(jnp.float32), x.astype(jnp.float32)], axis=1)
    w = weight.astype(jnp.float32)
    y = sum(xf[:, i:i + T] * w[i] for i in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    new_tail = jax.vmap(
        lambda row, n: lax.dynamic_slice_in_dim(row, n, K - 1, axis=0)
    )(xf, t_real)
    return jax.nn.silu(y), new_tail.astype(tail.dtype)


def conv_token(x: jnp.ndarray, tail: jnp.ndarray, weight: jnp.ndarray, bias=None
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token of ``conv_chunk``: ``x`` [B, C], ``tail`` [B, K-1, C].
    Returns ``(y [B, C] float32, new tail [B, K-1, C])``."""
    xf = x.astype(jnp.float32)
    window = jnp.concatenate([tail.astype(jnp.float32), xf[:, None]], axis=1)
    y = jnp.einsum("bkc,kc->bc", window, weight.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y), window[:, 1:].astype(tail.dtype)


def conv_decode_step(pool: jnp.ndarray, layer, slots: jnp.ndarray, runs: jnp.ndarray,
                     x: jnp.ndarray, weight: jnp.ndarray, bias=None
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One decode token of the convolution for every lane, over the tails in
    the pool's slots: ``conv_token`` on ``read_tail``'s rows, the new tails
    written back.

    ``pool`` [layers, slots, R, W] (``tail_block``; or ``read_tail``'s flat
    row a slot); ``slots`` [B]; ``runs`` [B] bool (a lane that does not run
    keeps its tail bit for bit); ``x`` [B, C]; ``weight`` [K, C]; ``bias`` [C]
    or None.  Returns ``(y [B, C] float32, pool)``.  A slice and an update a
    lane, each a slot's whole tiles: a kernel over the same blocks (the slot
    picked in an index map, as the state kernels pick it) read a decode frame
    of ``kimi-linear-48b-a3b`` 6 % shorter on a v5e, under the 10 % its issue
    asked of it (``PERF.md``, Findings, PR 54)."""
    old = read_tail(pool, layer, slots, weight.shape[0] - 1)  # [B, K-1, C]
    y, tail = conv_token(x, old, weight, bias)
    return y, write_tail(pool, layer, slots, jnp.where(runs[:, None, None], tail, old))


# under this module's span; ``ops/ssm.py`` runs the same two under its own
causal_conv = jax.named_scope("smg.linattn.conv")(conv_chunk)
conv_decode = jax.named_scope("smg.linattn.conv")(conv_decode_step)
kda_causal_conv = jax.named_scope("smg.kda.conv")(conv_chunk)
kda_conv_decode = jax.named_scope("smg.kda.conv")(conv_decode_step)


# --------------------------------------------------------------------------
# prefill: the chunked form


def _sub_blocks(C: int) -> int:
    """Sub-blocks of ``SUB`` rows a chunk of ``C`` rows is worked in: a power
    of two of them, or the chunk whole as one block."""
    n = C // SUB
    return n if n * SUB == C and n & (n - 1) == 0 else 1


def _substitution(A: jnp.ndarray) -> jnp.ndarray:
    """``(I + A)^-1`` for strictly lower triangular ``A`` [c, c, B] by forward
    substitution one row at a time (row i needs rows < i).  The batch is the
    minor axis and fills the lanes; a step's products are elementwise."""
    c = A.shape[0]

    def row(i, T):
        a = lax.dynamic_index_in_dim(A, i, 0, keepdims=False)  # [c, B]
        new = -a - jnp.sum(a[:, None, :] * T, axis=0)
        return lax.dynamic_update_index_in_dim(T, new, i, 0)

    return lax.fori_loop(0, c, row, jnp.zeros_like(A)) + jnp.eye(c, dtype=A.dtype)[..., None]


def _unit_lower_inverse(A: jnp.ndarray) -> jnp.ndarray:
    """``(I + A)^-1`` for strictly lower triangular ``A`` [..., C, C]: the
    diagonal blocks of ``SUB`` rows by forward substitution, all of them and
    every chunk and head at once (``SUB`` dependent steps), then merged two by
    two, ``[[P, 0], [B, Q]]^-1 = [[P^-1, 0], [-Q^-1 B P^-1, Q^-1]]``, until
    one block is left."""
    *lead, C, _ = A.shape
    n = _sub_blocks(C)
    s = C // n
    block = lambda size, r, c: A[..., r * size:(r + 1) * size, c * size:(c + 1) * size]
    diag = jnp.stack([block(s, b, b) for b in range(n)], axis=-3)  # [..., n, s, s]
    inv = jnp.moveaxis(_substitution(jnp.moveaxis(diag.reshape(-1, s, s), 0, -1)), -1, 0)
    inv = inv.reshape(*lead, n, s, s)
    while n > 1:
        P, Q = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        B = jnp.stack([block(s, b + 1, b) for b in range(0, n, 2)], axis=-3)
        low = -_mm("...ij,...jk->...ik", _mm("...ij,...jk->...ik", Q, B), P)
        inv = jnp.concatenate([jnp.concatenate([P, jnp.zeros_like(P)], axis=-1),
                               jnp.concatenate([low, Q], axis=-1)], axis=-2)
        n, s = n // 2, 2 * s
    return inv[..., 0, :, :]


def _chunked(x, C: int):
    """``[G, T, H, ...]`` (``T`` a multiple of ``C``) as float32 chunks
    ``[N, G, H, C, ...]``: the chunks lead, so that the scan over them takes
    whole slices and a few of them at a time are a reshape away."""
    G, T = x.shape[:2]
    x = x.astype(jnp.float32).reshape(G, T // C, C, *x.shape[2:])
    return jnp.moveaxis(x, (1, 3), (0, 2))


def _padded(xs, T: int, chunk: int):
    """``xs`` padded along the tokens to whole chunks of ``C = min(chunk, T)``
    (a padded row has ``beta`` 0 and ``g`` 0: it writes and decays nothing),
    and ``C``."""
    C = min(chunk, T)
    pad = (-T) % C
    if pad:
        xs = [jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)) for x in xs]
    return xs, C


def _solve_and_scan(A, qk, k_in, v, beta, q_dec, k_tail, last, S0, T: int):
    """What the two rules share once a chunk's decay weights are made.  Per
    chunk ``[N, G, H, C, ..]``: ``A`` [C, C] strictly lower (``b_i`` times the
    keys' decayed products), ``qk`` [C, C] lower (the queries' with the keys),
    ``k_in`` the keys times ``b`` and their decay from the chunk's start,
    ``q_dec`` the queries times theirs, ``k_tail`` the keys times their decay
    to the chunk's end, ``last`` the whole chunk's decay (broadcast against the
    state ``[dk, dv]``).  Returns ``(o [G, T, H, dv], S)``."""
    N, G, H, C, dv = v.shape
    Tm = _unit_lower_inverse(A)
    k_cum = _mm("...ij,...jd->...id", Tm, k_in)
    v_new = _mm("...ij,...jd->...id", Tm, v * beta[..., None])

    def step(S, xs):
        k_cum_n, v_new_n, qk_n, q_dec_n, k_tail_n, last_n = xs
        v_n = v_new_n - _mm("ghcd,ghde->ghce", k_cum_n, S)
        o_n = _mm("ghcd,ghde->ghce", q_dec_n, S) + _mm("ghij,ghje->ghie", qk_n, v_n)
        S = S * last_n + _mm("ghcd,ghce->ghde", k_tail_n, v_n)
        return S, o_n

    S, o = lax.scan(step, S0.astype(jnp.float32), (k_cum, v_new, qk, q_dec, k_tail, last))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(G, N * C, H, dv)  # [N, G, H, C, dv] before
    return o[:, :T], S


@jax.named_scope("smg.linattn.prefill")
def gated_delta_chunked(q, k, v, g, beta, S0, chunk: int = CHUNK):
    """The recurrence over a whole chunk of tokens, the decay a number a head.

    ``q``, ``k`` [G, T, H, dk] (normalised, ``q`` scaled), ``v`` [G, T, H, dv],
    ``g`` [G, T, H] the log of the decay (<= 0), ``beta`` [G, T, H], ``S0``
    [G, H, dk, dv] the state before the first token.  A padded row has
    ``beta`` 0 and ``g`` 0: it writes nothing and decays nothing.  Returns
    ``(o [G, T, H, dv], S [G, H, dk, dv])``, float32."""
    T = q.shape[1]
    (q, k, v, g, beta), C = _padded((q, k, v, g, beta), T, chunk)
    q, k, v, g, beta = (_chunked(x, C) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # [N, G, H, C]
    i = jnp.arange(C)
    lower = i[:, None] >= i[None, :]
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)  # [.., C, C]
    kb = k * beta[..., None]
    A = jnp.where(i[:, None] > i[None, :], _mm("...id,...jd->...ij", kb, k) * decay, 0.0)
    qk = _mm("...id,...jd->...ij", q, k) * decay
    return _solve_and_scan(
        A, qk, kb * jnp.exp(gc)[..., None], v, beta, q * jnp.exp(gc)[..., None],
        k * jnp.exp(gc[..., -1:] - gc)[..., None], jnp.exp(gc[..., -1])[..., None, None], S0, T)


# Chunks whose ``[SUB, SUB, dk]`` decay weights ``kda_chunked`` makes at a
# time, every head and diagonal block of them: 32 heads of 128 key channels
# are 16 MiB a chunk of 64.
_KDA_CHUNKS_AT_ONCE = 4


def _kda_products(q, k, kb, gc):
    """A chunk's ``(A, qk)`` [..., C, C] (lower triangles, the diagonal
    included) of its rows [..., C, dk]: ``sum_d x_i[d] k_j[d] exp(G_i[d] -
    G_j[d])`` for ``x`` = ``kb`` and ``q``.  Inside a diagonal block of ``SUB``
    rows the weights are made for every pair and channel.  For a row block and
    the keys before it they factor about the block's first row, ``exp(G_i -
    G_ref) exp(G_ref - G_j)`` with ``j < ref <= i``, and the products are
    matmuls of the rescaled rows and keys: both exponents are of differences
    ``<= 0``, and what underflows in a factor is smaller still in the product
    it stands for."""
    C, dk = q.shape[-2:]
    n = _sub_blocks(C)
    s = C // n
    blocked = lambda x: x.reshape(*x.shape[:-2], n, s, dk)
    qs, ks, kbs, gs = blocked(q), blocked(k), blocked(kb), blocked(gc)
    i = jnp.arange(s)
    lower = (i[:, None] >= i[None, :])[..., None]
    w = jnp.where(lower, jnp.exp(jnp.where(
        lower, gs[..., :, None, :] - gs[..., None, :, :], 0.0)), 0.0) * ks[..., None, :, :]
    A = jnp.sum(kbs[..., :, None, :] * w, axis=-1)  # [..., n, s, s]
    qk = jnp.sum(qs[..., :, None, :] * w, axis=-1)
    if n == 1:
        return A[..., 0, :, :], qk[..., 0, :, :]
    ref = gs[..., :1, :]  # [..., n, 1, dk]
    rows = jnp.exp(gs - ref)
    before = (jnp.arange(C) < (jnp.arange(n) * s)[:, None])[..., None]  # [n, C, 1]
    keys = jnp.where(before, jnp.exp(jnp.where(
        before, ref - gc[..., None, :, :], 0.0)), 0.0) * k[..., None, :, :]  # [..., n, C, dk]
    left = _mm("...id,...jd->...ij", jnp.concatenate([kbs * rows, qs * rows], axis=-2), keys)
    same = jnp.eye(n, dtype=bool)[:, None, :, None]

    def whole(left, diag):  # [..., n, s, C] and the diagonal blocks [..., n, s, s]
        diag = jnp.where(same, diag[..., :, :, None, :], 0.0).reshape(left.shape)
        return (left + diag).reshape(*left.shape[:-3], C, C)

    return whole(left[..., :s, :], A), whole(left[..., s:, :], qk)


@jax.named_scope("smg.kda.prefill")
def kda_chunked(q, k, v, g, beta, S0, chunk: int = CHUNK):
    """``gated_delta_chunked`` with the decay a number a key channel: ``g``
    [G, T, H, dk].  With the log-decay cumulated inside a chunk, ``G_i`` [dk],
    the keys' products are ``sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])`` for
    ``i >= j``: every exponent is of a difference ``<= 0``, so a channel that
    falls by more than float32 holds inside a chunk gives 0 and nothing
    overflows (``_kda_products``).  The products are made for
    ``_KDA_CHUNKS_AT_ONCE`` chunks at a time; what goes on to the solve and
    the scan is ``[C, C]`` a head and chunk, as for the other rule."""
    T = q.shape[1]
    (q, k, v, g, beta), C = _padded((q, k, v, g, beta), T, chunk)
    q, k, v, g, beta = (_chunked(x, C) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-2)  # [N, G, H, C, dk]
    i = jnp.arange(C)
    kb = k * beta[..., None]
    rows = lambda x: x.reshape(-1, *x.shape[2:])  # [N * G, H, C, dk]
    A, qk = lax.map(lambda xs: _kda_products(*xs), tuple(rows(x) for x in (q, k, kb, gc)),
                    batch_size=_KDA_CHUNKS_AT_ONCE)
    A = jnp.where(i[:, None] > i[None, :], A, 0.0).reshape(*q.shape[:-1], C)
    return _solve_and_scan(
        A, qk.reshape(A.shape), kb * jnp.exp(gc), v, beta, q * jnp.exp(gc),
        k * jnp.exp(gc[..., -1:, :] - gc), jnp.exp(gc[..., -1, :])[..., None], S0, T)


# --------------------------------------------------------------------------
# the pools.  A slot's block is whole tiles of its pool (the state ``[dk, H *
# dv]``, the tail ``tail_block``'s ``[R, W]``) and is read and written with a
# dynamic slice, one for each sequence: written as ``pool[layer, slots]`` and
# ``pool.at[layer, slots].set`` inside the layer scan, XLA:TPU keeps a second
# copy of the whole pool beside the carried one (1.9 GB at 72 slots), where
# slices of a carried buffer update in place.


def read_state(pool: jnp.ndarray, layer, slots: jnp.ndarray) -> jnp.ndarray:
    """Rows ``slots`` [G] of the recurrent-state pool ``[layers, slots, dk,
    H * dv]`` in ``layer``: ``[G, dk, H * dv]``."""
    row = lambda s: lax.dynamic_slice(pool, (layer, s, 0, 0), (1, 1, *pool.shape[2:]))[0, 0]
    return jnp.stack([row(slots[g]) for g in range(slots.shape[0])])


def write_state(pool: jnp.ndarray, layer, slots: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    for g in range(slots.shape[0]):
        pool = lax.dynamic_update_slice(pool, rows[g][None, None].astype(pool.dtype),
                                        (layer, slots[g], 0, 0))
    return pool


def tail_padded_rows(R: int, dtype) -> int:
    """Rows a tail block of ``R`` rows takes in HBM: whole tiles of the dtype."""
    tile = 32 // jnp.dtype(dtype).itemsize
    return -(-R // tile) * tile


def tail_block(C: int, K: int, dtype) -> tuple[int, int]:
    """``(R, W)``: how a slot's convolution tail, the last ``K - 1`` inputs of
    ``C`` channels, lies in its pool ``[layers, slots, R, W]``, ``R * W = (K -
    1) * C`` in ``read_tail``'s order.  The slot must not be the pool's
    second-minor axis: a TPU tiles the last two axes (8 sublanes of 32 bits by
    128 lanes, 16 rows of bfloat16), so with the tail as one flat row a slot
    was one sublane of every tile it shared with its neighbours, and writing
    72 KB moved their rows too, thirty times the bytes (``PERF.md``, PR 54).
    ``W`` is a multiple of 128 that divides ``C``, so a tap is ``C / W`` whole
    rows: the widest whose ``R`` the dtype's tile divides (not a byte more
    than the flat row held), else the one whose ``R`` lacks the fewest rows
    of whole tiles, the widest of those (a tap's rows are what ``read_tail``
    stands side by side again).  Where no 128 divides ``C`` the block is
    ``[K - 1, C]`` (toy widths)."""
    widths = [w for w in range(128, C + 1, 128) if C % w == 0]
    if not widths:
        return K - 1, C
    rows = lambda w: (K - 1) * C // w
    W = min(widths, key=lambda w: (tail_padded_rows(rows(w), dtype) - rows(w), -w))
    return rows(W), W


def read_tail(pool: jnp.ndarray, layer, slots: jnp.ndarray, taps: int) -> jnp.ndarray:
    """Blocks ``slots`` [G] of the convolution pool ``[layers, slots, R, W]``
    (``tail_block``) in ``layer``, as ``[G, K - 1, C]`` (``taps`` is K - 1).
    A pool ``[layers, slots, (K - 1) * C]`` is read the same way: the flat
    row a slot, which ``models/nemotron_h.py`` still keeps (its
    ``state_shapes`` says why)."""
    start = (0,) * (pool.ndim - 2)
    row = lambda s: lax.dynamic_slice(pool, (layer, s, *start), (1, 1, *pool.shape[2:]))[0, 0]
    rows = jnp.stack([row(slots[g]) for g in range(slots.shape[0])])
    return rows.reshape(slots.shape[0], taps, -1)


def write_tail(pool: jnp.ndarray, layer, slots: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    blocks = rows.reshape(rows.shape[0], 1, 1, *pool.shape[2:]).astype(pool.dtype)
    start = (0,) * (pool.ndim - 2)
    for g in range(slots.shape[0]):
        pool = lax.dynamic_update_slice(pool, blocks[g], (layer, slots[g], *start))
    return pool


# --------------------------------------------------------------------------
# decode: one token


def pool_to_heads(S: jnp.ndarray, heads: int) -> jnp.ndarray:
    """The pool's rows ``[..., dk, H * dv]`` as ``[..., H, dk, dv]``."""
    *lead, dk, HV = S.shape
    return jnp.moveaxis(S.reshape(*lead, dk, heads, HV // heads), -2, -3)


def heads_to_pool(S: jnp.ndarray) -> jnp.ndarray:
    """``[..., H, dk, dv]`` as the pool's rows ``[..., dk, H * dv]``."""
    *lead, H, dk, dv = S.shape
    return jnp.moveaxis(S, -3, -2).reshape(*lead, dk, H * dv)


@jax.named_scope("smg.linattn.decode")
def gated_delta_step(pool, layer, slots, q, k, v, alpha, beta):
    """One token for every lane, reading and writing the pool's slots.

    ``pool`` [layers, slots, dk, H * dv] float32; ``slots`` [B]; ``q``, ``k``
    [B, H, dk]; ``v`` [B, H, dv]; ``alpha``, ``beta`` [B, H] (a lane that does
    not run has ``alpha`` 1 and ``beta`` 0, which leaves its state bit for
    bit).  Returns ``(o [B, H, dv] float32, pool)``."""
    H = q.shape[1]
    S = pool_to_heads(read_state(pool, layer, slots), H)  # [B, H, dk, dv]
    a = alpha[..., None, None]
    Sk = _mm("bhkv,bhk->bhv", S, k)
    u = beta[..., None] * (v - alpha[..., None] * Sk)
    S = a * S + k[..., :, None] * u[..., None, :]
    o = _mm("bhkv,bhk->bhv", S, q)
    return o, write_state(pool, layer, slots, heads_to_pool(S))


@jax.named_scope("smg.kda.decode")
def kda_step(pool, layer, slots, q, k, v, alpha, beta):
    """``gated_delta_step`` with the decay a number a key channel: ``alpha``
    [B, H, dk], one number a row of a head's state where that has one a head
    (a lane that does not run has ``alpha`` 1 everywhere and ``beta`` 0).  The
    state is decayed first and the delta taken against the decayed state."""
    H = q.shape[1]
    S = alpha[..., None] * pool_to_heads(read_state(pool, layer, slots), H)  # [B, H, dk, dv]
    u = beta[..., None] * (v - _mm("bhkv,bhk->bhv", S, k))
    S = S + k[..., :, None] * u[..., None, :]
    o = _mm("bhkv,bhk->bhv", S, q)
    return o, write_state(pool, layer, slots, heads_to_pool(S))
