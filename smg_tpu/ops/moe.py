"""The routed-expert layer: route, dispatch, grouped products, combine.

One place for every model that routes tokens to experts
(``models/pangu_moe.py``; ``models/llama.py``'s Qwen-MoE branch).  A process
**holds** a contiguous range of the routed experts (all of them, or one chip's
share of a deployment).  The layer scores every token over all the outputs the
router has and keeps the ``top_k`` largest.  A pick is of one of three kinds:

- on a **held** expert: the pairs are sorted by expert and go through the
  grouped products (``expert_layer``);
- on a real expert **held elsewhere**: it adds nothing here (its chip would
  add it, over an exchange this layer does not have: no code stands in);
- on an **identity** expert (``models/longcat_flash.py``: the router's last
  outputs are experts that compute nothing, ``E_i(x) = x``): ``w_i x``, added
  on the token's own chip with no exchange, as a shared expert is, because
  every chip computes it alike for its own tokens (``identity_picks``).  No
  chip holds such an expert and it is in no dispatch: to ``dispatch`` it is a
  pick that is not on a held expert.

Shapes follow the rows routed here: the pairs on held experts, sorted by
expert, are the rows of three grouped matrix products (one group an expert).
There is no ``[tokens, experts, ...]`` array, and no capacity: the rows go
through a buffer of ``rows_buffer(T * top_k)`` rows, once where all of them
fit it (every decode column, any prefill whose routing is near even) and in
as many passes as it takes where they do not, so that no token is ever
dropped, whatever the routing.

The grouped product is ``ops/pallas/moe_experts.py`` on a TPU (``impl``
"pallas"; "pallas_interpret" in the tests) and ``lax.ragged_dot`` elsewhere
("xla").
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Rows a pass computes at most.  Small inputs take all their pairs at once;
# a 4,096-token prefill (32,768 pairs at top 8; 49,152 at top 12) takes an
# eighth of them, twice what an even routing sends to a sixteenth of the
# experts, so that the gathered rows, the experts' hidden activations and
# their results stay near 150 MB beside a cache that fills the chip.
ROWS_ALL_AT_ONCE = 2048


def rows_buffer(pairs: int) -> int:
    if pairs <= ROWS_ALL_AT_ONCE:
        # whole sublane tiles of rows for the kernel's blocks: one lane at
        # top 22 is 22 pairs, which no tile of 8 divides
        return pairs if pairs % 8 == 0 else -(-pairs // 16) * 16
    return max(ROWS_ALL_AT_ONCE, -(-pairs // 8 // 256) * 256)


class Routing(NamedTuple):
    experts: jax.Array  # [T, k] int32: the experts each token picked
    weights: jax.Array  # [T, k] float32: what each pick's result is scaled by


class Dispatch(NamedTuple):
    token: jax.Array  # [T*k] int32: the token of each pair, pairs sorted by held expert
    place: jax.Array  # [T, k] int32: where each pair sits in that order
    group_sizes: jax.Array  # [held] int32: rows of each held expert
    rows: jax.Array  # scalar int32: pairs on held experts (they sort first)


@jax.named_scope("smg.moe.route")
def route(x, router, *, top_k: int, scoring: str, norm_topk: bool, scale: float,
          select_bias=None) -> Routing:
    """Scores of ``x`` [T, E] over all experts of ``router`` [E, X], float32;
    the ``top_k`` largest, renormalised to sum 1 where ``norm_topk``, times
    ``scale``.  ``scoring`` "softmax" is a softmax over all experts, "sigmoid"
    an independent sigmoid of each.  With ``select_bias`` [X] the experts are
    picked by score plus bias and weighed by the score alone."""
    logits = jnp.einsum("te,ex->tx", x, router, preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if select_bias is None:
        top, experts = jax.lax.top_k(scores, top_k)
    else:
        _, experts = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return Routing(experts.astype(jnp.int32), top * scale)


@jax.named_scope("smg.moe.dispatch")
def dispatch(experts, held: tuple[int, int]) -> Dispatch:
    """Sort the token-expert pairs by held expert (stable, so a token's rows
    keep their order); pairs on experts held elsewhere sort behind them all."""
    T, k = experts.shape
    first, count = held
    local = jnp.where((experts >= first) & (experts < first + count), experts - first, count)
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    place = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=jnp.int32))
    bounds = jnp.searchsorted(flat[order], jnp.arange(count + 1, dtype=jnp.int32))
    sizes = jnp.diff(bounds).astype(jnp.int32)
    return Dispatch(order // k, place.reshape(T, k), sizes, bounds[count].astype(jnp.int32))


@jax.named_scope("smg.moe.zero")
def identity_picks(x, routing: Routing, first: int):
    """What the identity experts give for ``x`` [T, E]: ``(sum of w_i over a
    token's picks i >= first) x``, float32, and how many such picks there
    were.  The router's outputs from ``first`` on are identity experts; a
    padded token's picks are -1 and on none."""
    on = routing.experts >= first
    weight = jnp.sum(jnp.where(on, routing.weights, 0.0), axis=-1, keepdims=True)
    return weight * x.astype(jnp.float32), jnp.sum(on).astype(jnp.int32)


def grouped_matmul(rows, weights, group_sizes, impl: str, layer=None):
    """``rows`` [R, K] times ``weights`` [G, K, N], row ``r`` against the
    weights of its group; rows past the groups' total give zeros.  With
    ``layer``, ``weights`` is a stack [L, G, K, N] and the layer is picked
    where it costs no copy (inside the kernel; as an operand of XLA's dot)."""
    if impl.startswith("pallas"):
        from smg_tpu.ops.pallas.moe_experts import grouped_matmul as kernel

        return kernel(rows, weights, group_sizes, 0 if layer is None else layer,
                      interpret=(impl == "pallas_interpret"))
    if layer is not None:
        weights = jax.lax.dynamic_index_in_dim(weights, layer, 0, keepdims=False)
    out = jax.lax.ragged_dot(rows, weights, group_sizes)
    # XLA:TPU leaves what it finds in the rows past the groups' total (NaN
    # among it, which the combine's weight of 0 does not silence)
    past = jnp.arange(rows.shape[0])[:, None] >= jnp.sum(group_sizes)
    return jnp.where(past, jnp.zeros((), out.dtype), out)


def relu2(x):
    """``relu(x)^2``, squared in float32, in ``x``'s dtype."""
    return jnp.square(jax.nn.relu(x.astype(jnp.float32))).astype(x.dtype)


def _experts(rows, w_gate, w_up, w_down, group_sizes, impl, layer):
    up = grouped_matmul(rows, w_up, group_sizes, impl, layer)
    if w_gate is None:  # no gate matrix: a squared ReLU
        hidden = relu2(up)
    else:
        hidden = jax.nn.silu(grouped_matmul(rows, w_gate, group_sizes, impl, layer)) * up
    return grouped_matmul(hidden, w_down, group_sizes, impl, layer)


def expert_layer(x, routing: Routing, w_gate, w_up, w_down, held: tuple[int, int],
                 impl: str = "xla", layer=None):
    """What the held experts give for ``x`` [T, E]: ``sum_i w_i E_i(x)`` over
    each token's picks on held experts.  ``w_gate``, ``w_up`` [held, E, F] and
    ``w_down`` [held, F, E] are the held experts' gated MLPs (with ``layer``:
    the stacks of all layers, [L, held, ..]); with ``w_gate`` None an expert is
    ``w_down relu(w_up x)^2`` (``models/nemotron_h.py``).  ``E`` is whatever
    width the experts work in: ``routing`` may come from a wider input than
    ``x`` (there the router reads the model's width and the experts a latent).
    Returns the result [T, E] (float32) and ``(picks on held experts, held
    experts that got a row)``."""
    T, k = routing.experts.shape
    E = x.shape[-1]
    d = dispatch(routing.experts, held)
    R = rows_buffer(T * k)
    starts = jnp.cumsum(d.group_sizes) - d.group_sizes
    # the tokens of the sorted pairs, with a whole buffer of padding behind
    # them so that a pass may read past the last pair
    token = jnp.concatenate([d.token, jnp.zeros((R,), jnp.int32)])

    def one_pass(lo, y):
        """Rows ``lo .. lo + R`` of the sorted pairs."""
        sizes = jnp.clip(starts + d.group_sizes - lo, 0, R) - jnp.clip(starts - lo, 0, R)
        rows = x[jax.lax.dynamic_slice(token, (lo,), (R,))]
        with jax.named_scope("smg.moe.experts"):
            out = _experts(rows, w_gate, w_up, w_down, sizes, impl, layer)
        with jax.named_scope("smg.moe.combine"):
            # a token's result is the weighted sum of its own rows: gathered,
            # pick by pick, from where dispatch put them (no scatter)
            at = d.place - lo
            mine = (at >= 0) & (at < R) & (d.place < d.rows)
            w = jnp.where(mine, routing.weights, 0.0)
            at = jnp.clip(at, 0, R - 1)
            for j in range(k):
                y = y + w[:, j, None] * out[at[:, j]].astype(jnp.float32)
        return y

    y = jnp.zeros((T, E), jnp.float32)
    if R >= T * k:
        y = one_pass(jnp.int32(0), y)
    else:
        _, y = jax.lax.while_loop(
            lambda c: c[0] < d.rows,
            lambda c: (c[0] + R, one_pass(c[0], c[1])),
            (jnp.int32(0), y))
    return y, (d.rows, jnp.sum(d.group_sizes > 0).astype(jnp.int32))
