"""One array for a grouped prefill's host inputs.

A grouped prefill takes the group's tokens, page tables, lengths and
sampling vectors from the host: eight small arrays and the sampling key's
counter.  A host-to-device upload costs the same quarter of a millisecond on
a v5e whatever it carries up to 16 KB (``scripts/time_prefill_uploads.py``),
so they travel as one ``int32`` vector that the compiled program takes apart
again at static offsets.  ``pack`` writes it on the host, ``unpack`` reads it
under ``jit``; both follow ``_fields``, so the layout is written down once.
The float vectors go as their bit patterns and come back through
``lax.bitcast_convert_type``: no value changes.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_FLOATS = ("temps", "topps", "minps")


class PrefillInputs(NamedTuple):
    tokens: jax.Array  # [G, T] int32
    page_tables: jax.Array  # [G, mp] int32
    prefix_lens: jax.Array  # [G] int32
    t_reals: jax.Array  # [G] int32
    topks: jax.Array  # [G] int32
    temps: jax.Array  # [G] float32
    topps: jax.Array  # [G] float32
    minps: jax.Array  # [G] float32
    counter: jax.Array  # uint32 scalar: what the sampling key is folded with
    slots: "jax.Array | None" = None  # [G] int32 state slots, where the model has them


def _fields(G: int, T: int, mp: int, slots: bool) -> "tuple[list[tuple[str, slice, tuple]], int]":
    """(name, where, shape) of every field in ``PrefillInputs``' order, and
    the vector's length."""
    shapes = [("tokens", (G, T)), ("page_tables", (G, mp)), ("prefix_lens", (G,)),
              ("t_reals", (G,)), ("topks", (G,)), ("temps", (G,)), ("topps", (G,)),
              ("minps", (G,)), ("counter", ())]
    if slots:
        shapes.append(("slots", (G,)))
    out, off = [], 0
    for name, shape in shapes:
        end = off + math.prod(shape)
        out.append((name, slice(off, end), shape))
        off = end
    return out, off


def pack(chunks, temps, topks, topps, minps, counter: int, G: int, T: int,
         state_slots=None) -> np.ndarray:
    """The launch's packed inputs: ``chunks`` (token ids, prefix length, page
    table row) in the first ``len(chunks)`` of ``G`` rows of ``T`` tokens.  A
    padded row has no tokens, page 0, and samples greedily (temperature 0,
    top-k -1, top-p 1, min-p 0) from the garbage slot 0.  ``state_slots``
    [len(chunks)], where given, adds the rows' state slots."""
    g, mp = len(chunks), len(chunks[0][2])
    fields, size = _fields(G, T, mp, state_slots is not None)
    buf = np.zeros(size, np.int32)
    v = {name: buf[where].reshape(shape) for name, where, shape in fields}
    for i, (ids, pfx, row) in enumerate(chunks):
        v["tokens"][i, : len(ids)] = ids
        v["page_tables"][i] = row
        v["prefix_lens"][i] = pfx
        v["t_reals"][i] = len(ids)
    v["topks"][g:] = -1
    v["topks"][:g] = topks[:g]
    v["topps"].view(np.float32)[g:] = 1.0
    for name, vals in zip(_FLOATS, (temps, topps, minps)):
        v[name].view(np.float32)[:g] = vals[:g]
    v["counter"].view(np.uint32)[()] = counter
    if state_slots is not None:
        v["slots"][:g] = state_slots[:g]
    return buf


def unpack(packed: jax.Array, G: int, T: int, mp: int, slots: bool) -> PrefillInputs:
    """``pack``'s fields again, inside a compiled program."""
    got = {}
    for name, where, shape in _fields(G, T, mp, slots)[0]:
        x = packed[where].reshape(shape)
        if name in _FLOATS:
            x = lax.bitcast_convert_type(x, jnp.float32)
        elif name == "counter":
            x = lax.bitcast_convert_type(x, jnp.uint32)
        got[name] = x
    return PrefillInputs(**got)
