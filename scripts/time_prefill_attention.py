#!/usr/bin/env python3
"""Time the two attentions of a cold grouped prefill that
``ModelRunner._grouped_prefill_impl_for`` chooses between, standalone, on the
chip this process holds (ROADMAP S7):

    python3 scripts/time_prefill_attention.py [--out chiprun_out/prefill_attn.json]
        [--only qwen3-1.7b:1x2048,olmo-hybrid-7b:2x1024] [--blocks 512x512,1024x512]
        [--concat-keys | --flat-keys] [--rehearsal]

``ops.attention.attention_prefill_batched`` (XLA: the float32 scores
``[G, T, H, T]`` whole, through query blocks once they pass
``SCORE_BLOCK_BYTES``) against
``ops.pallas.flash_prefill.flash_attention_prefill`` (an online softmax over
key blocks that stops at the diagonal and at the row's length), each inside a
scan over ``LAYERS`` layers whose queries, keys and values arrive flat,
``[G, T, heads * 128]``, as the projections leave them, at the head counts of
the cells whose prefill runs this code:

- ``qwen3-1.7b`` (the ``eval`` cell): 16/8 heads of 128, groups of 1 to 8 rows
  in the token buckets 256 to 4,096;
- ``olmo-hybrid-7b`` (the ``gen`` cell's full layers): 30/30 heads of 128;
- ``llama-3.2-3b``: 24/8 heads, two shapes whose scores are 96 MiB, between
  the sizes the two cells' programs can have (64 and 120 MiB);
- ``openpangu-ultra-moe-718b`` and ``longcat-flash-chat`` (both ``reason``
  cells): 128 and 64 heads of latent attention, keys of 128 lanes a head and
  64 rotary lanes that the heads of a row share, values of 128.  XLA's form
  there is ``ops.latent_attention.latent_attention_prefill`` and the kernel
  takes the shared key as an operand of its own (``k_pe``) and each head's
  keys and values with the heads first, ``[G, H, T, 128]``, as the models'
  up-projections leave them; ``--flat-keys`` gives it those flat like the
  others', and ``--concat-keys`` times the other way to feed it the rotary
  key: written into every head's key, ``[G, T, H, 256]`` (flat), and no
  shared operand.

Each shape is timed with every row full, with every row at half the bucket
and two tokens (the prompt that the template's two tokens push into the next
bucket), and ragged (rows at 1, 3/4 and 1/2 + 2 of the bucket and the last a
padded row of 0, where the group has them).  Prints one JSON line a shape and
fill: milliseconds a layer for each (XLA once a shape: it scores the whole
bucket whatever the rows hold), the kernel's causal FLOP (4 x sum of
t_real^2 / 2 x H x D, and 2 x 64 more a pair for the shared key) over its time, and the largest difference between the
two outputs of one layer over the real tokens.  The dispatch rule's constant
(``runner.FLASH_PREFILL_MIN_SCORE_BYTES``) is read off the full column: the
size of the float32 scores, ``G x T x H x T x 4`` bytes, from which the kernel
wins.  Refuses to run without a TPU: a CPU time is not a device time
(``--rehearsal`` walks the same code at toy size with the kernel interpreted;
its times mean nothing).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from smg_tpu.ops.attention import attention_prefill_batched  # noqa: E402
from smg_tpu.ops.latent_attention import latent_attention_prefill  # noqa: E402
from smg_tpu.ops.pallas.flash_prefill import flash_attention_prefill  # noqa: E402

D = 128
DR = 64  # the rotary lanes of a latent model's shared key
LAYERS = 28
REPS = 5
LATENT = [(1, (256, 512, 1024, 1536, 2048)), (2, (256, 512, 1024, 1536, 2048))]
# name: heads, kv heads (0: latent attention, a rotary key the heads share),
# [(rows, token buckets)]
MODELS = {
    "qwen3-1.7b": (16, 8, [(1, (256, 512, 1024, 2048, 4096)), (2, (256, 512, 1024, 2048)),
                           (4, (256, 512, 1024)), (8, (256, 512))]),
    "olmo-hybrid-7b": (30, 30, [(1, (512, 1024, 2048, 4096)), (2, (512, 1024, 2048)),
                                (4, (512, 1024))]),
    # 24/8 heads put float32 scores of 96 MiB between the two cells' 64 and 120
    "llama-3.2-3b": (24, 8, [(1, (1024,)), (4, (512,))]),
    "openpangu-ultra-moe-718b": (128, 0, LATENT),
    "longcat-flash-chat": (64, 0, LATENT),
}
REHEARSAL = {"toy": (4, 2, [(1, (256,)), (4, (128,))]), "toy-mha": (3, 3, [(2, (128,))]),
             "toy-latent": (2, 0, [(2, (128,))])}


def fills(G: int, T: int) -> dict:
    """Real tokens a row under each name."""
    ragged = [T, 3 * T // 4, T // 2 + 2, 0]
    out = {"full": [T] * G, "half+2": [T // 2 + 2] * G}
    if G > 1:
        out["ragged"] = [ragged[min(g, 3)] if g < G - 1 else 0 for g in range(G)]
    return out


def layers(attend, H: int, K: int, n: int, q, k, v, t_reals, *pe):
    """``n`` layers' attention of one grouped prefill, each layer's queries
    moved by the layer before so that none can be dropped.  ``pe``: a latent
    model's rotary queries ``[G, T, H * DR]`` and shared key ``[G, T, DR]``.
    ``k`` and ``v`` flat, ``[G, T, K * D]``, or with the heads first,
    ``[G, K, T, D]``, which ``attend`` then takes as they are."""
    G, T, _ = q.shape
    pos = jnp.broadcast_to(jnp.arange(T), (G, T))
    if pe:
        pe = (pe[0].reshape(G, T, H, DR), pe[1])
    scale = (D + DR) ** -0.5 if pe else D ** -0.5
    by_head = lambda x: x if x.ndim == 4 else x.reshape(G, T, K, D)

    def layer_body(h, _):
        out = attend((q + h).reshape(G, T, H, D), by_head(k), by_head(v), pos, t_reals, scale,
                     *pe)
        return h + out.reshape(G, T, H * D), None

    return jax.lax.scan(layer_body, jnp.zeros_like(q), None, length=n)[0]


def xla_latent(q, k, v, pos, t_reals, scale, q_pe, k_pe):
    return latent_attention_prefill(q, q_pe, k, k_pe, v, pos, t_reals, scale)


def concat_keys(q, k, q_pe, k_pe):
    """The rotary key written into every head's key: queries and keys of 256
    lanes a head (128 + 64 and 64 of zeros), the shared operand gone."""
    zeros = jnp.zeros((*q.shape[:-1], D - DR), q.dtype)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (*k.shape[:-1], DR))
    return (jnp.concatenate([q, q_pe, zeros], axis=-1),
            jnp.concatenate([k, k_pe, zeros], axis=-1))


def timed(fn, *a) -> float:
    """Seconds a call, host clock round ``block_until_ready``, after one
    warm-up (which compiles)."""
    fn(*a).block_until_ready()
    t = time.perf_counter()
    for _ in range(REPS):
        out = fn(*a)
    out.block_until_ready()
    return (time.perf_counter() - t) / REPS


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out")
    ap.add_argument("--only", default="", help="comma-separated model:GxT shapes (default: all)")
    ap.add_argument("--blocks", default="",
                    help="comma-separated QxK blocks of the kernel (default: its own)")
    ap.add_argument("--concat-keys", action="store_true",
                    help="latent shapes: the rotary key inside every head's key")
    ap.add_argument("--flat-keys", action="store_true",
                    help="latent shapes: keys and values [G, T, H * 128], not heads first")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    only = {s for s in args.only.split(",") if s}
    blocks = [tuple(int(n) for n in b.split("x")) for b in args.blocks.split(",") if b] or [
        (None, None)]
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"time_prefill_attention: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    rows = []
    for model, (H, K, shapes) in (REHEARSAL if args.rehearsal else MODELS).items():
        for G, buckets in shapes:
            for T in buckets:
                if only and f"{model}:{G}x{T}" not in only and model not in only:
                    continue
                latent, KV = not K, K or H
                kq, kk, kv, kqp, kkp = jax.random.split(jax.random.PRNGKey(0), 5)
                q = jax.random.normal(kq, (G, T, H * D), dtype)
                k = jax.random.normal(kk, (G, T, KV * D), dtype)
                v = jax.random.normal(kv, (G, T, KV * D), dtype)
                pe = (jax.random.normal(kqp, (G, T, H * DR), dtype),
                      jax.random.normal(kkp, (G, T, DR), dtype)) if latent else ()
                xla, xla_one = (jax.jit(functools.partial(
                    layers, xla_latent if latent else attention_prefill_batched, H, KV, n))
                    for n in (LAYERS, 1))
                xla_ms = None
                for bq, bk in blocks:
                    if bq and (bq > T or bk > T):
                        continue

                    heads_first = latent and not (args.concat_keys or args.flat_keys)

                    def kernel(q, k, v, _pos, t_reals, scale, q_pe=None, k_pe=None,
                               bq=bq, bk=bk, heads_first=heads_first):
                        if q_pe is not None and args.concat_keys:
                            (q, k), q_pe, k_pe = concat_keys(q, k, q_pe, k_pe), None, None
                        return flash_attention_prefill(q, k, v, t_reals, scale, block_q=bq,
                                                       block_k=bk, interpret=args.rehearsal,
                                                       q_pe=q_pe, k_pe=k_pe,
                                                       kv_heads_first=heads_first)

                    flash, flash_one = (jax.jit(functools.partial(layers, kernel, H, KV, n))
                                        for n in (LAYERS, 1))
                    for fill, reals in fills(G, T).items():
                        t_reals = jnp.asarray(reals, jnp.int32)
                        a = (q, k, v, t_reals, *pe)
                        # the same keys and values as the kernel takes them
                        b = (q, *(x.reshape(G, T, KV, D).swapaxes(1, 2) for x in (k, v)),
                             t_reals, *pe) if heads_first else a
                        if xla_ms is None:
                            xla_ms = timed(xla, *a) / LAYERS * 1e3
                        ms = timed(flash, *b) / LAYERS * 1e3
                        real = jnp.arange(T)[None, :, None] < t_reals[:, None, None]
                        diff = float(jnp.max(jnp.where(real, jnp.abs(
                            flash_one(*b).astype(jnp.float32)
                            - xla_one(*a).astype(jnp.float32)), 0)))
                        flop = (4 * D + 2 * DR * latent) * sum(t * t / 2 for t in reals) * H
                        row = {"model": model, "G": G, "T": T, "fill": fill, "t_reals": reals,
                               "block_q": bq, "block_k": bk,
                               "keys": ("concat" if args.concat_keys else "shared-flat"
                                        if args.flat_keys else "shared") if latent else "own",
                               "xla_ms_per_layer": xla_ms, "pallas_ms_per_layer": ms,
                               "pallas_causal_tflop_per_s": flop / ms / 1e9,
                               "max_abs_diff": diff, "device_kind": dev.device_kind,
                               "rehearsal": args.rehearsal}
                        print(json.dumps(row), flush=True)
                        rows.append(row)
                        if args.out:
                            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                            with open(args.out, "w") as f:
                                json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
