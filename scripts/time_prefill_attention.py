#!/usr/bin/env python3
"""Time the two attentions of a cold grouped prefill that
``ModelRunner._grouped_prefill_impl_for`` chooses between, standalone, on the
chip this process holds (ROADMAP S7):

    python3 scripts/time_prefill_attention.py [--out chiprun_out/prefill_attn.json]
        [--only qwen3-1.7b:1x2048,olmo-hybrid-7b:2x1024] [--blocks 512x512,1024x512]
        [--rehearsal]

``ops.attention.attention_prefill_batched`` (XLA: the float32 scores
``[G, T, H, T]`` whole, through query blocks once they pass
``SCORE_BLOCK_BYTES``) against
``ops.pallas.flash_prefill.flash_attention_prefill`` (an online softmax over
key blocks that stops at the diagonal and at the row's length), each inside a
scan over ``LAYERS`` layers whose queries, keys and values arrive flat,
``[G, T, heads * 128]``, as the projections leave them, at the head counts of
the two cells whose prefill runs this code:

- ``qwen3-1.7b`` (the ``eval`` cell): 16/8 heads of 128, groups of 1 to 8 rows
  in the token buckets 256 to 4,096;
- ``olmo-hybrid-7b`` (the ``gen`` cell's full layers): 30/30 heads of 128;
- ``llama-3.2-3b``: 24/8 heads, two shapes whose scores are 96 MiB, between
  the sizes the two cells' programs can have (64 and 120 MiB).

Each shape is timed with every row full, with every row at half the bucket
and two tokens (the prompt that the template's two tokens push into the next
bucket), and ragged (rows at 1, 3/4 and 1/2 + 2 of the bucket and the last a
padded row of 0, where the group has them).  Prints one JSON line a shape and
fill: milliseconds a layer for each (XLA once a shape: it scores the whole
bucket whatever the rows hold), the kernel's causal FLOP (4 x sum of
t_real^2 / 2 x H x D) over its time, and the largest difference between the
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
from smg_tpu.ops.pallas.flash_prefill import flash_attention_prefill  # noqa: E402

D = 128
LAYERS = 28
REPS = 5
# name: heads, kv heads, [(rows, token buckets)]
MODELS = {
    "qwen3-1.7b": (16, 8, [(1, (256, 512, 1024, 2048, 4096)), (2, (256, 512, 1024, 2048)),
                           (4, (256, 512, 1024)), (8, (256, 512))]),
    "olmo-hybrid-7b": (30, 30, [(1, (512, 1024, 2048, 4096)), (2, (512, 1024, 2048)),
                                (4, (512, 1024))]),
    # 24/8 heads put float32 scores of 96 MiB between the two cells' 64 and 120
    "llama-3.2-3b": (24, 8, [(1, (1024,)), (4, (512,))]),
}
REHEARSAL = {"toy": (4, 2, [(1, (256,)), (4, (128,))]), "toy-mha": (3, 3, [(2, (128,))])}


def fills(G: int, T: int) -> dict:
    """Real tokens a row under each name."""
    ragged = [T, 3 * T // 4, T // 2 + 2, 0]
    out = {"full": [T] * G, "half+2": [T // 2 + 2] * G}
    if G > 1:
        out["ragged"] = [ragged[min(g, 3)] if g < G - 1 else 0 for g in range(G)]
    return out


def layers(attend, H: int, K: int, n: int, q, k, v, t_reals):
    """``n`` layers' attention of one grouped prefill, each layer's queries
    moved by the layer before so that none can be dropped."""
    G, T, _ = q.shape
    pos = jnp.broadcast_to(jnp.arange(T), (G, T))

    def layer_body(h, _):
        out = attend((q + h).reshape(G, T, H, D), k.reshape(G, T, K, D),
                     v.reshape(G, T, K, D), pos, t_reals, D ** -0.5)
        return h + out.reshape(G, T, H * D), None

    return jax.lax.scan(layer_body, jnp.zeros_like(q), None, length=n)[0]


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
                kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
                q = jax.random.normal(kq, (G, T, H * D), dtype)
                k = jax.random.normal(kk, (G, T, K * D), dtype)
                v = jax.random.normal(kv, (G, T, K * D), dtype)
                xla, xla_one = (jax.jit(functools.partial(
                    layers, attention_prefill_batched, H, K, n)) for n in (LAYERS, 1))
                xla_ms = None
                for bq, bk in blocks:
                    if bq and (bq > T or bk > T):
                        continue

                    def kernel(q, k, v, _pos, t_reals, scale, bq=bq, bk=bk):
                        return flash_attention_prefill(q, k, v, t_reals, scale, block_q=bq,
                                                       block_k=bk, interpret=args.rehearsal)

                    flash, flash_one = (jax.jit(functools.partial(layers, kernel, H, K, n))
                                        for n in (LAYERS, 1))
                    for fill, reals in fills(G, T).items():
                        t_reals = jnp.asarray(reals, jnp.int32)
                        a = (q, k, v, t_reals)
                        if xla_ms is None:
                            xla_ms = timed(xla, *a) / LAYERS * 1e3
                        ms = timed(flash, *a) / LAYERS * 1e3
                        real = jnp.arange(T)[None, :, None] < t_reals[:, None, None]
                        diff = float(jnp.max(jnp.where(real, jnp.abs(
                            flash_one(*a).astype(jnp.float32)
                            - xla_one(*a).astype(jnp.float32)), 0)))
                        flop = 4 * sum(t * t / 2 for t in reals) * H * D
                        row = {"model": model, "G": G, "T": T, "fill": fill, "t_reals": reals,
                               "block_q": bq, "block_k": bk,
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
