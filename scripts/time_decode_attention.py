#!/usr/bin/env python3
"""Time the two decode attentions ``ModelRunner._attn_impl_for`` chooses
between, standalone, on the chip this process holds (ROADMAP S2):

    python3 scripts/time_decode_attention.py [--out chiprun_out/attn.json]
        [--only qwen3-1.7b:16x128,olmo-hybrid-7b:16x256] [--pages-per-block N]
        [--rehearsal]

``ops.attention.attention_decode_cached`` (XLA: gathers every lane's whole
table) against ``ops.pallas.decode_attention.paged_attention_decode_cached``
(streams the pages each lane holds, in blocks), each inside a scan over the
layers of one cache at a cell's widths:

- ``qwen3-1.7b`` (the ``eval`` cell): 28 layers, 4,725 pages, 16/8 heads of
  128; batch 8 and 16 behind tables of 64, 128 and 256 pages, batch 32 and
  64 behind 128 and 256, and three small programs (1 x 8, 1 x 64, 4 x 32);
- ``olmo-hybrid-7b`` (the ``gen`` cell's 4 full layers): 5,087 pages, 30/30
  heads of 128; batch 16 behind 128 and 256 pages;
- ``openpangu-ultra-moe-718b`` (the ``reason`` cell): 5 layers of one latent
  buffer, 640 lanes an entry (kv heads 0 marks it), 128 absorbed query heads;
  batch 16, 32 and 64 behind 64, 128 and 256 pages.  The XLA form is
  ``attention_decode_cached`` with one head as wide as the entry and the cache
  as its own V, the kernel ``latent_attention_decode_cached``;
- ``longcat-flash-chat`` (its ``reason`` cell): 8 cache layers (two sublayers
  a layer) of the same 640-lane entries under 64 absorbed heads; batch 64
  behind 64, 128 and 256 pages;
- ``mimo-v2-flash`` (the ``mixed`` cell's 2 full layers): 64/4 heads, keys of
  192 and values of 128 (K pages of 768 lanes, V of 512); batch 16, 32 and
  64 behind 128, 256 and 512 pages;
- ``nemotron-3-super-120b-a12b`` (its ``reason`` cell's one attention layer):
  32/2 heads of 128, pages of 256 lanes; batch 64 behind 64, 128 and 256 pages;
- ``k-exaone-236b-a23b:verify`` (its ``reason`` cell's 2 full layers, the
  verify column): two rows a lane, 64/8 heads of 128; batch 64 behind 128
  and 256 pages.  ``ops.attention.
  attention_verify_cached`` against ``paged_attention_verify_cached``;
- ``mimo-v2-flash:window`` (its 5 window layers): 64/8 heads over a ring of
  144 entries a lane (1,536 and 1,024 lanes), with the sink; batch 16, 32 and
  64.  No table and no fill: ``ops.window_attention.window_attention_decode``
  against ``ops.pallas.window_decode.window_attention_decode``;

lanes filled to a quarter, a half and all of the table.  Prints one JSON line
per shape: milliseconds a layer for each (XLA once a shape: it reads the
whole table whatever it holds), the kernel's held bytes over its time
(GB/s) and its microseconds a block of its own size (a lane's fixed part
spread over its blocks: the fit below parts the two), the largest
difference between the two outputs on random data, and
per (batch, table) the line through the kernel's three fills: a fixed cost
a layer and a rate.  The dispatch rule is read off the half-full column.
Refuses to run without a TPU: a CPU time is not a device time
(``--rehearsal`` walks the same code at toy size with the kernel interpreted,
to find typos before they cost chip time; its times mean nothing).
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
import numpy as np  # noqa: E402

from smg_tpu.ops.attention import attention_decode_cached, attention_verify_cached  # noqa: E402
from smg_tpu.ops.pallas.decode_attention import (  # noqa: E402
    _pages_per_block,
    latent_attention_decode_cached,
    paged_attention_decode_cached,
    paged_attention_verify_cached,
)

PS, N = 16, 8
REPS = 5
FILLS = (0.25, 0.5, 1.0)
# name: layers, pages, heads, kv heads, head dim, [(batch, table widths)], the
# values' head dim where it is not the keys', and the rows a lane of a verify
# column (then the values' head dim is given too)
MODELS = {
    "mimo-v2-flash": (2, 20000, 64, 4, 192,
                      [(16, (128, 256, 512)), (32, (128, 256, 512)), (64, (128, 256, 512))],
                      128),
    "qwen3-1.7b": (28, 4725, 16, 8, 128,
                   [(1, (8, 64)), (4, (32,)), (8, (64, 128, 256)),
                    (16, (64, 128, 256)), (32, (128, 256)), (64, (128, 256))]),
    "olmo-hybrid-7b": (4, 5087, 30, 30, 128, [(16, (128, 256))]),
    "openpangu-ultra-moe-718b": (5, 20000, 128, 0, 640,
                                 [(16, (64, 128, 256)), (32, (64, 128, 256)),
                                  (64, (64, 128, 256))]),
    "longcat-flash-chat": (8, 20000, 64, 0, 640, [(64, (64, 128, 256))]),
    "nemotron-3-super-120b-a12b": (1, 20000, 32, 2, 128, [(64, (64, 128, 256))]),
    "k-exaone-236b-a23b:verify": (2, 20000, 64, 8, 128, [(64, (128, 256))], 128, 2),
}
# the window layers' form: layers, slots, ring entries, heads, kv heads, head
# dims of keys and values, window, batches
WINDOW_MODELS = {"mimo-v2-flash:window": (5, 73, 144, 64, 8, 192, 128, 128, (16, 32, 64))}
WINDOW_REHEARSAL = {"toy:window": (2, 5, 32, 16, 8, 64, 32, 8, (2, 8))}
REHEARSAL = {"toy": (2, 40, 4, 2, 64, [(2, (4, 8)), (8, (8,))]),
             "toy-narrow-v": (2, 40, 8, 4, 64, [(2, (4, 8))], 32),
             "toy:verify": (2, 40, 4, 2, 64, [(2, (4, 8))], 64, 2),
             "toy-latent": (2, 40, 4, 0, 256, [(2, (4, 8))]),
             "toy-latent-8-layers": (8, 40, 8, 0, 256, [(2, (8,))])}
LATENT_VALUE_LANES = {640: 512, 256: 128}  # entry lanes -> lanes of its value


def model_shape(entry):
    """A ``MODELS`` entry whole: layers, pages, heads, kv heads, head dim, [(batch,
    table widths)], the values' head dim, the rows a lane (1 but for a verify column)."""
    L, P, H, K, D, shapes, *rest = entry
    return L, P, H, K, D, shapes, (rest[0] if rest else D), (rest[1] if len(rest) > 1 else 1)


def latent_forms(D: int, pages_per_block, interpret: bool) -> dict:
    """The two forms over one latent buffer, with ``columns``' signature; the
    result is padded back to the entry's lanes so that layers chain."""
    vl = LATENT_VALUE_LANES[D]
    back = lambda o: jnp.pad(o[..., :vl], ((0, 0), (0, 0), (0, D - vl)))

    def xla(q, kc, _vc, hk, _hv, n, l, tables, entry, scale):
        return back(attention_decode_cached(q, kc, kc, hk, hk, n, l, tables, entry, scale))

    def pallas(q, kc, _vc, hk, _hv, n, l, tables, entry, scale):
        return back(latent_attention_decode_cached(
            q, kc, hk, n, l, tables, entry, latent=vl, scale=scale,
            pages_per_block=pages_per_block, interpret=interpret))

    return {"xla": xla, "pallas": pallas}


def narrow_value_forms(forms: dict, D: int) -> dict:
    """Forms whose values are narrower than their keys, with the result
    padded back to the keys' width so that layers chain."""
    def padded(attend):
        def form(*a):
            out = attend(*a)
            return jnp.pad(out, ((0, 0), (0, 0), (0, D - out.shape[-1])))
        return form
    return {name: padded(attend) for name, attend in forms.items()}


def verify_forms(rows: int, pages_per_block, interpret: bool) -> dict:
    """The verify column's two forms with ``columns``' signature: ``rows``
    query rows a lane (the lane's query shifted a little a row), each lane
    holding ``n - rows`` side rows before them; the rows' mean chains the
    layers."""
    def form(attend, **kw):
        def verify(q, kc, vc, hk, hv, n, l, tables, entry, scale):
            q_rows = jnp.stack([q + w for w in range(rows)], axis=1)
            held = jnp.full((q.shape[0],), n - rows, jnp.int32)
            out = attend(q_rows, kc, vc, hk, hv, held, l, tables, entry, scale, **kw)
            return out.mean(axis=1).astype(q.dtype)
        return verify

    return {"xla": form(attention_verify_cached),
            "pallas": form(paged_attention_verify_cached, pages_per_block=pages_per_block,
                           interpret=interpret)}


def window_rows(models: dict, only: set, interpret: bool, dev) -> list:
    """The window layers' two forms, each inside a scan over the layers of
    one store: milliseconds a layer, the kernel's ring bytes over its time,
    and the largest difference between the two outputs."""
    from smg_tpu.ops.pallas.window_decode import window_attention_decode as kernel
    from smg_tpu.ops.window_attention import window_attention_decode as xla

    rows = []
    for model, (L, S, R, H, K, D, Dv, W, batches) in models.items():
        keys = jax.random.split(jax.random.PRNGKey(1), 6)
        rk = jax.random.normal(keys[0], (L, S, R, K * D), jnp.bfloat16)
        rv = jax.random.normal(keys[1], (L, S, R, K * Dv), jnp.bfloat16)
        sink = 4.0 + jax.random.normal(keys[2], (H,), jnp.float32)
        for B in batches:
            if only and f"{model}:{B}" not in only and model not in only:
                continue
            q = jax.random.normal(keys[3], (B, H, D), jnp.bfloat16)
            sk = jax.random.normal(keys[4], (L, B, N, K * D), jnp.bfloat16)
            sv = jax.random.normal(keys[5], (L, B, N, K * Dv), jnp.bfloat16)
            slots = jnp.asarray(1 + np.arange(B) % (S - 1), jnp.int32)
            entry = jnp.asarray(1000 + 37 * np.arange(B), jnp.int32)  # past the ring's wrap

            def columns_of(form):
                def layer_body(h, xs):
                    l, hk, hv = xs
                    out = form(q + h, rk, rv, hk, hv, jnp.int32(N), l, slots, entry, W, sink,
                               D ** -0.5)
                    return h + jnp.pad(out, ((0, 0), (0, 0), (0, D - Dv))), None
                return jax.jit(lambda: jax.lax.scan(
                    layer_body, jnp.zeros_like(q), (jnp.arange(L), sk, sv))[0])

            fns = {"xla": columns_of(xla),
                   "pallas": columns_of(functools.partial(kernel, interpret=interpret))}
            ms = {name: timed(fn) / L * 1e3 for name, fn in fns.items()}
            diff = float(jnp.max(jnp.abs(fns["pallas"]().astype(jnp.float32)
                                         - fns["xla"]().astype(jnp.float32))))
            ring_bytes = B * R * K * (D + Dv) * 2
            row = {"model": model, "B": B, "ring": R, "xla_ms_per_layer": ms["xla"],
                   "pallas_ms_per_layer": ms["pallas"],
                   "pallas_ring_gb_per_s": ring_bytes / ms["pallas"] / 1e6,
                   "max_abs_diff": diff, "device_kind": dev.device_kind,
                   "rehearsal": interpret}
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


def columns(attend, L, D, q, kc, vc, hk_all, hv_all, tables, entry):
    """One decode column's attention: every layer of the cache in turn."""

    def layer_body(h, xs):
        l, hk, hv = xs
        return h + attend(q + h, kc, vc, hk, hv, jnp.int32(N), l, tables, entry,
                          D ** -0.5), None

    return jax.lax.scan(layer_body, jnp.zeros_like(q),
                        (jnp.arange(L), hk_all, hv_all))[0]


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
    ap.add_argument("--only", default="",
                    help="comma-separated model:BxMP shapes (default: all)")
    ap.add_argument("--pages-per-block", type=int, default=None,
                    help="the kernel's block (default: its own choice)")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    only = {s for s in args.only.split(",") if s}
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"time_decode_attention: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 3
    pallas = functools.partial(paged_attention_decode_cached,
                               pages_per_block=args.pages_per_block,
                               interpret=args.rehearsal)
    rng = np.random.default_rng(0)
    rows = []
    rows += window_rows(WINDOW_REHEARSAL if args.rehearsal else WINDOW_MODELS, only,
                        args.rehearsal, dev)
    for model, entry in (REHEARSAL if args.rehearsal else MODELS).items():
        L, P, H, K, D, shapes, Dv, q_rows = model_shape(entry)
        if only and not any(s.startswith(model + ":") for s in only):
            continue
        latent = K == 0  # one buffer of ``D`` lanes an entry and no V
        kd = D if latent else K * D
        vd = K * Dv if K else kd  # V's lanes
        kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(0), 4)
        # random, so that the two outputs can be compared; what the cache
        # holds does not change what the attentions cost
        kc = jax.random.normal(kk, (L, P, PS, kd), jnp.bfloat16)
        vc = kc if latent else jax.random.normal(kv, (L, P, PS, vd), jnp.bfloat16)
        page_bytes = PS * (kd if latent else kd + vd) * 2  # K and V, or the one buffer
        forms = (latent_forms(D, args.pages_per_block, args.rehearsal) if latent
                 else verify_forms(q_rows, args.pages_per_block, args.rehearsal) if q_rows > 1
                 else {"xla": attention_decode_cached, "pallas": pallas})
        if vd != kd:
            forms = narrow_value_forms(forms, D)
        for B, widths in shapes:
            q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
            side = jax.random.normal(ks, (L, B, N, kd), jnp.bfloat16)
            side_v = side[..., :vd]
            for mp in widths:
                if only and f"{model}:{B}x{mp}" not in only:
                    continue
                # distinct pages while the pool has them
                ids = (rng.permutation(P - 1)[: B * mp] if B * mp < P
                       else rng.integers(0, P - 1, B * mp))
                tables = jnp.asarray(ids.reshape(B, mp) + 1, jnp.int32)
                fns = {name: jax.jit(functools.partial(columns, attend, L, D))
                       for name, attend in forms.items()}
                xla_ms = None
                fit = []
                # the pages of one of the kernel's blocks
                block_pages = args.pages_per_block or _pages_per_block(PS, kd, 2, mp)
                for fill in FILLS:
                    held = int(fill * mp * PS) - N
                    entry = jnp.full((B,), held, jnp.int32)
                    a = (q, kc, vc, side, side_v, tables, entry)
                    if xla_ms is None:
                        xla_ms = timed(fns["xla"], *a) / L * 1e3
                    ms = timed(fns["pallas"], *a) / L * 1e3
                    diff = float(jnp.max(jnp.abs(
                        fns["pallas"](*a).astype(jnp.float32)
                        - fns["xla"](*a).astype(jnp.float32))))
                    held_bytes = B * -(-held // PS) * page_bytes
                    fit.append((held_bytes, ms))
                    row = {"model": model, "B": B, "mp": mp, "fill": fill,
                           "xla_ms_per_layer": xla_ms, "pallas_ms_per_layer": ms,
                           "pallas_held_gb_per_s": held_bytes / ms / 1e6,
                           "pallas_us_per_block": ms * 1e3 * block_pages / (B * -(-held // PS)),
                           "pallas_block_bytes": block_pages * page_bytes,
                           "max_abs_diff": diff,
                           "pages_per_block": args.pages_per_block,
                           "device_kind": dev.device_kind,
                           "rehearsal": args.rehearsal}
                    print(json.dumps(row), flush=True)
                    rows.append(row)
                slope, fixed = np.polyfit([b for b, _ in fit], [m for _, m in fit], 1)
                row = {"model": model, "B": B, "mp": mp, "fit": True,
                       "pallas_fixed_ms_per_layer": float(fixed),
                       "pallas_rate_gb_per_s": float(1 / slope / 1e6),
                       "xla_ms_per_layer": xla_ms}
                print(json.dumps(row), flush=True)
                rows.append(row)
                if args.out:
                    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                    with open(args.out, "w") as f:
                        json.dump(rows, f, indent=1)
        del kc, vc
    return 0


if __name__ == "__main__":
    sys.exit(main())
