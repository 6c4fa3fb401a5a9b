#!/usr/bin/env python3
"""Time the routed experts' grouped products standalone, on the chip this
process holds:

    python3 scripts/time_moe_experts.py [--out chiprun_out/moe.json] [--rehearsal]

``ops.moe._experts`` (gate, up and down of the held experts over rows sorted
by expert) under the kernel ``ops/pallas/moe_experts.py`` at several tilings
and under XLA's ``lax.ragged_dot``, at the widths of the ``reason`` cell (16
held experts of 7,680 x 2,048 in each of 4 stacked layers, bfloat16) in its
two regimes:

- a decode column: a buffer of ``lanes x 8`` rows of which a sixteenth are
  routed here (64 lanes: 32 rows; 16 lanes: 8 rows), spread over the experts
  as a seeded random router spreads them, so that some experts get no row.
  Bound by the bytes of the experts hit (94.4 MB each); the line gives
  GB/s over those bytes, and the same rows with every expert hit once for
  comparison;
- a prefill: 2,048 and 4,096 tokens, whose 1,024 and 2,048 rows sit in the
  buffer ``rows_buffer`` gives them.  Bound by the MXU; the line gives
  TFLOP/s over the rows computed.

One JSON line a shape and form, with the largest difference between the
kernel's result and XLA's over the rows computed.  Refuses to run without a TPU (``--rehearsal``
walks the same code at toy size with the kernel interpreted).
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

from smg_tpu.ops import moe  # noqa: E402
from smg_tpu.ops.pallas import moe_experts  # noqa: E402

REPS = 5
LAYERS, HELD, TOP_K, ROUTER = 4, 16, 8, 256


def sizes_for(tokens: int, rng, every_expert: bool = False) -> np.ndarray:
    """Rows of each held expert when ``tokens`` tokens pick 8 of 256 at random."""
    picks = np.concatenate([rng.choice(ROUTER, TOP_K, replace=False) for _ in range(tokens)])
    sizes = np.bincount(picks[picks < HELD], minlength=HELD)
    if every_expert:
        sizes = np.maximum(sizes, 1)
    return sizes.astype(np.int32)


def timed(fn, *a) -> float:
    jax.block_until_ready(fn(*a))
    t = time.perf_counter()
    for _ in range(REPS):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / REPS


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"time_moe_experts: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    E, F = (256, 128) if args.rehearsal else (7680, 2048)
    dt = jnp.float32 if args.rehearsal else jnp.bfloat16
    kg, ku, kd, kx = jax.random.split(jax.random.PRNGKey(0), 4)
    w_gate = jax.random.normal(kg, (LAYERS, HELD, E, F), dt) * 0.02
    w_up = jax.random.normal(ku, (LAYERS, HELD, E, F), dt) * 0.02
    w_down = jax.random.normal(kd, (LAYERS, HELD, F, E), dt) * 0.02
    expert_bytes = 3 * E * F * jnp.dtype(dt).itemsize
    rng = np.random.default_rng(0)
    interp = args.rehearsal
    # (regime, tokens, every expert hit, tilings (tm, tk, tn) of gate/up; down swaps tk and tn)
    decode_tiles = [None, (128, 1536, 1024), (128, 3840, 512), (32, 1536, 1024),
                    (128, 1536, 2048), (128, 7680, 512)]
    prefill_tiles = [None, (256, 1536, 1024), (512, 1536, 1024), (256, 768, 2048),
                     (128, 1536, 1024)]
    shapes = ([("decode", 16, False, decode_tiles[:2]), ("decode", 64, False, decode_tiles),
               ("decode", 64, True, decode_tiles[:1])]
              + [("prefill", t, False, prefill_tiles) for t in (2048, 4096)])
    if args.rehearsal:
        shapes = [("decode", 16, False, [None, (64, 128, 128)]),
                  ("prefill", 512, False, [None])]
    rows_out = []
    for regime, tokens, every, tilings in shapes:
        sizes = sizes_for(tokens, rng, every)
        R = moe.rows_buffer(tokens * TOP_K)
        x = jax.random.normal(kx, (R, E), dt)
        gs = jnp.asarray(sizes)
        n_rows, hit = int(sizes.sum()), int((sizes > 0).sum())
        want: dict = {}  # XLA's result for these rows, which each tiling is held to

        def run(form, tiles):
            # the weights are arguments: closed over, 4.5 GB would be constants of the program
            if form == "xla":
                fn = lambda x, gs, w_gate, w_up, w_down: moe._experts(
                    x, w_gate, w_up, w_down, gs, "xla", jnp.int32(1))
            else:
                def fn(x, gs, w_gate, w_up, w_down):
                    gm = functools.partial(moe_experts.grouped_matmul, interpret=interp)
                    t1 = tiles
                    t2 = None if tiles is None else (tiles[0], min(tiles[2] * 2, F), 1536)
                    if args.rehearsal and tiles is not None:
                        t2 = (tiles[0], 128, 128)
                    g = gm(x, w_gate, gs, 1, tiles=t1)
                    u = gm(x, w_up, gs, 1, tiles=t1)
                    return gm(jax.nn.silu(g) * u, w_down, gs, 1, tiles=t2)
            try:
                jitted = jax.jit(fn)
                secs = timed(jitted, x, gs, w_gate, w_up, w_down)
                out = np.asarray(jitted(x, gs, w_gate, w_up, w_down)[:n_rows], np.float32)
            except Exception as e:  # noqa: BLE001 - a tiling the compiler refuses is a result
                return {"error": f"{type(e).__name__}: {str(e)[:200]}"}
            if form == "xla":
                want["out"] = out
            return {"max_abs_diff_to_xla": float(np.max(np.abs(out - want["out"]), initial=0.0)),
                    "out_abs_max": float(np.max(np.abs(want["out"]), initial=0.0)),
                    "ms": secs * 1e3,
                    "hit_gb_per_s": hit * expert_bytes / secs / 1e9,
                    "tflop_per_s": n_rows * 2 * 3 * E * F / secs / 1e12}

        for form, tiles in [("xla", None)] + [("pallas", t) for t in tilings]:
            row = {"regime": regime, "tokens": tokens, "buffer_rows": R, "rows": n_rows,
                   "experts_hit": hit, "form": form,
                   "tiles": tiles or (form == "pallas" and moe_experts.tiling(R, E, F)) or None,
                   **run(form, tiles), "device_kind": dev.device_kind,
                   "rehearsal": args.rehearsal}
            print(json.dumps(row), flush=True)
            rows_out.append(row)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows_out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
