#!/usr/bin/env python3
"""Time the two decode attentions ``ModelRunner._attn_impl_for`` chooses
between, standalone, on the chip this process holds (ROADMAP S2):

    python3 scripts/time_decode_attention.py [--out chiprun_out/attn.json]

``ops.attention.attention_decode_cached`` (XLA: gathers every lane's whole
table) against ``ops.pallas.decode_attention.paged_attention_decode_cached``
(streams the pages that hold tokens), each inside a scan over the layers of
one cache at the ``qwen3-1.7b.eval`` cell's widths, at batch 8 and 16, tables
of 64, 128 and 256 pages, lanes filled to a quarter, a half and all of the
table.  Prints one JSON line per shape: milliseconds per layer for each.
Refuses to run without a TPU: a CPU time is not a device time.
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

from smg_tpu.ops.attention import attention_decode_cached  # noqa: E402
from smg_tpu.ops.pallas.decode_attention import paged_attention_decode_cached  # noqa: E402

L, P, PS, H, K, D, N = 28, 4725, 16, 16, 8, 128, 8
REPS = 5


def columns(attend, q, kc, vc, hk_all, hv_all, tables, entry):
    """One decode column's attention: every layer of the cache in turn."""

    def layer_body(h, xs):
        l, hk, hv = xs
        return h + attend(q + h, kc, vc, hk, hv, jnp.int32(N), l, tables, entry,
                          D ** -0.5), None

    return jax.lax.scan(layer_body, jnp.zeros_like(q),
                        (jnp.arange(L), hk_all, hv_all))[0]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"time_decode_attention: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 3
    key = jax.random.PRNGKey(0)
    # what the cache holds does not change what the attentions cost
    kc = jnp.full((L, P, PS, K * D), 0.01, jnp.bfloat16)
    vc = jnp.full((L, P, PS, K * D), 0.02, jnp.bfloat16)
    rng = np.random.default_rng(0)
    rows = []
    for B in (8, 16):
        q = jax.random.normal(key, (B, H, D), jnp.bfloat16)
        side = jax.random.normal(key, (L, B, N, K * D), jnp.bfloat16)
        for mp in (64, 128, 256):
            tables = jnp.asarray(
                rng.permutation(P - 1)[: B * mp].reshape(B, mp) + 1, jnp.int32)
            for fill in (0.25, 0.5, 1.0):
                entry = jnp.full((B,), int(fill * mp * PS) - N, jnp.int32)
                row = {"B": B, "mp": mp, "fill": fill,
                       "device_kind": dev.device_kind}
                for name, attend in (("xla", attention_decode_cached),
                                     ("pallas", paged_attention_decode_cached)):
                    fn = jax.jit(functools.partial(columns, attend))
                    a = (q, kc, vc, side, side, tables, entry)
                    fn(*a).block_until_ready()
                    t = time.perf_counter()
                    for _ in range(REPS):
                        out = fn(*a)
                    out.block_until_ready()
                    row[f"{name}_ms_per_layer"] = (
                        (time.perf_counter() - t) / (REPS * L) * 1e3)
                print(json.dumps(row), flush=True)
                rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
