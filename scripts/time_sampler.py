#!/usr/bin/env python3
"""Time the sampler's top values and the sampler whole, standalone, on the
chip this process holds (ROADMAP S6):

    python3 scripts/time_sampler.py [--out chiprun_out/sampler.json]
        [--only 16x151936,1x151936] [--blocks 128,64+8,256+16] [--rehearsal]

``engine.sampling.top_values`` (block maxima, ``top_k`` of the maxima, the
held blocks gathered, ``top_k`` of those) against ``lax.top_k`` of the whole
row, which the v5e runs as a sort of the vocabulary, at the logits' shapes
of ``qwen3-1.7b`` ([16, 151936] in a decode column, [1, 151936] after a solo
prefill), ``olmo-hybrid-7b`` ([16, 100352]) and ``mistral-nemo-12b``
([16, 131072]); then ``sample_tokens`` whole with each of the two, and its
other parts one by one (the gumbel argmax, the two ``logsumexp``, the greedy
argmax), which is what a column still pays after the cut.

Each form runs 32 times inside one jitted scan, each pass's input depending
on the last one's output, so the host's dispatch is paid once; ``rowmax`` is
that loop round one pass over the row and nothing else, the floor of the
method.  Prints one JSON line a shape, microseconds a call.  Refuses to run
without a TPU: a CPU time is not a device time (``--rehearsal`` walks the
same code at toy size to find typos; its times mean nothing).
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

from smg_tpu.engine import sampling  # noqa: E402

PASSES = 32
REPS = 5
SHAPES = ((16, 151936), (16, 100352), (1, 151936), (16, 131072))
REHEARSAL = ((4, 131072), (1, 32773))


def plain(z, k):
    return jax.lax.top_k(z, k)[0]


def per_call_us(fn, z, *args) -> float:
    """Microseconds one ``fn(z, *args)`` takes inside a scan of ``PASSES`` of
    them; ``fn`` gives a float array whose sum feeds the next pass."""

    @jax.jit
    def loop(z, *args):
        def body(c, _):
            return fn(z + c, *args).sum() * 1e-38, None

        return jax.lax.scan(body, jnp.float32(0), None, length=PASSES)[0]

    loop(z, *args).block_until_ready()
    t = time.perf_counter()
    for _ in range(REPS):
        out = loop(z, *args)
    out.block_until_ready()
    return (time.perf_counter() - t) / (REPS * PASSES) * 1e6


def sampler(top_values):
    """``sample_tokens`` with ``top_values`` in place."""

    def run(logits, key, *params):
        kept, sampling.top_values = sampling.top_values, top_values
        try:
            toks, lps = sampling.sample_tokens(logits, key, *params)
        finally:
            sampling.top_values = kept
        return lps + toks

    return run


def sampler_args(B: int) -> tuple:
    """A key and the four parameter rows: greedy lanes beside sampled ones
    (one program either way: the parameters are data)."""
    return (jax.random.PRNGKey(1),
            jnp.where(jnp.arange(B) % 2 == 0, 0.0, 0.6).astype(jnp.float32),
            jnp.full((B,), 20, jnp.int32), jnp.full((B,), 0.95, jnp.float32),
            jnp.zeros((B,), jnp.float32))


def parts():
    key = jax.random.PRNGKey(1)
    return {
        "gumbel_argmax": lambda z: jnp.argmax(
            z + jax.random.gumbel(key, z.shape, jnp.float32), -1).astype(jnp.float32),
        "logsumexp_twice": lambda z: jax.nn.logsumexp(z, -1) + jax.nn.logsumexp(z * 0.5, -1),
        "argmax": lambda z: jnp.argmax(z, -1).astype(jnp.float32),
        "rowmax": lambda z: z.max(-1),
    }


def time_shape(B: int, V: int, blocks: list[tuple[int, ...]]) -> dict:
    z = jax.random.normal(jax.random.PRNGKey(V + B), (B, V), jnp.float32) * 3.0
    k = min(sampling.K_CAP, V)
    ref = np.asarray(plain(z, k))
    row: dict = {"shape": [B, V], "equal": True, "top_values_us": {}, "sample_tokens_us": {}}
    forms = {"plain": plain, "tree": sampling.top_values,
             **{"+".join(map(str, b)): functools.partial(sampling.top_values, blocks=b)
                for b in blocks}}
    for name, fn in forms.items():
        got = np.asarray(jax.jit(fn, static_argnums=1)(z, k))
        row["equal"] &= bool((got.view(np.int32) == ref.view(np.int32)).all())
        row["top_values_us"][name] = round(per_call_us(lambda z, fn=fn: fn(z, k), z), 1)
        row["sample_tokens_us"][name] = round(per_call_us(sampler(fn), z, *sampler_args(B)), 1)
    row["parts_us"] = {n: round(per_call_us(fn, z), 1) for n, fn in parts().items()}
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--only", default="", help="BxV,BxV: these shapes only")
    ap.add_argument("--blocks", default="", help="128,128+16,64+8: cuts to time beside the tree's")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"time_sampler: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    shapes = REHEARSAL if args.rehearsal else SHAPES
    if args.only:
        shapes = tuple(tuple(int(n) for n in s.split("x")) for s in args.only.split(","))
    blocks = [tuple(int(n) for n in b.split("+")) for b in args.blocks.split(",") if b]
    rows = []
    for B, V in shapes:
        rows.append(time_shape(B, V, blocks))
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": {"platform": dev.platform, "kind": dev.device_kind},
                       "passes": PASSES, "reps": REPS, "rehearsal": args.rehearsal,
                       "rows": rows}, f, indent=1)
    return 0 if all(r["equal"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
