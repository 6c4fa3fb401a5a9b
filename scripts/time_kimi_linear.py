#!/usr/bin/env python3
"""Check and time the ``kimi-linear-48b-a3b`` configuration standalone, on the
chip this process holds:

    python3 scripts/time_kimi_linear.py --check 3 [--float8 2]
    python3 scripts/time_kimi_linear.py --check 2 --draw dt_max=0.3,attn_out=3
    python3 scripts/time_kimi_linear.py --time

Builds the runner ``serve`` would build for ``benchmark/configs/
kimi-linear-48b-a3b.json`` (random weights, auto-sized caches).

``--check N`` runs the benchmark's own comparison (``benchmark/reference.
check_engine``: serving-path logits against the position-by-position float32
reference, the wrong-page control and the drive's six) for N seeds and prints
each seed's rows and controls and the drive's reading of the state rounded to
bfloat16.  Once with the weights as the configuration draws them, and once more
for every ``--draw`` given: those sizes of ``random_weights`` overridden and the
weights drawn again (the old ones freed first: two copies do not fit).

``--float8 N``: the nearest precision below the configuration's, as
``scripts/time_nemotron_h.py`` reads it (its ``through_float8``): the reference
rows from the weights as served, then every matrix through ``float8_e4m3fn`` and
back and the serving path on those, N seeds.  It comes last: the weights stay
rounded.

``--time`` times, after one warm-up each, on the host clock round
``block_until_ready``: the KDA decode step alone over the pool's layers at 64
lanes, as the kernel ``smg.kda.decode`` (the body that expands the decay a
channel), as ``smg.linattn.decode`` on the same pool (the body of the decay a
head, given each head's mean decay: what the extra operand costs is the
difference) and as the XLA form; the convolution's decode step alone
(``conv_decode_step``: a slice and an update of the tail pool a lane) over the
pool's layers at 64 lanes, eight columns of it a call so that the call's own
cost stays small beside it; a decode frame of 8 columns at 64 lanes; a
grouped prefill of eight 512-token rows and of two 2,048-token rows; the
chunked prefill form of the recurrence alone (``kda_chunked``), one call a KDA
layer chained, at eight rows of 512 tokens and at one of 1,024.

``--picks SEED``: for the check's first sequence of that seed, the experts the
program's dense forward (bfloat16, ``forward_train``, run un-jitted) and the
reference pick, expert layer by expert layer: the tokens whose eight picks
differ between the two, and for the first of them both sets and the scores'
levels.

Prints one JSON line a reading.  Refuses to run without a TPU: a CPU time is
not a device time.  ``--rehearsal`` runs the same code at the configuration's
rehearsal widths on the CPU and prints no times.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

CELL = "kimi-linear-48b-a3b.reason"
REPS = 3
SEED0 = 2900005000


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--check", type=int, default=0, metavar="N")
    ap.add_argument("--draw", action="append", default=[], metavar="NAME=VALUE,...")
    ap.add_argument("--float8", type=int, default=0, metavar="N")
    ap.add_argument("--time", action="store_true")
    ap.add_argument("--picks", type=int, default=None, metavar="SEED")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    import catalog
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.recurrent_runner import RecurrentModelRunner
    from smg_tpu.models.config import ModelConfig

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"time_kimi_linear: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    cell = catalog.Cell(catalog.load_benchmark(), CELL, rehearsal=args.rehearsal)
    dtype = "float32" if args.rehearsal else "bfloat16"
    model = ModelConfig.from_hf_config(cell.hf_config, dtype=dtype)
    config = EngineConfig(
        model=model, dtype=dtype,
        # three quarters of the chip: ``--picks`` and the reference hold
        # temporaries of their own beside the caches
        cache=CacheConfig(dtype=dtype, auto_size=not args.rehearsal, num_pages=1024,
                          hbm_utilization=0.75),
        scheduler=SchedulerConfig(decode_horizon=8, max_seq_len=1024 if args.rehearsal else 8192,
                                  max_prefill_tokens=256 if args.rehearsal else 4096))
    runner = RecurrentModelRunner(config)
    print(json.dumps({"device": dev.device_kind, "pages": runner.spec.num_pages,
                      "state": runner.state_info(), "latent": runner.latent_info(),
                      "moe": runner.moe_info()}), flush=True)
    if args.picks is not None:
        picks(args, runner, cell, args.picks)
    if args.check:
        check(args, runner, cell)
    if args.time:
        timings(args, runner)
    return 0


def check(args, runner, cell) -> None:
    import dataclasses
    import types

    import jax

    import reference
    from smg_tpu.models import kimi_linear

    arch = cell.architecture
    drives = []

    class Kept(arch.Drive):
        def __init__(self, *a):
            super().__init__(*a)
            drives.append(self)

    served = types.SimpleNamespace(**{**vars(arch), "drive": Kept})
    cell.architecture = served
    engine = types.SimpleNamespace(runner=runner)

    def one(seed: int, **said) -> dict:
        t = time.perf_counter()
        drives.clear()
        c = reference.check_engine(engine, cell, SEED0 + seed, args.rehearsal)
        rows = {f"{impl}.{k}": e for impl, per in c["errors"].items() for k, e in per.items()}
        control = {k: round(v, 4) for k, v in c["control_errors"].items()}
        out = {**said, "seed": seed, "ok": c["ok"], "worst": round(c["worst"], 4),
               "worst_row": max(rows, key=rows.get), "least": round(min(rows.values()), 4),
               "rows": {impl: [round(e, 3) for e in per.values()]
                        for impl, per in c["errors"].items()},
               "controls": control, "least_control": min(control.values()),
               "state_in_bfloat16_moves": [round(d.rounded_state_reading or 0.0, 5)
                                           for d in drives],
               "seconds": round(time.perf_counter() - t, 1)}
        print(json.dumps(out), flush=True)
        return out

    drawn = dict(runner.model_cfg.random_init)
    for draw in [""] + args.draw:
        changed = {}
        for pair in filter(None, draw.split(",")):
            name, value = pair.split("=")
            changed[name] = float(value)
        if changed:
            cfg = dataclasses.replace(runner.model_cfg, random_init=tuple(
                sorted({**drawn, **changed}.items())))
            runner.params = None  # two copies of the weights do not fit the chip
            runner.params = jax.jit(lambda k: kimi_linear.init_params(cfg, k))(
                jax.random.PRNGKey(0))
        for seed in range(args.check):
            one(seed, draw=changed)
    if args.float8:
        from time_nemotron_h import through_float8

        # the reference rows from the weights as served, the serving path on
        # the rounded ones: ``check_engine`` asks for both rows first
        calls = []

        def logits(params, *a):
            out = arch.logits(params, *a)
            calls.append(1)
            if len(calls) == 2:
                print(json.dumps({"float8_moved_rms": through_float8(runner, arch._rounded)}),
                      flush=True)
            return out

        cell.architecture = types.SimpleNamespace(**{**vars(served), "logits": logits})
        for seed in range(args.float8):
            one(seed, weights="float8_e4m3fn")
            calls[:] = [1, 1, 1]  # rounded once


def picks(args, runner, cell, seed: int) -> None:
    """Where the program's and the reference's routers part (``--picks``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from smg_tpu.models import kimi_linear
    from smg_tpu.ops import moe

    arch, cfg, hf = cell.architecture, runner.model_cfg, cell.hf_config
    n = 92 if args.rehearsal else 704
    tokens = np.random.default_rng(SEED0 + seed).integers(
        2, hf["vocab_size"], size=n).astype(np.int32)
    ours, theirs = [], []
    real_route, real_ref = moe.route, arch._route

    def route(x, router, **kw):
        r = real_route(x, router, **kw)
        logits = jnp.einsum("te,ex->tx", x, router, preferred_element_type=jnp.float32)
        ours.append((np.asarray(r.experts), np.asarray(logits)))
        return r

    def ref_route(u, w, **kw):
        picked, weight = real_ref(u, w, **kw)
        theirs.append((np.asarray(picked), np.asarray(u @ w["router"])))
        return picked, weight

    moe.route, arch._route = route, ref_route
    try:
        with jax.disable_jit():
            got = kimi_linear.forward_train(runner.params, cfg, runner.inv_freq,
                                            jnp.asarray(tokens)[None], moe_impl="xla")[0]
        want = arch.logits(runner.params, hf, tokens, list(range(n)))
    finally:
        moe.route, arch._route = real_route, real_ref
    diff = np.max(np.abs(np.asarray(got, np.float32) - want), axis=-1) / np.std(want, axis=-1)
    out = {"picks_of_seed": seed, "dense_forward_error_max": round(float(diff.max()), 4),
           "dense_forward_error_median": round(float(np.median(diff)), 4),
           "rows_over_0.3": np.flatnonzero(diff > 0.3)[:20].tolist(), "layers": []}
    for l, ((a, la), (b, lb)) in enumerate(zip(ours, theirs)):
        rows = np.flatnonzero((np.sort(a, 1) != np.sort(b, 1)).any(1))
        line = {"expert_layer": l, "tokens_whose_picks_differ": int(rows.size),
                "first": rows[:8].tolist()}
        if rows.size:
            t = int(rows[0])
            unit = np.abs(la[t]).min()  # one level's step is twice the least logit but 0
            line["program_only"] = sorted(set(a[t].tolist()) - set(b[t].tolist()))
            line["reference_only"] = sorted(set(b[t].tolist()) - set(a[t].tolist()))
            line["program_logits_of_both"] = [round(float(la[t][e]), 4) for e in
                                              line["program_only"] + line["reference_only"]]
            line["reference_logits_of_both"] = [round(float(lb[t][e]), 4) for e in
                                                line["program_only"] + line["reference_only"]]
            line["least_abs_logit"] = round(float(unit), 5)
        out["layers"].append(line)
    print(json.dumps(out), flush=True)


def timings(args, runner) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from smg_tpu.ops.linear_attention import conv_decode_step, kda_chunked, kda_step
    from smg_tpu.ops.pallas.linattn_decode import kda_decode, linattn_decode
    from time_olmo_hybrid import chunked_form_ms

    cfg = runner.model_cfg
    B, N, ps = (8 if args.rehearsal else 64), 8, runner.config.cache.page_size

    def timed(fn):
        out = []
        for _ in range(REPS + 1):
            t = time.perf_counter()
            jax.block_until_ready(fn())
            out.append((time.perf_counter() - t) * 1e3)
        return [round(x, 3) for x in out[1:]]  # the first run compiles

    res = {}
    H, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (l2(jax.random.normal(ks[i], (B, H, dk), jnp.float32)) for i in (0, 1))
    v = jax.random.normal(ks[2], (B, H, dv), jnp.float32)
    alpha = jax.random.uniform(ks[3], (B, H, dk), jnp.float32, 0.5, 1.0)
    beta = jax.random.uniform(ks[4], (B, H), jnp.float32)
    slots = jnp.arange(1, B + 1, dtype=jnp.int32)
    layers = runner.s_pool.shape[0]
    steps = {"xla": lambda pool, l: kda_step(pool, l, slots, q, k, v, alpha, beta)}
    if runner.state_kernel_fits or args.rehearsal:
        steps["kernel_decay_a_channel"] = lambda pool, l: kda_decode(
            pool, l, slots, q, k, v, alpha, beta, interpret=args.rehearsal)
        steps["kernel_decay_a_head"] = lambda pool, l: linattn_decode(
            pool, l, slots, q, k, v, jnp.mean(alpha, axis=-1), beta, interpret=args.rehearsal)

    for name, step in steps.items():
        def run(pool, step=step):
            def body(l, c):
                pool, acc = c
                y, pool = step(pool, l)
                return pool, acc + jnp.sum(y)
            return jax.lax.fori_loop(0, layers, body, (pool, jnp.float32(0)))

        fn = jax.jit(run, donate_argnums=(0,))

        def once(fn=fn):
            runner.s_pool, acc = fn(runner.s_pool)
            return acc

        try:
            res[f"kda_decode_{name}_ms_{layers}_layers_{B}_lanes"] = timed(once)
        except Exception as e:  # noqa: BLE001 - a form that does not compile is a reading too
            res[f"kda_decode_{name}_failed"] = f"{type(e).__name__}: {str(e)[:200]}"
    res["kda_decode_least_ms"] = round(B * layers * 2 * H * dk * dv * 4 / 819e9 * 1e3, 3)

    K = cfg.linear_conv_kernel_dim
    C = math.prod(runner.c_pool.shape[2:]) // (K - 1)
    x = jax.random.normal(ks[0], (B, C), jnp.float32).astype(runner.c_pool.dtype)
    conv_w = jax.random.normal(ks[1], (K, C), jnp.float32).astype(runner.c_pool.dtype)

    def tails(pool):
        def body(i, c):
            pool, acc = c
            y, pool = conv_decode_step(pool, i % layers, slots, slots > 0, x, conv_w)
            return pool, acc + jnp.sum(y)
        return jax.lax.fori_loop(0, N * layers, body, (pool, jnp.float32(0)))

    tails = jax.jit(tails, donate_argnums=(0,))

    def once():
        runner.c_pool, acc = tails(runner.c_pool)
        return acc

    res[f"conv_decode_ms_{N}_columns_{layers}_layers_{B}_lanes"] = timed(once)
    res["conv_decode_least_ms"] = round(
        N * B * layers * 2 * (K - 1) * C * runner.c_pool.dtype.itemsize / 819e9 * 1e3, 3)

    zeros, ones = np.zeros(B, np.float32), np.ones(B, np.float32)
    w = 16 if args.rehearsal else 128

    def frame():
        toks, _l, _s = runner.decode_multi_async(
            np.zeros(B, np.int32), np.full(B, w * ps - N, np.int32),
            np.zeros((B, w), np.int32), zeros, np.full(B, -1, np.int32), ones, zeros, N,
            max_steps=N, stop_state=(np.full((B, 1), -1, np.int32),
                                     np.full(B, np.int32(2**30)), np.ones(B, bool)),
            state_slots=np.arange(1, B + 1, dtype=np.int32))
        return toks

    res[f"decode_frame_ms_{N}_columns_{B}_lanes"] = timed(frame)
    table = np.zeros(runner.max_pages_per_seq, np.int32)
    for G, T in ((2, 64),) if args.rehearsal else ((8, 512), (2, 2048)):
        group = [([0] * T, 0, table)] * G
        one = (np.zeros(G, np.float32), np.full(G, -1, np.int32), np.ones(G, np.float32),
               np.zeros(G, np.float32))

        def prefill(group=group, one=one):
            runner.prefill_batched(group, *one)
            return runner.k_cache

        res[f"grouped_prefill_ms_{G}x{T}"] = timed(prefill)
    for G, T in ((2, 64),) if args.rehearsal else ((8, 512), (1, 1024)):
        res[f"kda_chunked_ms_{layers}_layers_{G}x{T}"] = chunked_form_ms(
            kda_chunked, layers, G, T, H, dk, dv, per_channel=True)
    if args.rehearsal:
        res = {k: v for k, v in res.items() if "_ms" not in k}
        res["rehearsal"] = True
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    sys.exit(main())
