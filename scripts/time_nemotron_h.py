#!/usr/bin/env python3
"""Check and time the ``nemotron-3-super-120b-a12b`` configuration standalone,
on the chip this process holds:

    python3 scripts/time_nemotron_h.py --check 5 [--trace-over 0.2] [--float8 2]
    python3 scripts/time_nemotron_h.py --check 2 --draw dt_max=0.3,bc_gain=3
    python3 scripts/time_nemotron_h.py --time

Builds the runner ``serve`` would build for ``benchmark/configs/
nemotron-3-super-120b-a12b.json`` (random weights, auto-sized caches).

``--check N`` runs the benchmark's own comparison (``benchmark/reference.
check_engine``: serving-path logits against the position-by-position float32
reference, the wrong-page control and the drive's own) for N seeds and prints
each seed's rows and controls, and the drive's reading of the state it was
handed: the largest share of a live slot's elements that bfloat16 holds
exactly over the sound columns, and under the control that rounds the pool
(``architectures/nemotron_h.STATE_COARSE_LIMIT`` lies between the two).
Once with the weights as the configuration draws them, and once more for every
``--draw`` given: those sizes of ``random_weights`` overridden and the weights
drawn again (the old ones freed first: two copies do not fit).

``--trace-over X``: for a seed whose worst sound row reads over X, where that
row's error arises: the program's dense forward of the sequence (bfloat16,
``forward_train``) beside the reference's, layer by layer (the distance of
the row's stream from the reference's over the reference's size, and the same
for the sequence's median row, the rows over three times that), and in every
expert layer the rows whose picks differ between the two and the picks the two
make for the row's token.  ``--trace-row SEED:ROW`` does that for one row
without the check.

``--float8 N``: the nearest precision below the configuration's, as PR 41 and
PR 43 read it: the reference rows from the weights as served, then every
matrix through ``float8_e4m3fn`` and back (one scale a matrix and layer, in
place a layer at a time, since two copies do not fit) and the serving path on
those, N seeds.  It comes last: the weights stay rounded.

``--time`` times, after one warm-up each, on the host clock round
``block_until_ready``: the state-space decode step alone over the pool's layers
at 64 lanes, as the kernel ``smg.ssm.decode``, as its XLA form, and as
``smg.linattn.decode`` given the same state (the delta rule with ``beta`` 0 and
every head its own copy of ``B`` and ``C``: the rule as a static argument of
that kernel would run no faster than this); a decode frame of 8 columns at 64
lanes; the grouped prefill launches of one cold row of 512 and of 1,024 tokens
and of two rows of 2,048.

Prints one JSON line a reading.  Refuses to run without a TPU: a CPU time is
not a device time.  ``--rehearsal`` runs the same code at the configuration's
rehearsal widths on the CPU and prints no times.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

CELL = "nemotron-3-super-120b-a12b.reason"
REPS = 3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--check", type=int, default=0, metavar="N")
    ap.add_argument("--draw", action="append", default=[], metavar="NAME=VALUE,...")
    ap.add_argument("--seeds", default=None, metavar="I,J,...",
                    help="which of --check's seeds, and in what order (default: all N)")
    ap.add_argument("--trace-over", type=float, default=None, metavar="X")
    ap.add_argument("--trace-row", default=None, metavar="SEED:ROW",
                    help="trace one compared row without the check, e.g. 4:pallas.decode[1]+0")
    ap.add_argument("--float8", type=int, default=0, metavar="N")
    ap.add_argument("--time", action="store_true")
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
        print(f"time_nemotron_h: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    cell = catalog.Cell(catalog.load_benchmark(), CELL, rehearsal=args.rehearsal)
    dtype = "float32" if args.rehearsal else "bfloat16"
    model = ModelConfig.from_hf_config(cell.hf_config, dtype=dtype)
    config = EngineConfig(
        model=model, dtype=dtype,
        cache=CacheConfig(dtype=dtype, auto_size=not args.rehearsal, num_pages=1024),
        scheduler=SchedulerConfig(decode_horizon=8, max_seq_len=1024 if args.rehearsal else 8192,
                                  max_prefill_tokens=256 if args.rehearsal else 4096))
    runner = RecurrentModelRunner(config)
    print(json.dumps({"device": dev.device_kind, "pages": runner.spec.num_pages,
                      "state": runner.state_info(), "moe": runner.moe_info()}), flush=True)
    if args.trace_row:
        seed, row = args.trace_row.split(":", 1)
        trace_row(args, runner, cell, cell.architecture, int(seed), row)
    if args.check:
        check(args, runner, cell)
    if args.time:
        timings(args, runner)
    return 0


SEED0 = 2900004700


def check(args, runner, cell) -> None:
    import dataclasses
    import types

    import jax

    import reference
    from smg_tpu.models import nemotron_h

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
        # a drive's columns: four sound, the wrong page, four controls on the
        # sound state, then the rounded pool
        sound = max(x for d in drives for col in d.coarse_shares[:-1] for x in col)
        rounded = min(x for d in drives for x in d.coarse_shares[-1])
        worst = max(rows, key=rows.get)
        out = {**said, "seed": seed, "ok": c["ok"], "worst": round(c["worst"], 4),
               "worst_row": worst, "least": round(min(rows.values()), 4),
               "rows": {impl: [round(e, 3) for e in per.values()]
                        for impl, per in c["errors"].items()},
               "controls": control, "least_control": min(control.values()),
               "coarse_share": {"sound_max": sound, "rounded_min": rounded,
                                "limit": arch.STATE_COARSE_LIMIT},
               "seconds": round(time.perf_counter() - t, 1)}
        print(json.dumps(out), flush=True)
        return out

    drawn = dict(runner.model_cfg.random_init)
    seeds = ([int(i) for i in args.seeds.split(",")] if args.seeds else range(args.check))
    for draw in [""] + args.draw:
        changed = {}
        for pair in filter(None, draw.split(",")):
            name, value = pair.split("=")
            changed[name] = float(value)
        if changed:
            cfg = dataclasses.replace(runner.model_cfg, random_init=tuple(
                sorted({**drawn, **changed}.items())))
            runner.params = None  # two copies of the weights do not fit the chip
            runner.params = jax.jit(lambda k: nemotron_h.init_params(cfg, k))(
                jax.random.PRNGKey(0))
        for seed in seeds:
            got = one(seed, draw=changed)
            if args.trace_over is not None and got["worst"] > args.trace_over and not changed:
                trace_row(args, runner, cell, arch, seed, got["worst_row"])
    if args.float8:
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
        for seed in list(seeds)[: args.float8]:
            one(seed, weights="float8_e4m3fn")
            calls[:] = [1, 1, 1]  # rounded once


def through_float8(runner, grid) -> dict:
    """Every matrix of the served weights onto ``float8_e4m3fn``'s grid
    (``grid(x, mantissa_bits)``: ``architectures/nemotron_h._rounded``), one
    scale a matrix and layer, in place; returns by group how far that moved
    them (relative root mean square)."""
    import jax
    import jax.numpy as jnp

    def rounded(w):
        # e4m3fn's grid by arithmetic (a pair of conversions inside one
        # program rounds nothing on a TPU: ``arch._rounded``): three mantissa
        # bits down to 2^-6, steps of 2^-9 below, the largest value 448
        f = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(f)), 1e-30) / 448.0
        v = f / scale
        q = jnp.where(jnp.abs(v) < 2.0 ** -6, jnp.round(v * 512.0) / 512.0, grid(v, 3)) * scale
        return q.astype(w.dtype), jnp.sum(jnp.square(q - f)), jnp.sum(jnp.square(f))

    whole = jax.jit(rounded, donate_argnums=0)

    @jax.jit
    def layer_of(w, l):
        return rounded(w[l])

    put = jax.jit(lambda w, l, q: w.at[l].set(q), donate_argnums=0)
    params, moved = runner.params, {}
    for group, tree in params.items():
        num = den = 0.0
        if not isinstance(tree, dict):
            if tree.ndim == 2:
                params[group], a, b = whole(tree)
                num, den = float(a), float(b)
        else:
            for name, w in tree.items():
                if w.ndim < 3:  # a vector a layer: norms, biases, per-head numbers
                    continue
                for l in range(w.shape[0]):
                    q, a, b = layer_of(w, l)
                    w = put(w, l, q)
                    num, den = num + float(a), den + float(b)
                tree[name] = w
        if den:
            moved[group] = round((num / den) ** 0.5, 5)
    return moved


def trace_row(args, runner, cell, arch, seed: int, worst_row: str) -> None:
    """Where the error of one compared row arises (``--trace-over``)."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from smg_tpu.models import nemotron_h
    from smg_tpu.ops import moe

    # the sequences as ``reference.check_engine`` draws them
    lens, n_dec = ((88, 48), 4) if args.rehearsal else ((700, 380), 4)
    hf, params, cfg = cell.hf_config, runner.params, runner.model_cfg
    rng = np.random.default_rng(SEED0 + seed)
    toks = [rng.integers(2, hf["vocab_size"], size=n + n_dec).astype(np.int32) for n in lens]
    kind, s, j = re.match(r"\w+\.(prefill|decode)\[(\d)\](?:\+(\d))?", worst_row).groups()
    s = int(s)
    row = lens[s] - 1 + (int(j) + 1 if kind == "decode" else 0)
    tokens = toks[s][: row + 1]
    f32 = jnp.float32

    # the reference, a layer at a time (``arch.logits``'s loop)
    sh = arch._shape(hf)
    up = lambda tree, i: {k: v[i].astype(f32) for k, v in tree.items()}
    ref_h, ref_picks, seen = [], [], dict.fromkeys(sh["n"], 0)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        for k in sh["kinds"]:
            i = seen[k]
            seen[k] += 1
            if k == "mamba":
                h = jax.jit(arch._mamba_layer, static_argnames=("heads", "head_dim", "state",
                                                                "groups", "eps"))(
                    h, up(params["mamba"], i), heads=sh["Hm"], head_dim=sh["P"],
                    state=sh["N"], groups=sh["R"], eps=sh["eps"])
            elif k == "attn":
                h = arch._attention_layer(h, up(params["attn"], i), head_dim=sh["D"],
                                          eps=sh["eps"])
            else:
                w = up(params["moe"], i)
                picked, _ = arch._route(arch._rms(h, w["norm"], sh["eps"]), w, top_k=sh["top_k"],
                                        scale=sh["scale"], renorm=sh["renorm"])
                ref_picks.append(np.sort(np.asarray(picked), axis=1))
                h = arch._moe_layer(h, w, params["experts"], i, jax.jit(arch._expert),
                                    first=sh["first"], top_k=sh["top_k"], scale=sh["scale"],
                                    renorm=sh["renorm"], eps=sh["eps"])
            ref_h.append(np.asarray(h))
        last = arch._rms(h[row][None], params["final_norm"].astype(f32), sh["eps"])
        want = np.asarray(last @ params["lm_head"].astype(f32))[0]

    # the program's dense forward, its layers and its routers overheard
    got_h, got_picks = [], []
    real = {n: getattr(nemotron_h, n) for n in ("mamba_layer", "attention_layer", "moe_layer")}
    real_route = moe.route

    def heard(fn):
        def layer(h, *a, **kw):
            out = fn(h, *a, **kw)
            got_h.append(np.asarray(out[0][0].astype(f32)))
            return out
        return layer

    def route(flat, *a, **kw):
        r = real_route(flat, *a, **kw)
        got_picks.append(np.sort(np.asarray(r.experts), axis=1))
        return r

    try:
        for n, fn in real.items():
            setattr(nemotron_h, n, heard(fn))
        moe.route = route
        logits = nemotron_h.forward_train(params, cfg, runner.inv_freq,
                                          jnp.asarray(tokens)[None], moe_impl=runner.moe_impl)
    finally:
        for n, fn in real.items():
            setattr(nemotron_h, n, fn)
        moe.route = real_route
    got = np.asarray(logits[0, row].astype(f32))
    diff = np.abs(got - want)
    away = lambda a, b: np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)
    layers, e = [], 0
    for l, (k, a, b) in enumerate(zip(sh["kinds"], got_h, ref_h)):
        d = away(a, b)
        far = np.flatnonzero(d > 3 * np.median(d))
        line = {"layer": l, "kind": k, "row": round(float(d[row]), 5),
                "median_row": round(float(np.median(d)), 5),
                "rows_over_3x_median": far[:8].tolist(), "the_farthest": round(float(d.max()), 5)}
        if k == "moe":
            a, b = got_picks[e], ref_picks[e]
            line["rows_whose_picks_differ"] = np.flatnonzero((a != b).any(axis=1))[:8].tolist()
            line["picks_only_program"] = sorted(set(a[row].tolist()) - set(b[row].tolist()))
            line["picks_only_reference"] = sorted(set(b[row].tolist()) - set(a[row].tolist()))
            e += 1
        layers.append(line)
    print(json.dumps({
        "trace_of": worst_row, "seed": seed, "row": int(row), "token": int(tokens[row]),
        "dense_forward_error": round(float(diff.max() / np.std(want)), 4),
        "entries_over_half_the_worst": int(np.sum(diff > 0.5 * diff.max())),
        "worst_entry": int(diff.argmax()), "layers": layers}), flush=True)


def timings(args, runner) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from smg_tpu.ops.pallas.linattn_decode import linattn_decode
    from smg_tpu.ops.pallas.ssm_decode import ssm_decode
    from smg_tpu.ops.ssm import ssd_step

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
    H, P, S, R = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, H, P), jnp.float32)
    Bm, Cm = (jax.random.normal(k, (B, R, S), jnp.float32) for k in jax.random.split(key))
    dt, decay = jnp.full((B, H), 0.1), jnp.full((B, H), 0.9)
    slots = jnp.arange(1, B + 1, dtype=jnp.int32)
    layers = runner.s_pool.shape[0]
    per_head = lambda a: jnp.repeat(a, H // R, axis=1)
    steps = {"xla": lambda pool, l: ssd_step(pool, l, slots, x, dt, decay, Bm, Cm)}
    if runner.state_kernel_fits or args.rehearsal:
        steps["kernel"] = lambda pool, l: ssm_decode(
            pool, l, slots, x, dt, decay, Bm, Cm, interpret=args.rehearsal)
        if not args.rehearsal:
            # the gated delta rule's kernel on the same pool: q = C, k = B, v =
            # dt x, beta 0 (no delta term is read), a key and a query a head
            steps["linattn_kernel"] = lambda pool, l: linattn_decode(
                pool, l, slots, per_head(Cm), per_head(Bm), x * dt[..., None], decay,
                jnp.zeros_like(decay))

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
            res[f"ssm_decode_{name}_ms_{layers}_layers_{B}_lanes"] = timed(once)
        except Exception as e:  # noqa: BLE001 - a form that does not compile is a reading too
            res[f"ssm_decode_{name}_failed"] = f"{type(e).__name__}: {str(e)[:200]}"
    res["ssm_decode_least_ms"] = round(
        B * layers * 2 * H * P * S * 4 / 819e9 * 1e3, 3)

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
    # the cell's launches alone: one cold row (86-94 % of its grouped prefills)
    # at two rungs, and a step's budget as two rows
    table = np.zeros(runner.max_pages_per_seq, np.int32)
    for G, T in ((1, 64), (1, 128), (2, 128)) if args.rehearsal else ((1, 512), (1, 1024), (2, 2048)):
        group = [([0] * T, 0, table)] * G
        plain = (np.zeros(G, np.float32), np.full(G, -1, np.int32), np.ones(G, np.float32),
                 np.zeros(G, np.float32))

        def prefill():  # timed before the loop moves on
            runner.prefill_batched(group, *plain)
            return runner.k_cache

        res[f"grouped_prefill_ms_{G}x{T}"] = timed(prefill)
    if args.rehearsal:
        res = {k: v for k, v in res.items() if "_ms" not in k}
        res["rehearsal"] = True
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    sys.exit(main())
