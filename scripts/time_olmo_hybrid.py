#!/usr/bin/env python3
"""Time the programs of the ``olmo-hybrid-7b`` configuration standalone, on the
chip this process holds:

    python3 scripts/time_olmo_hybrid.py [--out chiprun_out/olmo.json]

Builds the runner ``serve`` would build for ``benchmark/configs/
olmo-hybrid-7b.json`` (random weights, auto-sized caches) and times, after one
warm-up each, on the host clock round ``block_until_ready``:

- one 4,096-token chunk on the grouped path (``prefill_batched``, one member;
  and one row of 1,024 and of 2,048 tokens there), on the solo path
  (``prefill``) and on the continuing path (``prefill_extend``), which is what
  a prompt cut by a step's budget takes (PERF.md 7.3h);
- a decode frame of 8 columns at 16 lanes behind 128- and 256-page tables;
- the linear-attention decode step alone (the kernel ``smg.linattn.decode``
  and its XLA form) over the 12 layers of the state pool at 16 lanes;
- the chunked prefill form of the recurrence alone (``gated_delta_chunked``),
  12 calls chained, at one row of 1,024 and of 2,048 tokens.

Prints one JSON line.  Refuses to run without a TPU: a CPU time is not a
device time.  ``--rehearsal`` runs the same code at the configuration's
rehearsal widths on the CPU and prints no times.

``--check N`` instead runs the benchmark's own comparison
(``benchmark/reference.check_engine``: serving-path logits against the
token-by-token float32 reference, the wrong-page control and the two state
controls) for N seeds and prints each seed's worst error and its controls,
with the weights as ``init_params`` draws them, or once for every pair of
``--lin-norm-scale`` and ``--full-norm-scale`` given (the two kinds of mixer's
post-norm weights multiplied by them: how loudly the layers that read the
pages speak beside those that keep state decides how far one wrong page moves
the logits, and how far the linear layers' rounding does).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

REPS = 3


def chunked_form_ms(form, layers: int, G: int, T: int, H: int, dk: int, dv: int,
                    per_channel: bool) -> list[float]:
    """Milliseconds of ``layers`` calls of a chunked prefill form
    (``gated_delta_chunked``, or ``kda_chunked`` with ``per_channel``) chained
    in one program: a call's keys, queries and decays take a little of the
    call before's output, so that nothing of a call can leave the loop."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (jax.random.normal(ks[i], (G, T, H, dk), jnp.float32) for i in (0, 1))
    v = jax.random.normal(ks[2], (G, T, H, dv), jnp.float32)
    g = -jax.random.uniform(ks[3], (G, T, H, dk) if per_channel else (G, T, H),
                            jnp.float32, 0.02, 1.5)
    beta = jax.random.uniform(ks[4], (G, T, H), jnp.float32)
    lanes = min(dk, dv)

    @jax.jit
    def run(q, k, v, g, beta):
        def layer(_, c):
            S, o = c
            mix = jnp.pad(0.01 * o[..., :lanes], [(0, 0)] * 3 + [(0, dk - lanes)])
            gl = g * (1 + (mix if per_channel else mix[..., 0]))
            o, S = form(l2(q + mix), l2(k + mix), v, gl, beta, S)
            return S, o
        return jax.lax.fori_loop(0, layers, layer, (jnp.zeros((G, H, dk, dv), jnp.float32),
                                                    jnp.zeros_like(v)))

    out = []
    for _ in range(REPS + 1):
        t = time.perf_counter()
        jax.block_until_ready(run(q, k, v, g, beta))
        out.append((time.perf_counter() - t) * 1e3)
    return [round(x, 3) for x in out[1:]]  # the first run compiles


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--check", type=int, default=0, metavar="N")
    ap.add_argument("--full-norm-scale", type=float, action="append", default=[])
    ap.add_argument("--lin-norm-scale", type=float, action="append", default=[])
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.recurrent_runner import RecurrentModelRunner
    from smg_tpu.models.config import ModelConfig
    from smg_tpu.ops.linear_attention import gated_delta_chunked, gated_delta_step
    from smg_tpu.ops.pallas.linattn_decode import linattn_decode

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"time_olmo_hybrid: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    with open(os.path.join(ROOT, "benchmark", "configs", "olmo-hybrid-7b.json")) as f:
        conf = json.load(f)
    if args.rehearsal:
        conf = {**conf, **conf["rehearsal"]}
    own = {"assumed", "deployment", "chips", "serve_args", "rehearsal", "status",
           "architecture", "reduced", "published"}
    dtype = "float32" if args.rehearsal else "bfloat16"
    model = ModelConfig.from_hf_config({k: v for k, v in conf.items() if k not in own}, dtype=dtype)
    chunk = 256 if args.rehearsal else 4096
    config = EngineConfig(
        model=model, dtype=dtype,
        cache=CacheConfig(dtype=dtype, auto_size=not args.rehearsal, num_pages=1024),
        scheduler=SchedulerConfig(decode_horizon=8, max_seq_len=1024 if args.rehearsal else 8192,
                                  max_prefill_tokens=chunk))
    runner = RecurrentModelRunner(config)
    if args.check:
        return check(args, runner)
    mp = runner.max_pages_per_seq
    table = np.zeros(mp, np.int32)
    ids = [0] * chunk
    one = (np.zeros(1, np.float32), np.full(1, -1, np.int32), np.ones(1, np.float32),
           np.zeros(1, np.float32))

    def timed(fn):
        out = []
        for _ in range(REPS + 1):
            t = time.perf_counter()
            fn()
            jax.block_until_ready((runner.k_cache, runner.s_pool))
            out.append((time.perf_counter() - t) * 1e3)
        return [round(x, 2) for x in out[1:]]  # the first run compiles

    res = {"device": dev.device_kind, "pages": runner.spec.num_pages,
           "state": runner.state_info(), "chunk_tokens": chunk}
    res["grouped_ms"] = timed(lambda: runner.prefill_batched([(ids, 0, table)], *one))
    for T in () if args.rehearsal else (1024, 2048):
        res[f"grouped_ms_1x{T}"] = timed(
            lambda T=T: runner.prefill_batched([(ids[:T], 0, table)], *one))
    res["solo_ms"] = timed(lambda: runner.prefill(ids, 0, table, 0.0, -1, 1.0, 0.0))
    res["continuing_ms"] = timed(lambda: runner.prefill_extend(ids, 0, table))
    B, N, ps = 16, 8, config.cache.page_size
    zeros, ones = np.zeros(B, np.float32), np.ones(B, np.float32)
    for w in (16,) if args.rehearsal else (128, 256):
        def frame(w=w):
            toks, _l, _s = runner.decode_multi_async(
                np.zeros(B, np.int32), np.full(B, w * ps - N, np.int32),
                np.zeros((B, w), np.int32), zeros, np.full(B, -1, np.int32), ones, zeros, N,
                max_steps=N, stop_state=(np.full((B, 1), -1, np.int32),
                                         np.full(B, np.int32(2**30)), np.ones(B, bool)),
                state_slots=np.arange(1, B + 1, dtype=np.int32))
            jax.block_until_ready(toks)
        res[f"decode_frame_ms_mp{w}"] = timed(frame)
    # the decode step of the linear layers alone, every layer in turn
    cfg = model
    H, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key = jax.random.PRNGKey(0)
    q, k = (jax.random.normal(kk, (B, H, dk), jnp.float32) for kk in jax.random.split(key))
    v = jax.random.normal(key, (B, H, dv), jnp.float32)
    alpha, beta = jnp.full((B, H), 0.9), jnp.ones((B, H))
    slots = jnp.arange(1, B + 1, dtype=jnp.int32)
    layers = runner.s_pool.shape[0]

    def every_layer(step):
        def run(pool):
            def body(l, c):
                pool, acc = c
                o, pool = step(pool, l, slots, q, k, v, alpha, beta)
                return pool, acc + o
            return jax.lax.fori_loop(0, layers, body, (pool, jnp.zeros((B, H, dv))))
        return jax.jit(run, donate_argnums=0)

    forms = {"xla": gated_delta_step}
    if runner.linattn_kernel_fits:
        forms["pallas"] = (lambda *a: linattn_decode(*a, interpret=True)) if args.rehearsal \
            else linattn_decode
    for name, step in forms.items():
        fn = every_layer(step)

        def once(fn=fn):
            runner.s_pool, acc = fn(runner.s_pool)
            jax.block_until_ready(acc)
        res[f"linattn_decode_{name}_ms_{layers}_layers"] = timed(once)
    state_bytes = B * layers * 2 * H * dk * dv * 4
    res["linattn_decode_least_ms"] = round(state_bytes / 819e9 * 1e3, 3)
    for T in (64,) if args.rehearsal else (1024, 2048):
        res[f"gated_delta_chunked_ms_{layers}_layers_1x{T}"] = chunked_form_ms(
            gated_delta_chunked, layers, 1, T, H, dk, dv, per_channel=False)
    if args.rehearsal:
        res = {k: v for k, v in res.items() if not k.endswith("_ms") and "_ms_" not in k}
        res["rehearsal"] = True
    line = json.dumps(res)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


def check(args, runner) -> int:
    import types


    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import catalog
    import reference

    cell = catalog.Cell(catalog.load_benchmark(), "olmo-hybrid-7b.gen", rehearsal=args.rehearsal)
    engine = types.SimpleNamespace(runner=runner)
    full = runner.params["periods"]["full"]["attn_post_norm"]
    lin = runner.params["periods"]["lin"]["attn_post_norm"]
    pairs = list(zip(args.lin_norm_scale or [1.0], args.full_norm_scale or [1.0]))
    for lin_scale, full_scale in pairs:
        runner.params["periods"]["lin"]["attn_post_norm"] = (lin * lin_scale).astype(lin.dtype)
        runner.params["periods"]["full"]["attn_post_norm"] = (full * full_scale).astype(full.dtype)
        scale = [lin_scale, full_scale]
        for seed in range(args.check):
            t = time.perf_counter()
            c = reference.check_engine(engine, cell, 2900000200 + seed, args.rehearsal)
            errs = [e for per in c["errors"].values() for e in per.values()]
            print(json.dumps({"lin_full_norm_scale": scale, "seed": seed, "ok": c["ok"],
                              "worst": round(c["worst"], 4), "least": round(min(errs), 4),
                              "rows": [round(e, 2) for e in c["errors"]["xla"].values()],
                              "control": {k: round(v, 3) for k, v in c["control_errors"].items()},
                              "seconds": round(time.perf_counter() - t, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
