#!/usr/bin/env python3
"""Time one cold row's grouped prefill, the launch whole, at rungs of the
prefill ladder, on the chip this process holds (ROADMAP S7(2)):

    python3 scripts/time_prefill_rungs.py [--configs qwen3-1.7b,olmo-hybrid-7b]
        [--rungs 768,1024,1536,2048,3072,4096] [--out chiprun_out/rungs.json]
        [--rows 1,2] [--both-attentions] [--logits-from 512] [--rehearsal]

For each configuration of ``benchmark/configs`` it builds the runner ``serve``
would (random weights, the cell's scheduler arguments, the ladder grown by
``--rungs``) and launches ``prefill_batched`` with one full row of each rung:
forward, sampling and the fetch of the first token, as a step that admits
one prompt pays it.  Prints one JSON line a configuration: milliseconds a
launch (median of ``REPS`` after the compiling call) and the seconds the
first call took, compilation included, which is what a rung costs a cell's
set-up.  A launch's time is set by its padded length, so the gap between two
rungs is what a row pays for landing above the lower one.  ``--rows`` times
groups of that many full rows (a group pads to the octave, so only the
octaves among the rungs are timed for it); ``--both-attentions`` times every
launch twice, the cold grouped prefill's attention in XLA's form and in the
online-softmax kernel whatever the runner's rule would choose (its answer is
recorded beside them: what the kernel gives the launch whole, and whether the
rule's constant stands in the whole program), and from ``--logits-from``
tokens on holds the logits of the two programs against each other on one
ragged group a shape: the largest difference over the real rows, in units of
the logits' deviation.  Refuses to run
without a TPU: a CPU time is not a device time (``--rehearsal`` walks the
same code at the configurations' rehearsal widths; its times mean nothing).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

REPS = 7
# keys of a benchmark configuration that are the benchmark's own, not the model's
OWN = {"assumed", "deployment", "chips", "serve_args", "rehearsal", "status",
       "architecture", "reduced", "published"}


def logits_apart(runner, G: int, T: int, impl: str) -> dict:
    """The cold grouped forward of ``G`` ragged rows of random tokens under
    ``impl`` and under XLA's attention, on the runner's own pages: how far
    the two programs' logits lie apart over the real rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, mp = runner.model_cfg, runner.max_pages_per_seq
    rng = np.random.default_rng(T + G)
    t_reals = np.asarray([T, T // 2 + 2, 3 * T // 4, 0][:G] if G > 1 else [T - 3], np.int32)
    tokens = jnp.asarray(rng.integers(2, cfg.vocab_size, (G, T)), jnp.int32)
    tables = jnp.asarray(1 + np.arange(G * mp).reshape(G, mp), jnp.int32)
    got = {}
    for form in (impl, "xla"):
        fn = jax.jit(lambda params, kc, vc, form=form: runner.module.forward_prefill_batched(
            params, cfg, runner.inv_freq, tokens, jnp.zeros(G, jnp.int32),
            jnp.asarray(t_reals), kc, vc, tables, no_ctx=True, attn_impl=form),
            donate_argnums=(1, 2))
        logits, runner.k_cache, runner.v_cache = fn(runner.params, runner.k_cache,
                                                    runner.v_cache)
        got[form] = np.asarray(logits, np.float32)[t_reals > 0]
    apart = np.abs(got[impl] - got["xla"])
    # the tier-1 parity tests' tolerance for bfloat16: rtol = atol = 2e-2
    allowed = 2e-2 + 2e-2 * np.abs(got["xla"])
    return {"t_reals": t_reals.tolist(), "max_abs": float(apart.max()),
            "max_over_allowed": float((apart / allowed).max()),
            "logits_std": float(got["xla"].std()),
            "max_over_std": float(apart.max() / got["xla"].std()),
            "rel_err": float(np.linalg.norm(got[impl] - got["xla"])
                             / np.linalg.norm(got["xla"])),
            "same_argmax": bool((got[impl].argmax(-1) == got["xla"].argmax(-1)).all())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="qwen3-1.7b,olmo-hybrid-7b")
    ap.add_argument("--rungs", default="1024,1536,2048,3072,4096")
    ap.add_argument("--out")
    ap.add_argument("--rows", default="1", help="comma-separated group sizes")
    ap.add_argument("--both-attentions", action="store_true")
    ap.add_argument("--logits-from", type=int, default=0,
                    help="with --both-attentions: compare logits at rungs of this many tokens on")
    ap.add_argument("--rehearsal", action="store_true")
    opts = ap.parse_args()
    if opts.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.config import ModelConfig

    device = jax.devices()[0]
    if device.platform != "tpu" and not opts.rehearsal:
        print("time_prefill_rungs: no TPU here, and a CPU time is not a device time",
              file=sys.stderr)
        return 3
    rungs = sorted(int(r) for r in opts.rungs.split(","))
    if opts.rehearsal:
        rungs, opts.logits_from = [r // 16 for r in rungs], opts.logits_from // 16
    lines = []
    for name in opts.configs.split(","):
        with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as fh:
            conf = json.load(fh)
        if opts.rehearsal:
            conf = {**conf, **conf["rehearsal"]}
        dtype = "float32" if opts.rehearsal else "bfloat16"
        model = ModelConfig.from_hf_config(
            {k: v for k, v in conf.items() if k not in OWN}, dtype=dtype)
        if opts.rehearsal and opts.both_attentions and model.latent_cache:
            # the kernel slices whole 128-lane tiles: two heads of the published widths
            model = dataclasses.replace(model, num_heads=2, qk_nope_head_dim=128,
                                        qk_rope_head_dim=64, v_head_dim=128)
        ladder = tuple(sorted(set(SchedulerConfig.prefill_token_buckets) | set(rungs)))
        budget = max(rungs) if opts.rehearsal else max(ladder)
        engine = Engine(EngineConfig(
            model=model, dtype=dtype,
            cache=CacheConfig(dtype=dtype, auto_size=not opts.rehearsal, num_pages=256),
            scheduler=SchedulerConfig(
                decode_horizon=8, max_seq_len=2 * budget, max_prefill_tokens=budget,
                prefill_token_buckets=tuple(rungs) if opts.rehearsal else ladder)))
        runner = engine.runner
        table = np.zeros(runner.max_pages_per_seq, np.int32)
        out = {"config": name, "device": device.device_kind, "runner": type(runner).__name__,
               "rungs": {}}
        rule = runner._grouped_prefill_impl_for
        kernel = "pallas_interpret" if opts.rehearsal else "pallas"
        forms = {"rule": rule}
        if opts.both_attentions:
            forms = {"xla": lambda G, T, no_ctx: "xla",
                     "kernel": lambda G, T, no_ctx: kernel if no_ctx else "xla"}
        for G in (int(g) for g in opts.rows.split(",")):
            samp = (np.zeros(G, np.float32), np.full(G, -1, np.int32), np.ones(G, np.float32),
                    np.zeros(G, np.float32))
            kw = ({"state_slots": np.arange(G, dtype=np.int32)} if hasattr(runner, "s_pool")
                  else {})
            for T in rungs:
                cell, shape = {}, f"{G}x{T}" if G > 1 else str(T)
                logits = opts.both_attentions and not kw and 0 < opts.logits_from <= T
                if G > 1 and T & (T - 1):  # a group never launches at a half-octave rung
                    if logits:
                        out["rungs"][shape] = {"logits": logits_apart(runner, G, T, kernel)}
                    continue
                for form, answer in forms.items():
                    runner._grouped_prefill_impl_for = answer
                    runner.invalidate_compiled("prefill_batched")
                    rows = [([0] * T, 0, table)] * G
                    t0 = time.perf_counter()
                    runner.prefill_batched(rows, *samp, **kw)
                    first = time.perf_counter() - t0
                    took = []
                    for _ in range(REPS):
                        t0 = time.perf_counter()
                        runner.prefill_batched(rows, *samp, **kw)
                        took.append((time.perf_counter() - t0) * 1e3)
                    cell[form] = {"attention": answer(G, T, True),
                                  "launch_ms": statistics.median(took),
                                  "launch_ms_min": min(took), "first_call_s": first}
                if opts.both_attentions:
                    cell["rule"] = rule(G, T, True)
                    if logits:
                        cell["logits"] = logits_apart(runner, G, T, kernel)
                out["rungs"][shape] = cell if opts.both_attentions else cell["rule"]
        runner._grouped_prefill_impl_for = rule
        out["launches"] = dict(runner.prefill_padding["launches"])
        print(json.dumps(out), flush=True)
        lines.append(out)
        del engine, runner
    if opts.out:
        os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
        with open(opts.out, "w") as fh:
            json.dump(lines, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
