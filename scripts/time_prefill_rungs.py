#!/usr/bin/env python3
"""Time one cold row's grouped prefill, the launch whole, at rungs of the
prefill ladder, on the chip this process holds (ROADMAP S7(2)):

    python3 scripts/time_prefill_rungs.py [--configs qwen3-1.7b,olmo-hybrid-7b]
        [--rungs 768,1024,1536,2048,3072,4096] [--out chiprun_out/rungs.json]
        [--rehearsal]

For each configuration of ``benchmark/configs`` it builds the runner ``serve``
would (random weights, the cell's scheduler arguments, the ladder grown by
``--rungs``) and launches ``prefill_batched`` with one full row of each rung:
forward, sampling and the fetch of the first token, as a step that admits
one prompt pays it.  Prints one JSON line a configuration: milliseconds a
launch (median of ``REPS`` after the compiling call) and the seconds the
first call took, compilation included, which is what a rung costs a cell's
set-up.  A launch's time is set by its padded length, so the gap between two
rungs is what a row pays for landing above the lower one.  Refuses to run
without a TPU: a CPU time is not a device time (``--rehearsal`` walks the
same code at the configurations' rehearsal widths; its times mean nothing).
"""

from __future__ import annotations

import argparse
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="qwen3-1.7b,olmo-hybrid-7b")
    ap.add_argument("--rungs", default="1024,1536,2048,3072,4096")
    ap.add_argument("--out")
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
        rungs = [r // 16 for r in rungs]
    lines = []
    for name in opts.configs.split(","):
        with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as fh:
            conf = json.load(fh)
        if opts.rehearsal:
            conf = {**conf, **conf["rehearsal"]}
        dtype = "float32" if opts.rehearsal else "bfloat16"
        model = ModelConfig.from_hf_config(
            {k: v for k, v in conf.items() if k not in OWN}, dtype=dtype)
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
        samp = (np.zeros(1, np.float32), np.full(1, -1, np.int32), np.ones(1, np.float32),
                np.zeros(1, np.float32))
        kw = {"state_slots": np.zeros(1, np.int32)} if hasattr(runner, "s_pool") else {}
        out = {"config": name, "device": device.device_kind, "runner": type(runner).__name__,
               "rungs": {}}
        for T in rungs:
            row = [([0] * T, 0, table)]
            t0 = time.perf_counter()
            runner.prefill_batched(row, *samp, **kw)
            first = time.perf_counter() - t0
            took = []
            for _ in range(REPS):
                t0 = time.perf_counter()
                runner.prefill_batched(row, *samp, **kw)
                took.append((time.perf_counter() - t0) * 1e3)
            out["rungs"][str(T)] = {"launch_ms": statistics.median(took),
                                    "launch_ms_min": min(took), "first_call_s": first}
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
