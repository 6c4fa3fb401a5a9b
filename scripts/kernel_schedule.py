#!/usr/bin/env python3
"""Count the bundles of a decode kernel's loops in the compiler's own
schedule, without a chip (ROADMAP T15):

    python3 scripts/kernel_schedule.py --shapes mimo-v2-flash:64x256,qwen3-1.7b:16x128
        [--pages-per-block N] [--root DIR] [--keep DIR]

For each shape of ``scripts/time_decode_attention.py``'s ``MODELS`` (the paged
kernel, its verify column where the model's name ends in ``:verify``, the
latent kernel where the model has no KV heads) a child process compiles the
kernel for a described ``v5e:2x2`` under ``JAX_PLATFORMS=cpu`` with libtpu
started as ``LIBTPU_INIT_ARGS="--xla_jf_dump_to=DIR --xla_jf_dump_llo_text=true"``,
which writes every kernel's final VLIW schedule
(``DIR/*-smg.attn.decode*-final_bundles.txt``: a line a bundle, ``LB:`` where
a loop's body starts, ``PF:`` where a predicated region falls through, a ``>``
a loop the bundle is nested in).  The parent process (which never loads jax:
the child may abort once the dump is written, which does no harm) prints one
JSON line a shape: the kernel's bundles, and for every loop its depth, its
first bundle, the bundles of its body (nested loops included) and, of those,
the empty ones, the copies started (``dma.hbm_to_vmem``), the waits
(``dma.done.wait``), the bounds checks (``shalt.err``) and the bundles that
feed the MXU.  The grid's loop over
the lanes is depth 1, the loop over a lane's blocks the depth-2 loop that
holds the products.

These are **counts from a CPU compile, not times**: a bundle was about 0.8 ns
in three kernels' timings on a v5e (``PERF.md`` section 6, PR 45), a
predicated-off copy costs less than its bundles, and a loop whose bytes take
longer than its bundles waits for HBM whatever this prints.  ``--root``
compiles another tree's kernel (the parent commit unpacked somewhere) with
this script's shapes.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
_BUNDLE = re.compile(r"^\s*(0x[0-9a-f]+|0)\s+(?:([A-Z]{2}):)?\s*:?\s*(>*)\s*(\{.*)$")
_MXU = re.compile(r"vmat(?:mul|push)")
# an operation of the bundle, not its name in a bounds check's comment
_COPY = re.compile(r"(?:\{|;;)\s+%\d+ = dma\.hbm_to_vmem")
_WAIT = re.compile(r"(?:\{|;;)\s+%\d+ = dma\.done\.wait")
_CHECK = re.compile(r"= shalt\.err")


def child(args) -> int:
    """Compile one shape's kernel for the described chip (the dump is a side
    effect of the compile)."""
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from smg_tpu.ops.pallas import decode_attention as kernels  # the root's, before ...

    sys.path.insert(0, HERE)
    import time_decode_attention as shapes  # ... this script's shapes, whatever the root

    model, _, lanes = args.child.rpartition(":")
    B, mp = (int(x) for x in lanes.split("x"))
    L, P, H, K, D, _, Dv, q_rows = shapes.model_shape(shapes.MODELS[model])
    dev = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    on = SingleDeviceSharding(dev)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=on)
    i = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=on)
    n, ps, kw = shapes.N, shapes.PS, {"pages_per_block": args.pages_per_block}
    tail = (i(), i(B, mp), i(B))
    if K == 0:  # the latent cache: one buffer of ``D`` lanes an entry
        vl = shapes.LATENT_VALUE_LANES[D]
        fn = lambda q, c, side, n_extra, l, t, e: kernels.latent_attention_decode_cached(
            q, c, side, n_extra, l, t, e, latent=vl, scale=D ** -0.5, **kw)
        a = (s(B, H, D), s(L, P, ps, D), s(B, n, D), i(), *tail)
    else:
        caches = (s(L, P, ps, K * D), s(L, P, ps, K * Dv), s(B, n, K * D), s(B, n, K * Dv))
        if q_rows > 1:
            fn = lambda q, kc, vc, hk, hv, held, l, t, e: kernels.paged_attention_verify_cached(
                q, kc, vc, hk, hv, held, l, t, e, D ** -0.5, **kw)
            a = (s(B, q_rows, H, D), *caches, i(B), *tail)
        else:
            fn = lambda q, kc, vc, hk, hv, n_extra, l, t, e: kernels.paged_attention_decode_cached(
                q, kc, vc, hk, hv, n_extra, l, t, e, D ** -0.5, **kw)
            a = (s(B, H, D), *caches, i(), *tail)
    # smglint: disable-next=RETRACE one compile a process, for its dump
    jax.jit(fn).lower(*a).compile()
    return 0


def loops_of(path: str) -> dict:
    """The schedule file reduced: its bundles, and a row a loop."""
    bundles = []  # (mark, depth, text)
    with open(path) as f:
        for line in f:
            m = _BUNDLE.match(line)
            if not m:
                continue
            mark, depth, text = m.group(2), len(m.group(3)), m.group(4)
            if text.startswith("{}") and bundles:
                depth = bundles[-1][1]  # an empty bundle carries no marks: it sits behind a branch
            bundles.append((mark, depth, text))
    loops = []
    for at, (mark, depth, _) in enumerate(bundles):
        if mark != "LB":
            continue
        end = at
        while end < len(bundles) and bundles[end][1] >= depth:
            end += 1
        body = bundles[at:end]
        loops.append({
            "depth": depth, "first_bundle": at, "bundles": len(body),
            "empty": sum(text.startswith("{}") for _, _, text in body),
            "copies_started": sum(len(_COPY.findall(text)) for _, _, text in body),
            "waits": sum(len(_WAIT.findall(text)) for _, _, text in body),
            "bounds_checks": sum(len(_CHECK.findall(text)) for _, _, text in body),
            "mxu_bundles": sum(bool(_MXU.search(text)) for _, _, text in body),
            "inner_loops": sum(m == "LB" and d > depth for m, d, _ in body),
        })
    return {"bundles": len(bundles), "loops": loops}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mimo-v2-flash:64x256",
                    help="comma-separated model:BxMP of time_decode_attention.py's MODELS")
    ap.add_argument("--pages-per-block", type=int, default=None)
    ap.add_argument("--root", default=os.path.join(HERE, ".."),
                    help="the tree whose smg_tpu is compiled (default: this one)")
    ap.add_argument("--keep", help="keep the compiler's dumps under this directory")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child(args)
    for shape in args.shapes.split(","):
        with tempfile.TemporaryDirectory() as tmp:
            out = os.path.join(args.keep, shape.replace(":", "_")) if args.keep else tmp
            os.makedirs(out, exist_ok=True)
            env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
                       LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={out} --xla_jf_dump_llo_text=true")
            cmd = [sys.executable, os.path.abspath(__file__), "--child", shape, "--root", args.root]
            if args.pages_per_block:
                cmd += ["--pages-per-block", str(args.pages_per_block)]
            run = subprocess.run(cmd, env=env, capture_output=True, text=True)
            found = [p for p in glob.glob(os.path.join(out, "*smg.attn.decode*-final_bundles.txt"))
                     if "schedule-analysis" not in p]
            if not found:
                print(f"kernel_schedule: no schedule for {shape} (exit {run.returncode}):\n"
                      f"{run.stderr[-2000:]}", file=sys.stderr)
                return 1
            row = {"shape": shape, "pages_per_block": args.pages_per_block,
                   "root": os.path.abspath(args.root), "source": "cpu_compile_counts",
                   **loops_of(found[0])}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
