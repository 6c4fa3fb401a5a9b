#!/usr/bin/env python
"""Set the program's step account beside the device's own trace.

Runs one ``--trace 2`` run of the benchmark (every argument goes to
``benchmark/run.py`` unchanged, and its result line is still the last line
of stdout) and, from the contexts the harness hands its per-layer readers,
writes ``step_account.json`` beside the run's ``run.json``:

- for the traced stretch: the seconds the host knew the chip had nothing
  queued (the step records' ``starved_s`` and its parts, over the records
  stamped in ``trace_window``) beside the device's idle seconds
  (``device.idle_share`` x the stretch) and ``breakdown.idle_gaps``; how
  ``smg.step.admit`` splits into planning, pack and dispatch, ``consume``
  into fetch and acceptance, ``launch`` into state build and dispatch, from
  the step records and again from the trace's own ``smg.*`` spans;
- for the whole window: the same sums over the window's records.

It edits nothing of the harness: it wraps ``run.per_layer`` to keep the
contexts.  On a program without the account (schema 8 and older) the
account's numbers are null.  Wants a chip, as ``run.py`` does; with
``--rehearsal`` it walks the same path at toy size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

KEYS = ("step_s", "gap_s", "consume_s", "fetch_wait_s", "admit_s", "admit_pack_s",
        "admit_dispatch_s", "launch_s", "dispatch_s", "starved_s", "starved_consume_s",
        "starved_admit_s", "starved_launch_s")


def account(steps: list, window: tuple) -> dict | None:
    """Sums of the account's keys over the records stamped in ``window``."""
    recs = [s for s in steps if window[0] <= s["t"] <= window[1]]
    if not recs or any(k not in r for r in recs for k in KEYS):
        return None
    out = {k: sum(r[k] for r in recs) for k in KEYS}
    out.update(
        records=len(recs), seconds=window[1] - window[0],
        admit_planning_s=out["admit_s"] - out["admit_pack_s"] - out["admit_dispatch_s"],
        consume_acceptance_s=out["consume_s"] - out["fetch_wait_s"],
        launch_dispatch_s=out["dispatch_s"] - out["admit_dispatch_s"],
        starved_other_s=out["starved_s"] - out["starved_consume_s"]
        - out["starved_admit_s"] - out["starved_launch_s"])
    out["launch_build_s"] = out["launch_s"] - out["launch_dispatch_s"]
    return out


def main() -> int:
    import run
    import trace_reduce

    kept: dict = {}
    per_layer = run.per_layer

    def keeping(bench, cell, *ctxs):
        kept["ctxs"] = ctxs
        return per_layer(bench, cell, *ctxs)

    run.per_layer = keeping
    rc = run.main()
    if rc or len(kept.get("ctxs", ())) < 2:
        return rc  # no traced run: nothing to set beside anything
    ctx, tctx = kept["ctxs"]
    trace = tctx["trace"]
    busy = trace_reduce.busy(trace)
    idle = trace_reduce.idle_share(trace)
    spans: dict = {}
    for name, _start, dur in trace["host"]:
        if name.startswith("smg."):
            spans[name] = spans.get(name, 0.0) + dur
    report = {
        "cell": ctx["cell"],
        "traced": {
            "device": {"window_s": busy["window_s"], "idle_share": idle,
                       "idle_s": idle / 100.0 * busy["window_s"]},
            "idle_gaps": trace_reduce.idle_gaps(trace, span_prefix="smg."),
            "account": account(tctx["steps"], tctx["trace_window"]),
            "span_seconds": dict(sorted(spans.items())),
        },
        "window": {"account": account(ctx["steps"], ctx["window"])},
    }
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(ROOT, "bench_out"))
    ap.add_argument("--seed", type=int, default=0)
    at, _rest = ap.parse_known_args()
    path = os.path.join(at.out, ctx["cell"], f"seed{at.seed}-trace2", "step_account.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print("step_account: " + json.dumps(report), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
