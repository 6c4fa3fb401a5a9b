#!/usr/bin/env python
"""Where the device's time went inside the decode and the prefill launches of
a traced run, by named scope.

    python scripts/scope_split.py <run directory> [--heads 20] [--scope smg.kda.layer] [--json]

``<run directory>`` is what one ``benchmark/run.py --trace 2`` run leaves
(``bench_out/<cell>/seed<n>-trace2``): ``trace_cut.json``, the quarter second
of the trace the harness keeps, and the program's scope map, which is read
from ``run.json`` (``loads_after.programs.scopes``) where the harness put it
there and else from ``trace.scopes.json``, which the engine writes beside the
trace's directory when the profile ends.  For each family (``decode``:
``jit_multi*``; ``prefill``: ``jit_step*``) it prints the launches' device
seconds, the leaf operations' seconds inside them and what is left (gaps
inside a launch), the seconds of each part (mixer, ffn, of which routing,
head, frame, other, unscoped) and of each scope with what came to it from
unscoped operations that only it reads (``~``), and the longest unscoped
heads (``--scope``: the longest heads under that scope instead).  The
arithmetic is ``benchmark/layer_metrics/_scope_time.py``'s, which the nine
``runner.*_time_share`` metrics read too: nothing is summed here that is not
summed there.  CPU, a second.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "layer_metrics"))

PARTS = ("mixer", "ffn", "routing", "head", "frame", "other", "unscoped")


def load(run_dir: str) -> tuple:
    """The kept stretch of the trace and the scope map of one run."""
    with open(os.path.join(run_dir, "trace_cut.json")) as f:
        trace = json.load(f)
    scopes = None
    run_json = os.path.join(run_dir, "run.json")
    if os.path.isfile(run_json):
        with open(run_json) as f:
            scopes = (json.load(f).get("loads_after", {}).get("programs") or {}).get("scopes")
    beside = os.path.join(run_dir, "trace.scopes.json")
    if not scopes and os.path.isfile(beside):
        with open(beside) as f:
            scopes = json.load(f)
    return trace, scopes


def table(trace: dict, scopes: dict, heads: int, scope: str = "") -> dict:
    """``{family: split with its parts and the longest heads under ``scope``}``."""
    import _scope_time as st

    out = {}
    for family in st.FAMILY:
        sp = st.split(trace, scopes, family, scope)
        if sp is None:
            continue
        sp["parts"] = {p: st.seconds_in(sp, p) for p in PARTS}
        sp["gaps_s"] = sp["family_s"] - sp["leaf_s"]
        sp["heads"] = sorted(sp["heads"].items(), key=lambda kv: -kv[1])[:heads]
        out[family] = sp
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("run_dir")
    ap.add_argument("--heads", type=int, default=20, help="heads to list")
    ap.add_argument("--scope", default="", help="list the heads under this scope, not the unscoped")
    ap.add_argument("--json", action="store_true", help="one JSON object instead of the table")
    args = ap.parse_args()
    trace, scopes = load(args.run_dir)
    if not scopes:
        print("scope_split: the run has no scope map (a program before PR 53, or no "
              "launch inside the profile)", file=sys.stderr)
        return 1
    out = table(trace, scopes, args.heads, args.scope)
    if args.json:
        print(json.dumps(out))
        return 0
    for family, sp in out.items():
        whole = sp["family_s"]
        pct = lambda s: f"{1e3 * s:9.3f} ms {100 * s / whole:6.2f} %"
        print(f"== {family}: launches {pct(whole)}")
        print(f"   leaf operations        {pct(sp['leaf_s'])}")
        print(f"   gaps inside a launch   {pct(sp['gaps_s'])}")
        print(f"   in launches no map resolves {pct(sp['unresolved_s'])}")
        print(f"   in programs of a stale executable {pct(sp['stale_s'])}")
        for p in PARTS:
            print(f"   {p:<22} {pct(sp['parts'][p])}")
        print("   -- by scope (~: of which from unscoped operations only it reads)")
        for scope, s in sorted(sp["scopes"].items(), key=lambda kv: -kv[1]):
            came = sp["adopted"].get(scope, 0.0)
            print(f"   {scope or '(no scope)':<26} {pct(s)}" + (f"   ~ {pct(came)}" if came else ""))
        print(f"   -- the {len(sp['heads'])} longest heads under {args.scope or 'no scope'}")
        for h, s in sp["heads"]:
            print(f"   {pct(s)}  {h}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
