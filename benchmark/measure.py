#!/usr/bin/env python3
"""Tools for whoever defines or re-measures a cell (not run by the driver).

    python3 benchmark/measure.py sweep --workload W --key rate_per_s --values 6,9,12
    python3 benchmark/measure.py sets  --workload W --seeds 11,12,13,14,15,16 --sets 2

``sweep`` runs the cell once per value of one traffic number (``run.py
--set``) and prints the table that finds the knee: the highest rate at which
the backlog (requests due and unfinished) is no larger at the window's end
than at its middle.  ``sets`` runs the cell as the contract's measurement
does: ``--sets`` sets of one run per seed, the same seeds in each, and prints
every end-to-end metric's median and spread (interquartile distance over the
median, ``statistics.quantiles(n=4)``) per set, with the distance from the
least to the most beside it, which a far-off run cannot hide in.  It stops at
the first run that fails or is not correct.  Each run is a process of its own;
this parent never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


REHEARSAL: list[str] = []


def run_once(workload: str, seed: int, seconds: float | None, trace: int, out: str,
             extra: list[str]) -> dict | None:
    extra = extra + REHEARSAL
    argv = [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
            "--seed", str(seed), "--trace", str(trace), "--out", out, *extra]
    if seconds is not None:
        argv += ["--seconds", str(seconds)]
    t = time.monotonic()
    proc = subprocess.run(argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    wall = time.monotonic() - t
    lines = proc.stdout.strip().splitlines()
    with open(os.path.join(out, "measure.log"), "a") as f:
        f.write(f"\n==== {' '.join(argv)} -> rc {proc.returncode}, {wall:.1f}s\n")
        f.write("\n".join(l for l in proc.stderr.splitlines()
                          if l.startswith("bench") or "Error" in l or "FAILED" in l
                          or "Traceback" in l or "kv cache" in l)[-6000:])
        f.write("\n" + "\n".join(lines[-2:]) + "\n")
    if proc.returncode != 0 or not lines:
        print(f"measure: run failed (rc {proc.returncode}): {proc.stderr[-1500:]}", flush=True)
        return None
    detail = next((json.loads(l[len("bench: detail "):]) for l in lines
                   if l.startswith("bench: detail ")), {})
    return {"line": json.loads(lines[-1]), "detail": detail, "wall_s": wall}


def spread(values: list) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def sweep(a) -> int:
    rows = []
    for v in a.values.split(","):
        r = run_once(a.workload, a.seed, a.seconds, 0, a.out, ["--set", f"{a.key}={v}"])
        if r is None:
            continue
        d, m = r["detail"], r["line"]["metrics"]
        rows.append({a.key: float(v), "attempted": r["line"]["attempted"],
                     "failed": r["line"]["failed"], "correct": r["line"]["correct"],
                     "backlog_mid": d.get("backlog_mid"), "backlog_end": d.get("backlog_end"),
                     "ttft_p50_ms": d.get("ttft_p50_ms"), "tpot_p50_ms": d.get("tpot_p50_ms"),
                     **{k: x["value"] for k, x in m.items()}, "wall_s": r["wall_s"]})
        print("measure: sweep " + json.dumps(rows[-1]), flush=True)
    with open(os.path.join(a.out, f"sweep-{a.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


def sets(a) -> int:
    seeds = [int(s) for s in a.seeds.split(",")]
    table: dict = {}
    for k in range(a.sets):
        runs = []
        for seed in seeds:
            r = run_once(a.workload, seed, a.seconds, 0, a.out, [])
            if r is None or not r["line"]["correct"]:
                # a cell that fails once is not ready to be measured: stop
                # here rather than spend the chip on the rest
                print(f"measure: set {k} seed {seed} failed or was not correct: "
                      + json.dumps(r["detail"] if r else None)[:3000], flush=True)
                return 1
            runs.append(r)
            d = r["detail"]
            print(f"measure: set {k} seed {seed} wall {r['wall_s']:.0f}s "
                  + json.dumps({**{n: x["value"] for n, x in r["line"]["metrics"].items()},
                                "attempted": r["line"]["attempted"],
                                "memory_peak_bytes": r["line"]["device"]["memory_peak_bytes"],
                                **{n: d.get(n) for n in (
                                    "e2e", "ttft_p50_ms", "tpot_p50_ms", "live_kv_tokens_peak",
                                    "cached_tokens", "prompt_tokens", "setup", "counters",
                                    "attention", "programs_warmed")}}),
                  flush=True)
        for name in (runs[0]["line"]["metrics"] if runs else {}):
            vals = [r["line"]["metrics"][name]["value"] for r in runs]
            table.setdefault(name, []).append(
                {"set": k, "n": len(vals), "median": statistics.median(vals),
                 "spread": spread(vals), "range": (max(vals) - min(vals)) / statistics.median(vals),
                 "values": vals})
    if a.trace:
        r = run_once(a.workload, seeds[0], a.seconds, a.trace, a.out, [])
        if r is not None:
            print("measure: traced " + json.dumps(r["line"]), flush=True)
    for name, per_set in table.items():
        print(f"measure: {name}: " + "; ".join(
            f"set {s['set']} median {s['median']:.6g} spread {100 * s['spread']:.2f}% "
            f"(least to most {100 * s['range']:.2f}%)" for s in per_set), flush=True)
    with open(os.path.join(a.out, f"sets-{a.workload}.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("sweep", "sets"):
        p = sub.add_parser(name)
        p.add_argument("--workload", required=True)
        p.add_argument("--seconds", type=float, default=None)
        p.add_argument("--rehearsal", action="store_true")
        p.add_argument("--out", default=os.path.join(os.path.dirname(HERE), "bench_out"))
    sw, st = sub.choices["sweep"], sub.choices["sets"]
    sw.add_argument("--key", required=True)
    sw.add_argument("--values", required=True)
    sw.add_argument("--seed", type=int, default=2024)
    st.add_argument("--seeds", required=True)
    st.add_argument("--sets", type=int, default=2)
    st.add_argument("--trace", type=int, default=0, choices=(0, 2),
                    help="2: one more run at the end, traced")
    a = ap.parse_args()
    os.makedirs(a.out, exist_ok=True)
    if a.rehearsal:
        REHEARSAL.append("--rehearsal")
    return sweep(a) if a.cmd == "sweep" else sets(a)


if __name__ == "__main__":
    sys.exit(main())
