"""From the load generator's records to latencies: shared by ``run.py``'s
end-to-end reduction and the ``caller.*`` per-layer readers."""

from __future__ import annotations

#: what a percentile reads when it falls on a request that failed
MISSED_MS = 1e9


def request_ok(r: dict) -> bool:
    return (r["error"] is None and r["finish"] == "length" and r["first"] is not None
            and r["output_tokens"] == r["want_output_tokens"]
            and r["prompt_tokens"] == r["want_prompt_tokens"])


def percentile(values: list, q: float) -> float:
    """The ``q`` quantile by linear interpolation; missing samples (None)
    rank as the worst."""
    vals = sorted(float("inf") if v is None else v for v in values)
    if not vals:
        return float("nan")
    pos = q * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    if vals[hi] == float("inf"):
        return float("inf") if pos > lo or vals[lo] == float("inf") else vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def latencies(reqs: list) -> tuple[list, list]:
    """``(ttft_ms, tpot_ms)`` per request, None where the request failed:
    first streamed content delta minus when the request was due, and (last
    delta - first delta) / (output tokens - 1)."""
    ttft, tpot = [], []
    for r in reqs:
        ok = request_ok(r)
        ttft.append((r["first"] - r["due"]) * 1e3 if ok else None)
        tpot.append((r["last"] - r["first"]) * 1e3 / (r["output_tokens"] - 1)
                    if ok and r["output_tokens"] > 1 else None)
    return ttft, tpot
