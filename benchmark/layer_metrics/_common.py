"""Small helpers the readers share.  (A file whose name starts with ``_`` is
not a metric.)"""

from __future__ import annotations

import statistics


def median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def in_window(t, window) -> bool:
    return t is not None and window is not None and window[0] <= t <= window[1]


def decode_records(ctx, window):
    """Step-ring records in ``window`` that consumed a decode frame."""
    return [s for s in ctx["steps"]
            if s["kind"] in ("decode", "mixed") and in_window(s["t"], window)]


def bench_module(name):
    """A module of benchmark/ (trace_reduce, peaks, client_reduce)."""
    import importlib
    import os
    import sys

    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module(name)


def peak(ctx):
    return bench_module("peaks").peaks_for(ctx["device"]["kind"])


def caller_latency(ctx, which: str, q: float):
    """The ``q`` quantile of the callers' TTFT (``which`` 0) or TPOT (1) in ms
    over the requests due in the window; a failed request ranks as the worst."""
    cr = bench_module("client_reduce")
    reqs = [r for r in ctx["requests"] if in_window(r["due"], ctx["window"])]
    if not reqs:
        return None
    return min(cr.percentile(cr.latencies(reqs)[which], q), cr.MISSED_MS)
