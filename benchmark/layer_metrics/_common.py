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


def columns_run(ctx):
    """Decode columns the device computed in the traced window: what a frame
    ran, not the ``horizon`` it asked for (a frame leaves early at a finish).
    Counted on the device where the decode programs run the paged attention
    kernel (``trace_reduce.kernel_columns`` over the architecture's
    ``attention_layers``), which covers exactly the launches whose device time
    the trace sums.  Else from the step ring, where the program's records
    carry ``columns_run``.  Else None: the sum of ``horizon`` is no stand-in."""
    layers = getattr(ctx["costs"], "attention_layers", None)
    if layers is not None:
        columns = bench_module("trace_reduce").kernel_columns(ctx["trace"], layers(ctx["hf"]))
        if columns:
            return columns
    recs = decode_records(ctx, ctx["trace_window"])
    if recs and all("columns_run" in s for s in recs):
        return sum(s["columns_run"] for s in recs) or None
    return None


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
