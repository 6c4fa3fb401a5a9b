"""Least time for the decode launches of the traced window over their device
time, in percent.  Least time: the matmul parameters read once per column
and the live lanes' cached keys and values once per column, over the chips'
memory bandwidth (decode is bound by bytes).  The live context is taken from
the client's records: a request decodes from its first token to its end and
holds ``prompt + output so far`` tokens meanwhile."""

from _common import bench_module, decode_records, peak

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: jit_multi* device time; bytes from shapes (architectures/)"}


def live_tokens(ctx, window) -> float:
    """Time-average over ``window`` of the context tokens held by requests
    that are decoding."""
    lo, hi = window
    total = 0.0
    for r in ctx["requests"]:
        if r["first"] is None or r["done"] is None:
            continue
        a, b = max(r["first"], lo), min(r["done"], hi)
        if b <= a:
            continue
        span = max(r["done"] - r["first"], 1e-9)
        mid = ((a + b) / 2 - r["first"]) / span  # progress through the output
        total += (b - a) * (r["prompt_tokens"] + mid * r["output_tokens"])
    return total / (hi - lo)


def read(ctx):
    if ctx["trace"] is None or ctx["trace_window"] is None:
        return None
    fam = bench_module("trace_reduce").family_time(ctx["trace"], "decode")
    columns = sum(s["horizon"] for s in decode_records(ctx, ctx["trace_window"]))
    if not fam or not columns:
        return None
    least = ctx["costs"].decode_min_seconds(
        ctx["hf"], columns, columns * live_tokens(ctx, ctx["trace_window"]),
        ctx["chips"], peak(ctx), ctx["kv_dtype_bytes"])
    return 100.0 * least / fam["seconds"]
