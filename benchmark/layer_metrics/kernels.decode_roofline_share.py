"""Least time for the decode columns run in the traced window over the device
time of the decode launches, in percent.  Least time: the matmul parameters
read once per column and the live lanes' cached keys and values once per
column, over the chips' memory bandwidth (decode is bound by bytes).  Columns
are those the device computed (``_common.columns_run``), not the ``horizon``
the frames asked for: counted by columns asked, the share of a stretch whose
frames leave early at a finish passes 100 %.  The live context is taken from
the client's records: a request decodes from its first token to its end and
holds ``prompt + output so far`` tokens meanwhile."""

from _common import bench_module, columns_run, peak

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: jit_multi* device time and the columns run (decode kernel "
                  "executions); bytes from shapes (architectures/)"}


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
    columns = columns_run(ctx)
    if not fam or not columns:
        return None
    least = ctx["costs"].decode_min_seconds(
        ctx["hf"], columns, columns * live_tokens(ctx, ctx["trace_window"]),
        ctx["chips"], peak(ctx), ctx["kv_dtype_bytes"])
    return 100.0 * least / fam["seconds"]
