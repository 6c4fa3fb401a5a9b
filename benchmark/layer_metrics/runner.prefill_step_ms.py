"""Median device time of one prefill launch (any of the three prefill
families: they share the jit name ``step``) in the traced window."""

from _common import bench_module, median

META = {"layer": "runner", "unit": "ms", "moves": "output_tok_per_s",
        "source": "device_trace: XLA Modules line, jit_step*"}


def read(ctx):
    if ctx["trace"] is None:
        return None
    fam = bench_module("trace_reduce").family_time(ctx["trace"], "prefill")
    return median(fam["durations"]) * 1e3 if fam else None
