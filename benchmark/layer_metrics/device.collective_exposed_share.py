"""Collective operations' device time during which no other operation runs
on that device, over the traced window, on the worst device, in percent."""

from _common import bench_module

META = {"layer": "device", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: XLA Ops line, all-reduce/all-gather/... leaves"}


def read(ctx):
    if ctx["trace"] is None:
        return None
    rec = bench_module("trace_reduce").collective_exposed(ctx["trace"])
    if rec is None or rec["window_s"] <= 0 or rec["collective_s"] <= 0:
        return None
    return 100.0 * rec["exposed_s"] / rec["window_s"]
