"""Device time of the decode programs in the traced window over the decode
columns launched in it (the sum of ``horizon`` over the step ring's decode
records stamped inside the traced window)."""

from _common import bench_module, decode_records

META = {"layer": "runner", "unit": "ms", "moves": "output_tok_per_s",
        "source": "device_trace: XLA Modules line, jit_multi*; step ring for the columns"}


def read(ctx):
    if ctx["trace"] is None:
        return None
    fam = bench_module("trace_reduce").family_time(ctx["trace"], "decode")
    columns = sum(s["horizon"] for s in decode_records(ctx, ctx["trace_window"]))
    return fam["seconds"] * 1e3 / columns if fam and columns else None
