"""Device time of the decode programs in the traced window over the decode
columns run in it: the columns the device computed (``_common.columns_run``),
not the ``horizon`` the frames asked for, since a frame leaves early at a
finish."""

from _common import bench_module, columns_run

META = {"layer": "runner", "unit": "ms", "moves": "output_tok_per_s",
        "source": "device_trace: XLA Modules line, jit_multi*, over the columns run "
                  "(decode kernel executions on the XLA Ops line)"}


def read(ctx):
    if ctx["trace"] is None or ctx["trace_window"] is None:
        return None
    fam = bench_module("trace_reduce").family_time(ctx["trace"], "decode")
    columns = columns_run(ctx)
    return fam["seconds"] * 1e3 / columns if fam and columns else None
