"""1 - (union of the device's operation intervals) / traced window, of the
least busy device of the mesh, in percent."""

from _common import bench_module

META = {"layer": "device", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: XLA Ops line"}


def read(ctx):
    return bench_module("trace_reduce").idle_share(ctx["trace"]) if ctx["trace"] else None
