"""Least time for the linear-attention decode steps of the traced window over
the device time of the operations that ran them, in percent.  Least time: a
decode column reads and writes the recurrent state of every live lane once in
every linear-attention layer (``architectures/<name>.linattn_state_bytes``,
float32), over the chip's memory bandwidth.  Device time: the leaf operations
whose name carries ``smg.linattn.decode`` (the kernel's own name).  Lanes times
columns come from the step ring: the decode tokens accepted in the traced
window by frames whose lanes held a state slot (``state_lanes``), which counts
no column the device ran and the host threw away, so the share errs low.
Nothing to read (another architecture, a program without state slots, the
XLA form of the step on the CPU) gives None."""

from _common import bench_module, decode_records, peak

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.linattn.decode; bytes from shapes "
                  "(architectures/)"}

KERNEL = "smg.linattn.decode"


def kernel_seconds(trace: dict) -> float:
    """Device seconds of the kernel's leaf operations, averaged over devices."""
    tr = bench_module("trace_reduce")
    per_dev = [sum(d for name, _s, d in tr.leaves(dev["ops"]) if KERNEL in name)
               for dev in trace["devices"].values()]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def read(ctx):
    costs = ctx["costs"]
    if ctx["trace"] is None or ctx["trace_window"] is None \
            or not hasattr(costs, "linattn_state_bytes"):
        return None
    lane_columns = sum(s["decode_tokens"] for s in decode_records(ctx, ctx["trace_window"])
                       if s.get("state_lanes"))
    seconds = kernel_seconds(ctx["trace"])
    if not lane_columns or not seconds:
        return None
    least = (lane_columns * costs.linear_layers(ctx["hf"]) * 2 * costs.linattn_state_bytes(ctx["hf"])
             / (ctx["chips"] * peak(ctx)["bytes_per_s"]))
    return 100.0 * least / seconds
