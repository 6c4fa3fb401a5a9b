"""Least time for the KDA layers' decode steps of the traced window over the
device time of the operations that ran them, in percent, for
``kimi-linear-48b-a3b.reason``.  Least time
(``architectures/kimi_linear.kda_decode_min_seconds``): for every lane that ran
and every KDA layer the state read and written (32 heads x 128 x 128 float32),
the convolution's tail read and written, and the lane's rows of ``q``, ``k``,
``v``, the decay (a number a key channel) and ``beta``, over the chip's memory
bandwidth.  Device time: the leaf operations named ``smg.kda.decode`` (the
kernel's own name; **not** ``smg.linattn.decode``, by which
``kernels.linattn_decode_roofline_share`` counts another rule's kernel) that
start inside a decode launch (``_kernel_time.seconds_in_decode``).  Lanes times
columns come from the step ring: the decode tokens accepted in the traced window
by frames whose lanes held a state slot (``state_lanes``), which counts no
column the device ran and the host threw away, so the share errs low.  The
lanes that ran are this reader's to give the cost function: the contract's
``decode_min_seconds`` has no such argument (PERF.md 7.16).  Nothing to read
(another architecture, a program without this kernel, the XLA form of the step
on the CPU) gives None."""

from _common import decode_records, peak
from _kernel_time import seconds_in_decode

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.kda.decode inside jit_multi*; lanes "
                  "that ran from the step ring, bytes from shapes (architectures/)"}

KERNEL = "smg.kda.decode"


def read(ctx):
    costs = ctx["costs"]
    if ctx["trace"] is None or ctx["trace_window"] is None \
            or not hasattr(costs, "kda_decode_min_seconds"):
        return None
    lane_columns = sum(s["decode_tokens"] for s in decode_records(ctx, ctx["trace_window"])
                       if s.get("state_lanes"))
    seconds = seconds_in_decode(ctx["trace"], KERNEL)
    if not lane_columns or not seconds:
        return None
    least = costs.kda_decode_min_seconds(ctx["hf"], lane_columns, ctx["chips"], peak(ctx),
                                         ctx["kv_dtype_bytes"])
    return 100.0 * least / seconds
