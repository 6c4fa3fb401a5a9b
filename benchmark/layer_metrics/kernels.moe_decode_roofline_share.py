"""Least time for the routed experts of the decode frames consumed in the
traced window over the device time of the grouped-product kernel inside decode
launches, in percent.  Least time, frame by frame: the larger of the bytes of
the held experts that got a row (``moe_experts_hit``, summed over layers and
columns, x ``expert_bytes``: an expert nobody picked is not read) over the
chip's bandwidth and the rows computed (``moe_picks_held`` x
``expert_flops_per_row``) over its peak.  Device time: the leaf operations
named ``smg.moe.experts`` that start inside decode launches (the kernel runs
in prefill too; those are inside ``kernels.prefill_roofline_share``).  The
step ring counts the frames the host consumed and the trace every launch (a
frame launched ahead and thrown away ran its columns too), so the share errs
low.  Nothing to read (a program without the counters, XLA's ragged product,
no trace) gives None."""

from _common import decode_records, peak
from _kernel_time import seconds_in_decode

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.moe.experts inside jit_multi*; "
                  "experts hit and rows from the step ring, bytes and FLOPs from shapes "
                  "(architectures/)"}

KERNEL = "smg.moe.experts"


def read(ctx):
    costs = ctx["costs"]
    if ctx["trace"] is None or ctx["trace_window"] is None \
            or not hasattr(costs, "expert_bytes"):
        return None
    recs = [s for s in decode_records(ctx, ctx["trace_window"]) if "moe_experts_hit" in s]
    seconds = seconds_in_decode(ctx["trace"], KERNEL)
    if not recs or not seconds:
        return None
    p = peak(ctx)
    by_bytes = costs.expert_bytes(ctx["hf"], ctx["kv_dtype_bytes"]) / p["bytes_per_s"]
    by_flops = costs.expert_flops_per_row(ctx["hf"]) / p["flops_per_s"]
    least = sum(max(s["moe_experts_hit"] * by_bytes, s["moe_picks_held"] * by_flops)
                for s in recs)
    return 100.0 * least / (ctx["chips"] * seconds) if least else None
