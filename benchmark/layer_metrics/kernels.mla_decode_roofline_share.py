"""Least time for the absorbed latent attention of the decode columns run in
the traced window over the device time of the kernel that ran it, in percent.
Least time: for every cached token of every live lane, in every layer, the
larger of its entry's bytes as published (``latent_entry_bytes``: 1,152 B) over
the chip's bandwidth and the heads' products with it
(``mla_decode_flops_per_token``) over the chip's peak: at 128 heads the two
are within a hundredth of each other on a v5e.  Lane-tokens are taken as
``kernels.decode_roofline_share`` takes them (columns run x the callers' live
context).  Device time: the leaf operations named ``smg.attn.decode`` that
start inside decode launches.  The entry is laid out on 640 lanes where 576
are published, so a kernel at the memory's rate reads 90 %.  Nothing to read
(another architecture, XLA attention, no trace) gives None."""

from _common import bench_module, columns_run, peak
from _kernel_time import seconds_in_decode

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.attn.decode inside jit_multi*; "
                  "bytes and FLOPs from shapes (architectures/)"}

KERNEL = "smg.attn.decode"


def read(ctx):
    costs = ctx["costs"]
    if ctx["trace"] is None or ctx["trace_window"] is None \
            or not hasattr(costs, "latent_entry_bytes"):
        return None
    columns = columns_run(ctx)
    seconds = seconds_in_decode(ctx["trace"], KERNEL)
    if not columns or not seconds:
        return None
    live = bench_module("catalog").layer_metric_reader("kernels.decode_roofline_share").live_tokens
    lane_tokens = columns * live(ctx, ctx["trace_window"]) * costs.attention_layers(ctx["hf"])
    p = peak(ctx)
    least = max(lane_tokens * costs.latent_entry_bytes(ctx["hf"], ctx["kv_dtype_bytes"])
                / p["bytes_per_s"],
                lane_tokens * costs.mla_decode_flops_per_token(ctx["hf"]) / p["flops_per_s"])
    return 100.0 * least / (ctx["chips"] * seconds)
