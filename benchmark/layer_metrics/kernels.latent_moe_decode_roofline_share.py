"""Least time for the latent experts of the decode frames consumed in the
traced window over the device time of the grouped-product kernel inside decode
launches, in percent, for ``nemotron-3-super-120b-a12b.reason``: the experts'
two products (no gate matrix) in a 1,024-wide latent at top 22 of 512.  This
file hands the cell's context to the reader of
``kernels.moe_decode_roofline_share`` and adds no arithmetic: the step ring's
``moe_experts_hit`` and ``moe_picks_held`` count held experts and the rows on
them, and the architecture's ``expert_bytes`` and ``expert_flops_per_row`` are
of two matrices.  Until a ``benchmark`` PR appends the cell to that metric's
``workloads`` (ROADMAP T11), which then folds this file in.  Another
architecture gives None."""

from _common import bench_module

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.moe.experts inside jit_multi*; "
                  "experts hit and rows from the step ring, bytes and FLOPs from shapes "
                  "(architectures/), by the reader of kernels.moe_decode_roofline_share"}


def read(ctx):
    if ctx["hf"].get("model_type") != "nemotron_h":
        return None
    reader = bench_module("catalog").layer_metric_reader("kernels.moe_decode_roofline_share")
    return reader.read(ctx)
