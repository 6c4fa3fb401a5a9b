"""``kernels.moe_decode_roofline_share`` for ``longcat-flash-chat.reason``: the
experts' grouped products at top 12 of 768 outputs.  This file hands the
cell's context to that reader and adds no arithmetic: the step ring's
``moe_experts_hit`` and ``moe_picks_held`` count held experts and the rows on
them, so an identity pick (``moe_picks_zero``), which reads no weight and
multiplies nothing, is in neither and costs 0 bytes and 0 FLOPs here.  Until a
``benchmark`` PR appends the cell to that metric's ``workloads`` (ROADMAP T11),
which then folds this file in.  Another architecture gives None."""

from _common import bench_module

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.moe.experts inside jit_multi*; "
                  "experts hit and rows from the step ring, bytes and FLOPs from shapes "
                  "(architectures/), by the reader of kernels.moe_decode_roofline_share"}


def read(ctx):
    if ctx["hf"].get("model_type") != "longcat_flash":
        return None
    reader = bench_module("catalog").layer_metric_reader("kernels.moe_decode_roofline_share")
    return reader.read(ctx)
