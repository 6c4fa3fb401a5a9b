"""``kernels.moe_decode_roofline_share`` for ``kimi-linear-48b-a3b.reason``: the
bytes of the held experts that got a row (the frame's count; an expert's three
matrices of 2,304 x 1,024) over the device time of ``smg.moe.experts`` in the
decode frames, in percent, at top 8 of 256 with 32 held.  This file hands the
cell's context to that reader and adds no arithmetic, until a ``benchmark`` PR
appends the cell to that metric's ``workloads`` (ROADMAP T11), which then folds
this file in.  Another architecture gives None."""

from _common import bench_module

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.moe.experts inside jit_multi*; "
                  "experts hit and rows from the step ring, bytes and FLOPs from shapes "
                  "(architectures/), by the reader of kernels.moe_decode_roofline_share"}


def read(ctx):
    if ctx["hf"].get("model_type") != "kimi_linear":
        return None
    reader = bench_module("catalog").layer_metric_reader("kernels.moe_decode_roofline_share")
    return reader.read(ctx)
