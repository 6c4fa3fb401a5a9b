"""``kernels.moe_decode_roofline_share`` for ``glm-5.2.longdoc``: the experts'
grouped products at top 8 of 256 (16 held) in four layers.  This file hands the
cell's context to that reader and adds no arithmetic: the step ring's
``moe_experts_hit`` and ``moe_picks_held`` count held experts and the rows on
them, the architecture file gives ``expert_bytes`` and ``expert_flops_per_row``.
Until a ``benchmark`` PR appends the cell to that metric's ``workloads``
(ROADMAP T11), which then folds this file in.  Another architecture gives
None."""

from _common import bench_module
from _dsa import is_cell

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.moe.experts inside jit_multi*; "
                  "experts hit and rows from the step ring, bytes and FLOPs from shapes "
                  "(architectures/), by the reader of kernels.moe_decode_roofline_share"}


def read(ctx):
    if not is_cell(ctx):
        return None
    reader = bench_module("catalog").layer_metric_reader("kernels.moe_decode_roofline_share")
    return reader.read(ctx)
