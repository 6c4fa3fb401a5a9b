"""``kernels.mla_decode_roofline_share`` for ``longcat-flash-chat.reason``: the
latent decode kernel at 64 heads over 8 cache layers (two a layer).  This file
hands the cell's context to that reader and adds no arithmetic: the cell's
architecture file gives ``latent_entry_bytes`` (1,152 B), ``attention_layers``
(8) and ``mla_decode_flops_per_token`` (at 64 heads the entry's bytes bound the
kernel: 1.4 us a thousand lane-tokens against 0.7 by FLOPs on a v5e).  Until a
``benchmark`` PR appends the cell to that metric's ``workloads`` (ROADMAP T11),
which then folds this file in.  Another architecture gives None."""

from _common import bench_module

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.attn.decode inside jit_multi*; "
                  "bytes and FLOPs from shapes (architectures/), by the reader of "
                  "kernels.mla_decode_roofline_share"}


def read(ctx):
    if ctx["hf"].get("model_type") != "longcat_flash":
        return None
    reader = bench_module("catalog").layer_metric_reader("kernels.mla_decode_roofline_share")
    return reader.read(ctx)
