"""``kernels.mla_decode_roofline_share`` for ``kimi-linear-48b-a3b.reason``: the
live lanes' latent entries of the three latent layers (as published, 1,152 B a
token and layer; at 32 heads the bytes are four times the heads' products'
time, where 128 heads have the two level) over the device time of
``smg.attn.decode`` in the decode frames, in percent.  The shared key is not
rotated in this model, which changes nothing the kernel reads.  This file hands
the cell's context to that reader and adds no arithmetic (the architecture
gives ``latent_entry_bytes``, ``mla_decode_flops_per_token`` and
``attention_layers`` = the latent layers), until a ``benchmark`` PR appends the
cell to that metric's ``workloads`` (ROADMAP T11), which then folds this file
in.  Another architecture gives None."""

from _common import bench_module

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.attn.decode inside jit_multi*; "
                  "bytes and FLOPs from shapes (architectures/), by the reader of "
                  "kernels.mla_decode_roofline_share"}


def read(ctx):
    if ctx["hf"].get("model_type") != "kimi_linear":
        return None
    reader = bench_module("catalog").layer_metric_reader("kernels.mla_decode_roofline_share")
    return reader.read(ctx)
