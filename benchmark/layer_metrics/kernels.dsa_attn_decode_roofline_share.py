"""Least time for the attention over the selection of the decode columns run in
the traced window over the device time of the gather and of what attends over
it, in percent, for ``glm-5.2.longdoc``.  Least time: for each live lane
``min(context, index_topk)`` entries (each caller's own context from the
client's records, cut at 2,048), in every layer (``attention_layers``: 5) and
column, the larger of an entry's bytes as published (``latent_entry_bytes``:
1,152 B) over the chip's bandwidth and the heads' products with it
(``mla_decode_flops_per_token``) over the chip's peak: at 64 heads the bytes
bound it on a v5e (1.4 us a thousand entries against 0.7).  Device time: the
leaf operations inside decode launches that the program's scope map puts under
``smg.attn.decode`` (the scores, the softmax and the weighted sum over the
gathered block) and ``smg.mla.sparse`` (the gather of the selected entries from
the pages and the side buffer).  A gather writes what it read and the products
read it again, so the share of a form that gathers cannot pass a third.
Nothing to read (another architecture, no trace, a program without the scope
map) gives None."""

from _dsa import SPARSE_ATTENTION, decode_roofline_share, is_cell, live_tokens

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_multi* under the scopes "
                  "smg.attn.decode and smg.mla.sparse (the program's scope map); entries from "
                  "each caller's context cut at index_topk, bytes and FLOPs from shapes "
                  "(architectures/)"}


def read(ctx):
    if ctx["trace"] is None or ctx["trace_window"] is None or not is_cell(ctx):
        return None
    costs, hf = ctx["costs"], ctx["hf"]
    entries = (live_tokens(ctx, ctx["trace_window"], cap=hf["index_topk"])
               * costs.attention_layers(hf))
    return decode_roofline_share(ctx, SPARSE_ATTENTION, entries,
                                 costs.latent_entry_bytes(hf, ctx["kv_dtype_bytes"]),
                                 costs.mla_decode_flops_per_token(hf))
