"""Least time for the indexers of the decode columns run in the traced window
over the device time of their scores and selection, in percent, for
``glm-5.2.longdoc``.  Least time: for every cached token of every live lane, in
each of the layers with an indexer (``index_layers``: 2), the larger of its
index key's bytes (``index_key_bytes``: 256 B) over the chip's bandwidth and the
index heads' products with it (``index_decode_flops_per_token``: 32 heads of
128) over the chip's peak; on a v5e the bytes bound it (0.31 us a thousand
lane-tokens against 0.04).  Lane-tokens are columns run x the callers' live
context, each caller's own.  Device time: the leaf operations inside decode
launches that the program's scope map puts under ``smg.mla.index.score`` and
``smg.mla.index.select`` (the products, the rectified weighted sum, the row's
largest); the keys' gather from the pages is under ``smg.mla.index.k`` and in
``runner.dsa_index_time_share``.  Nothing to read (another architecture, no
trace, a program without the scope map) gives None."""

from _dsa import INDEX_OVER_CONTEXT, decode_roofline_share, is_cell, live_tokens

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_multi* under the scopes "
                  "smg.mla.index.score and smg.mla.index.select (the program's scope map); bytes "
                  "and FLOPs from shapes (architectures/)"}


def read(ctx):
    if ctx["trace"] is None or ctx["trace_window"] is None or not is_cell(ctx):
        return None
    costs, hf = ctx["costs"], ctx["hf"]
    lane_tokens = live_tokens(ctx, ctx["trace_window"]) * costs.index_layers(hf)
    return decode_roofline_share(ctx, INDEX_OVER_CONTEXT, lane_tokens,
                                 costs.index_key_bytes(hf, ctx["kv_dtype_bytes"]),
                                 costs.index_decode_flops_per_token(hf))
