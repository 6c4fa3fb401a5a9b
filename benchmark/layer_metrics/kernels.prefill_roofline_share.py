"""Least time for the prefill launches of the traced window over their
device time, in percent.  Least time: 2 FLOPs per matmul parameter and real
(unpadded, uncached) prompt token plus attention's FLOPs over the causal
pairs, over the chips' peak (prefill is bound by compute).  The tokens are
those of the requests whose first token fell inside the traced window, from
the flight recorder's timelines."""

from _common import bench_module, in_window, peak

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: jit_step* device time; FLOPs from shapes (architectures/)"}


def read(ctx):
    if ctx["trace"] is None or ctx["trace_window"] is None:
        return None
    fam = bench_module("trace_reduce").family_time(ctx["trace"], "prefill")
    new_tokens = pairs = 0.0
    for tl in ctx["timelines"]:
        if in_window(tl["first_token_t"], ctx["trace_window"]):
            new = tl["prompt_tokens"] - tl["cached_tokens"]
            new_tokens += new
            pairs += new * (tl["cached_tokens"] + (new + 1) / 2.0)
    if not fam or not new_tokens:
        return None
    least = ctx["costs"].prefill_min_seconds(
        ctx["hf"], new_tokens, pairs, ctx["chips"], peak(ctx))
    return 100.0 * least / fam["seconds"]
