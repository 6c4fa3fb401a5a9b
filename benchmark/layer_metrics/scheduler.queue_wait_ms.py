"""Median ``admitted_t - queued_t`` over the flight recorder's timelines of
requests queued inside the window."""

from _common import in_window, median

META = {"layer": "scheduler", "unit": "ms", "moves": "output_tok_per_s",
        "source": "program_span: flight recorder timelines"}


def read(ctx):
    return median([(tl["admitted_t"] - tl["queued_t"]) * 1e3 for tl in ctx["timelines"]
                   if tl["admitted_t"] is not None and in_window(tl["queued_t"], ctx["window"])])
