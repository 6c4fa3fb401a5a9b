"""Median ``first_token_t - admitted_t`` over the flight recorder's timelines
of requests queued inside the window: from the step that admitted the request
(radix match, pages, the first prefill launch) to the step that accepted its
first token.  With ``gateway.ttft_added_ms``, ``scheduler.submit_lock_wait_ms``
and ``scheduler.queue_wait_ms`` it accounts for a caller's time to first token
from inside the program."""

from _common import in_window, median

META = {"layer": "scheduler", "unit": "ms", "moves": "output_tok_per_s",
        "source": "program_span: flight recorder timelines (admitted_t, first_token_t)"}


def read(ctx):
    return median([(tl["first_token_t"] - tl["admitted_t"]) * 1e3 for tl in ctx["timelines"]
                   if tl["admitted_t"] is not None and tl["first_token_t"] is not None
                   and in_window(tl["queued_t"], ctx["window"])])
