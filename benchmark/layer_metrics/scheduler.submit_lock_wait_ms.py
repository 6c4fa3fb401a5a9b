"""Median ``queued_t - submit_t`` over the flight recorder's timelines of
requests queued inside the window: how long ``Engine.submit`` waited for the
engine lock, which ``step()`` holds across the blocking fetch of the frame in
flight.  ``submit_t`` is stamped before that wait, ``queued_t`` after it, so
``scheduler.queue_wait_ms`` cannot see it.  A program whose timelines carry
no ``submit_t`` gives nothing to read."""

from _common import in_window, median

META = {"layer": "scheduler", "unit": "ms", "moves": "output_tok_per_s",
        "source": "program_span: flight recorder timelines (submit_t, queued_t)"}


def read(ctx):
    return median([(tl["queued_t"] - tl["submit_t"]) * 1e3 for tl in ctx["timelines"]
                   if tl.get("submit_t") is not None and in_window(tl["queued_t"], ctx["window"])])
