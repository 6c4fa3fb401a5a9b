"""Decode launches that ran one column because a request was waiting or in
the middle of its prefill (``horizon_reason == "pending_admission"``) over
all decode launches, from the flight recorder's step records inside the
window, in percent.  A step record names the reason of the launch that step
made ("" when it made none).  A closed loop never queues when a frame
launches, so this reads 0 there; it is for cells that offer more than the
system sustains.  A program whose step records carry no ``horizon_reason``
gives nothing to read."""

from _common import in_window

META = {"layer": "scheduler", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: flight recorder step ring (horizon_reason)"}


def read(ctx):
    reasons = [s["horizon_reason"] for s in ctx["steps"]
               if s.get("horizon_reason") and in_window(s["t"], ctx["window"])]
    if not reasons:
        return None
    return 100.0 * sum(r == "pending_admission" for r in reasons) / len(reasons)
