"""What the readers of the program's step account share.  (A file whose name
starts with ``_`` is not a metric.)

Since schema 9 every step record says where the step's host seconds went and
for how many of them the host knew the chip had nothing queued
(``smg_tpu/engine/spans.py``, ``StepAccount``): from the return of a fetch
that left no launch outstanding to the return of the next dispatch.  The
readers sum one key over the records stamped inside the window and divide by
the window's length, so the number rests on all 51 s of a run and not on the
traced 6 s.

Bias, all of it low: a record is stamped at its step's end, so the step that
straddles the window's end is left out and the one that straddles its start
is counted whole (one step of about a thousand); a launch that is never
fetched (a lookahead thrown away at a finish, a KV-only chunk) is proved done
only by the next fetch, so a device that left it early and sat idle through
the admission that followed is held for busy; and the host sees neither the
time a finished frame's tokens take to reach it nor the time a dispatched
program takes to start.  ``device.idle_share`` counts all three as idle: on
the chip the sum of ``starved_s`` over a traced stretch read 70 to 85 % of
the device's idle seconds in the four cells, never more (PERF.md section 5,
PR 39)."""

from _common import in_window


def share(ctx, key: str):
    """Percent of the window in which ``key`` ran, or None where the window
    holds no step record or the program's records lack the key."""
    window = ctx["window"]
    recs = [s for s in ctx["steps"] if in_window(s["t"], window)]
    if not recs or any(key not in s for s in recs):
        return None
    return 100.0 * sum(s[key] for s in recs) / (window[1] - window[0])
