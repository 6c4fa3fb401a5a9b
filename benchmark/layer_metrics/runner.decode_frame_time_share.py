"""Device time of the decode frame's own parts inside the decode launches over
the device time of those launches, in percent: the leaf operations traced
under ``smg.frame.*`` (``begin``: side buffers and the loop's first carry;
``emit``: a column's tokens, logprobs and stop state; ``penalties``; ``land``:
the frame's rows into pages, rings and state slots).
Summed by ``_scope_time`` from the trace's leaf operations and the program's
scope map; None without the map (the parent of PR 53) or the launches."""

from _scope_time import share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_multi* by the scope the program's "
                  "scope map gives them (scopes smg.frame.*), over jit_multi* device time"}


def read(ctx):
    return share(ctx, "decode", "frame")
