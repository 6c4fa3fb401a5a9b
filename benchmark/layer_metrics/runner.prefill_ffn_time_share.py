"""Device time of the feed-forward half of every layer inside the prefill
launches over the device time of those launches, in percent: as
``runner.decode_ffn_time_share``.  A part of a whole: read it beside
``runner.prefill_step_ms``.
Summed by ``_scope_time`` from the trace's leaf operations and the program's
scope map; None without the map (the parent of PR 53) or the launches."""

from _scope_time import share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_step* by the scope the program's "
                  "scope map gives them (scopes smg.mlp smg.moe.* smg.scmoe.*), over jit_step* device time"}


def read(ctx):
    return share(ctx, "prefill", "ffn")
