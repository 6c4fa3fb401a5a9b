"""Device time of choosing the experts and carrying rows to and from them inside
the decode launches over the device time of those launches, in percent: the
leaf operations traced under ``smg.moe.route``, ``smg.moe.dispatch`` and
``smg.moe.combine``.  A part of ``runner.decode_ffn_time_share``; None for a
model without routed experts.
Summed by ``_scope_time`` from the trace's leaf operations and the program's
scope map; None without the map (the parent of PR 53) or the launches."""

from _scope_time import share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_multi* by the scope the program's "
                  "scope map gives them (scopes smg.moe.route smg.moe.dispatch smg.moe.combine), over jit_multi* device time"}


def read(ctx):
    return share(ctx, "decode", "routing")
