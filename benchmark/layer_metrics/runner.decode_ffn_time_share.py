"""Device time of the feed-forward half of every layer inside the decode launches
over the device time of those launches, in percent: the leaf operations traced
under ``smg.mlp``, ``smg.moe.*`` and ``smg.scmoe.*`` (norm, router, dispatch, the
experts' products, combine, shared expert, residual).  A part of a whole: it
falls when the experts get faster and rises when anything else does, so read
it beside ``runner.decode_step_ms``.
Summed by ``_scope_time`` from the trace's leaf operations and the program's
scope map; None without the map (the parent of PR 53) or the launches."""

from _scope_time import share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_multi* by the scope the program's "
                  "scope map gives them (scopes smg.mlp smg.moe.* smg.scmoe.*), over jit_multi* device time"}


def read(ctx):
    return share(ctx, "decode", "ffn")
