"""Device time of the embedding, the head and the sampler inside the decode
launches over the device time of those launches, in percent: the leaf
operations traced under ``smg.embed``, ``smg.lm_head`` and ``smg.sample``.
Summed by ``_scope_time`` from the trace's leaf operations and the program's
scope map; None without the map (the parent of PR 53) or the launches."""

from _scope_time import share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_multi* by the scope the program's "
                  "scope map gives them (scopes smg.embed smg.lm_head smg.sample), over jit_multi* device time"}


def read(ctx):
    return share(ctx, "decode", "head")
