"""Device time of the mixer half of every layer inside the decode launches over the
device time of those launches, in percent: the leaf operations traced under
``smg.attn.*``, ``smg.mla.*``, ``smg.linattn.*``, ``smg.ssm.*`` and ``smg.kda.*``
(input norm, projections, convolution, gates, the attention or recurrence
kernel, output projection, residual).  A part of a whole: it falls when the
mixer gets faster and rises when anything else does, so read it beside
``runner.decode_step_ms``.
Summed by ``_scope_time`` from the trace's leaf operations and the program's
scope map; None without the map (the parent of PR 53) or the launches."""

from _scope_time import share

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations inside jit_multi* by the scope the program's "
                  "scope map gives them (scopes smg.attn.* smg.mla.* smg.linattn.* smg.ssm.* smg.kda.*), over jit_multi* device time"}


def read(ctx):
    return share(ctx, "decode", "mixer")
