"""Device time of the ``smg.kda.*`` operations inside the decode launches over
the device time of those launches, in percent, for
``kimi-linear-48b-a3b.reason``: beside the experts' kernel's time on the detail
line's ``breakdown`` it says whether the recurrence or the experts set the pace
of a column.  What a trace names ``smg.kda.*`` is the kernel ``smg.kda.decode``
and nothing else: the layer's other spans (``smg.kda.proj``, ``smg.kda.conv``,
``smg.kda.gates``, ``smg.kda.gate_norm``, ``smg.kda.out_proj``) run in fusions
that a trace names ``fusion.123`` whatever scope they were traced under, and no
reader can tell them from the stack's other fusions until ``trace_reduce``
keeps an event's scope (PERF.md, Open questions), so the share errs low by the
projections' time.  Another architecture, the XLA form of the step or no trace
gives None."""

from _common import bench_module
from _kernel_time import seconds_in_decode

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.kda.* inside jit_multi*, over "
                  "jit_multi* device time"}

SPANS = "smg.kda."


def read(ctx):
    if ctx["trace"] is None or ctx["trace_window"] is None \
            or ctx["hf"].get("model_type") != "kimi_linear":
        return None
    fam = bench_module("trace_reduce").family_time(ctx["trace"], "decode")
    seconds = seconds_in_decode(ctx["trace"], SPANS)
    if not fam or not fam["seconds"] or not seconds:
        return None
    return 100.0 * seconds / fam["seconds"]
