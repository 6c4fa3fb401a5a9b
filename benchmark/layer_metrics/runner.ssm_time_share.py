"""Device time of the state-space layers' decode steps inside the decode
launches over the device time of those launches, in percent, for
``nemotron-3-super-120b-a12b.reason``: beside ``runner``'s experts' share of
the same launches (the two kernels' times are on the detail line's
``breakdown``) it says which of the two new parts sets the pace of a column.
What is summed is the kernels named ``smg.ssm.decode``, by
``_kernel_time.seconds_in_decode``, and nothing else: the layer's other spans
(``smg.ssm.in_proj``, ``smg.ssm.conv``, ``smg.ssm.gate_norm``,
``smg.ssm.out_proj``) run in fusions that a trace names ``fusion.123`` whatever
scope they were traced under, and no reader can tell them from the stack's
other fusions until ``trace_reduce`` keeps an event's scope (PERF.md, Open
questions), so the share errs low by the projections' time.  Another
architecture, the XLA form of the step or no trace gives None."""

from _common import bench_module
from _kernel_time import seconds_in_decode

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.ssm.decode inside jit_multi*, over "
                  "jit_multi* device time"}

KERNEL = "smg.ssm.decode"


def read(ctx):
    if ctx["trace"] is None or ctx["trace_window"] is None \
            or ctx["hf"].get("model_type") != "nemotron_h":
        return None
    fam = bench_module("trace_reduce").family_time(ctx["trace"], "decode")
    seconds = seconds_in_decode(ctx["trace"], KERNEL)
    if not fam or not fam["seconds"] or not seconds:
        return None
    return 100.0 * seconds / fam["seconds"]
