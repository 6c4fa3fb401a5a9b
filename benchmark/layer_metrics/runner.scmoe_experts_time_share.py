"""Device time of the experts' grouped products inside the decode launches
over the device time of those launches, in percent, for
``longcat-flash-chat.reason``: the share of a decode column that the held
experts' three products take, which is most of what the shortcut branch
costs one chip in line (a deployment hides the branch behind the second
attention sublayer).  What is summed is the kernels named
``smg.moe.experts``, by ``_kernel_time.seconds_in_decode`` as the experts'
roofline share sums them, and nothing else: the rest of the branch (the
router's product and sort, the rows' gather, the combine, the identity
term's multiply-add; the program's scopes ``smg.scmoe.shortcut`` and
``smg.moe.zero``) runs in fusions that a trace names ``fusion.123`` whatever
scope they were traced under, 78 of the branch's 935 us in the traced layer of
PERF.md (Findings, PR 43), and no reader can tell them from the block's other
fusions until ``trace_reduce`` keeps an event's scope (PERF.md, Open
questions).  Another architecture, XLA's ragged product or no trace gives
None."""

from _common import bench_module
from _kernel_time import seconds_in_decode

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.moe.experts inside jit_multi*, over "
                  "jit_multi* device time"}

KERNEL = "smg.moe.experts"


def read(ctx):
    if ctx["trace"] is None or ctx["trace_window"] is None \
            or ctx["hf"].get("model_type") != "longcat_flash":
        return None
    fam = bench_module("trace_reduce").family_time(ctx["trace"], "decode")
    seconds = seconds_in_decode(ctx["trace"], KERNEL)
    if not fam or not fam["seconds"] or not seconds:
        return None
    return 100.0 * seconds / fam["seconds"]
