"""Least time for the window layers' decode attention of the traced window over
the device time of the kernel that ran it, in percent.  Least time: a decode
column reads, for every live lane and window layer, the keys and values inside
the window (``architectures/<name>.window`` entries of ``window_entry_bytes``),
over the chip's memory bandwidth; the kernel streams the whole ring, which is
the window and a frame or two more, and that surplus counts against it.
Device time: the leaf operations named ``smg.attn.window_decode`` (the ring
kernel's own name) inside decode launches.  Lanes times columns come from the
step ring: the decode tokens accepted in the traced window, which counts no
column the device ran and the host threw away, while the trace counts every
launch, so the share errs low.  Nothing to read (another architecture, the XLA
form of the step on the CPU) gives None."""

from _common import decode_records, peak
from _kernel_time import seconds_in_decode

META = {"layer": "kernels", "unit": "%", "moves": "output_tok_per_s",
        "source": "device_trace: leaf operations named smg.attn.window_decode inside decode "
                  "launches; bytes from shapes (architectures/)"}

KERNEL = "smg.attn.window_decode"


def read(ctx):
    costs = ctx["costs"]
    if ctx["trace"] is None or ctx["trace_window"] is None \
            or not hasattr(costs, "window_entry_bytes"):
        return None
    lane_columns = sum(s["decode_tokens"] for s in decode_records(ctx, ctx["trace_window"]))
    seconds = seconds_in_decode(ctx["trace"], KERNEL)
    if not lane_columns or not seconds:
        return None
    hf = ctx["hf"]
    least = (lane_columns * costs.window_layers(hf) * costs.window(hf)
             * costs.window_entry_bytes(hf, ctx["kv_dtype_bytes"])
             / (ctx["chips"] * peak(ctx)["bytes_per_s"]))
    return 100.0 * least / seconds
