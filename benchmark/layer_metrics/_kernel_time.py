"""Device time of one named kernel inside the decode launches.  (A file whose
name starts with ``_`` is not a metric.)"""

from __future__ import annotations

import bisect

from _common import bench_module


def seconds_in_decode(trace: dict, kernel: str) -> float:
    """Seconds of the leaf operations whose name starts with ``kernel`` (a
    Pallas kernel's custom call carries its named scope as its own name) and
    that start inside a decode launch (``jit_multi*`` on the XLA Modules
    line, found as ``trace_reduce.kernel_columns`` finds them), averaged over
    the devices."""
    tr = bench_module("trace_reduce")
    prefixes = tr.PROGRAM_FAMILIES["decode"]
    per_dev = []
    for dev in trace["devices"].values():
        spans = sorted((s, s + d) for name, s, d in dev["modules"]
                       if tr._base(name).startswith(prefixes))
        starts = [a for a, _b in spans]
        total = 0.0
        for name, s, d in tr.leaves(dev["ops"]):
            if name.lstrip("%").startswith(kernel):
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < spans[i][1]:
                    total += d
        per_dev.append(total)
    return sum(per_dev) / len(per_dev) if per_dev else 0.0
