"""From a profiler trace to numbers.  Two stages, so that the arithmetic can
be checked on a small recorded trace without JAX:

``load_xplane(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
into plain lists: ``{"devices": {name: {"ops": [[name, start_s, dur_s], ...],
"modules": [...]}}, "host": [[name, start_s, dur_s], ...]}``.

Everything else here is plain Python over those lists:

- busy time of a device is the union of the intervals in which an operation
  ran on it (the "XLA Ops" line; the "XLA Modules" line if there is none);
- a program's device time is the sum of its executions on the "XLA Modules"
  line, found by the program's jit name (no program has a named scope yet);
- the decode columns a device ran are the executions of the decode attention
  kernel inside the decode launches, over the layers that run it once a column;
- a collective's exposed time is the part of its interval during which no
  other leaf operation runs on the same device (a ``while`` or ``call``
  that merely encloses other operations is not a leaf);
- an idle gap is labelled by the innermost host span that covers most of it.
"""

from __future__ import annotations

import bisect
import re

#: jit names of the runner's step programs (engine/runner.py): the decode
#: megastep is ``jax.jit(multi)``, every prefill family ``jax.jit(step)``
PROGRAM_FAMILIES = {"decode": ("jit_multi",), "prefill": ("jit_step",)}

COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                       "collective-permute", "collective-broadcast")

NAME_CHARS = 160

_CPU_EXECUTOR = ("tf_XLAPjRtCpuClient", "tf_XLATfrtCpuClient", "tf_XLAEigen")


def load_xplane(path: str) -> dict:
    """Device operations, device modules and host spans of one trace."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    cpu_exec: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name.upper():
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                            for e in line.events if e.duration_ns > 0]
            if dev["ops"] or dev["modules"]:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                       for e in line.events if e.duration_ns > 0]
                if line.name.startswith(_CPU_EXECUTOR):
                    cpu_exec.extend(e for e in evs if not e[0].startswith("end: "))
                host.extend(evs)
    if not devices and cpu_exec:
        # the CPU backend (rehearsal) has no device plane: its executor
        # threads stand in, so that the same code runs end to end
        devices["/host:CPU executor"] = {"ops": cpu_exec, "modules": []}
    return {"devices": devices, "host": host}


def union(intervals: list) -> list:
    """Sorted, merged ``[start, end]`` pairs."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(merged: list) -> float:
    return sum(e - s for s, e in merged)


def clip(events: list, lo: float, hi: float) -> list:
    """``[start, end]`` of the events' parts inside ``[lo, hi]``."""
    out = []
    for _name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([a, b])
    return out


def window(trace: dict) -> tuple[float, float]:
    """The traced window: first start to last end of anything on a device."""
    starts, ends = [], []
    for dev in trace["devices"].values():
        for evs in (dev["ops"], dev["modules"]):
            if evs:
                starts.append(min(e[1] for e in evs))
                ends.append(max(e[1] + e[2] for e in evs))
    if not starts:
        return (0.0, 0.0)
    return (min(starts), max(ends))


def busy(trace: dict) -> dict:
    """Per device: busy seconds (union of op intervals) in the window."""
    lo, hi = window(trace)
    out = {}
    for name, dev in trace["devices"].items():
        evs = dev["ops"] or dev["modules"]
        out[name] = total(union(clip(evs, lo, hi)))
    return {"window_s": hi - lo, "busy_s": out}


def idle_share(trace: dict) -> float | None:
    """1 - busy/window of the least busy device, in percent."""
    b = busy(trace)
    if not b["busy_s"] or b["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - min(b["busy_s"].values()) / b["window_s"])


def _base(name: str) -> str:
    """A module's name without its numeric id: ``jit_multi(123)`` -> ``jit_multi``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def program_times(trace: dict) -> dict:
    """Per device and module name: ``[count, seconds, [durations]]``."""
    out: dict = {}
    for dname, dev in trace["devices"].items():
        per: dict = {}
        for name, _s, d in dev["modules"]:
            rec = per.setdefault(_base(name), [0, 0.0, []])
            rec[0] += 1
            rec[1] += d
            rec[2].append(d)
        out[dname] = per
    return out


def family_time(trace: dict, family: str) -> dict | None:
    """Launches, seconds and per-launch durations of one program family,
    averaged over the devices (every device of a mesh runs every launch)."""
    prefixes = PROGRAM_FAMILIES[family]
    per_dev = []
    for per in program_times(trace).values():
        count, secs, durs = 0, 0.0, []
        for name, (c, s, d) in per.items():
            if name.startswith(prefixes):
                count, secs = count + c, secs + s
                durs.extend(d)
        if count:
            per_dev.append((count, secs, durs))
    if not per_dev:
        return None
    n = len(per_dev)
    return {"launches": sum(c for c, _, _ in per_dev) / n,
            "seconds": sum(s for _, s, _ in per_dev) / n,
            "durations": per_dev[0][2]}


#: the paged decode attention kernel's own name (ops/pallas/decode_attention.py):
#: a decode column runs it once in every layer that holds keys and values
DECODE_KERNEL = "smg.attn.decode"


def kernel_columns(trace: dict, layers: int) -> float | None:
    """Columns the devices computed inside the decode launches: the operations
    named ``DECODE_KERNEL`` that start inside such a launch, over the
    ``layers`` that run it once a column, averaged over the devices.  An
    event's name is its whole HLO instruction, so the name is matched at its
    head: the operation that consumes the kernel's result names it too.
    A frame that left early at a finish counts the columns it ran, a frame
    launched ahead and thrown away counts what it ran too, and one that ran
    no column counts none.  None where no decode launch runs the kernel (XLA
    attention under a mesh): there is nothing to count."""
    prefixes = PROGRAM_FAMILIES["decode"]
    per_dev = []
    for dev in trace["devices"].values():
        spans = sorted((s, s + d) for name, s, d in dev["modules"]
                       if _base(name).startswith(prefixes))
        starts = [a for a, _b in spans]
        runs = 0
        for name, s, _d in dev["ops"]:
            if name.lstrip("%").startswith(DECODE_KERNEL):
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < spans[i][1]:
                    runs += 1
        if runs:
            per_dev.append(runs)
    if not per_dev or layers <= 0:
        return None
    return sum(per_dev) / len(per_dev) / layers


def leaves(events: list) -> list:
    """The events that enclose no other event (``while``, ``call`` and
    ``conditional`` bodies appear as children of their parent on the line)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [event, has_child]
    for ev in evs:
        while stack and ev[1] >= stack[-1][0][1] + stack[-1][0][2] - 1e-12:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        # a child lies wholly inside its parent; two operations that merely
        # overlap (an asynchronous collective and the next fusion) are siblings
        if stack and ev[1] + ev[2] <= stack[-1][0][1] + stack[-1][0][2] + 1e-12:
            stack[-1][1] = True
        stack.append([ev, False])
    while stack:
        top, has_child = stack.pop()
        if not has_child:
            out.append(top)
    return out


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVE_PREFIXES)


def collective_exposed(trace: dict) -> dict | None:
    """Per device: collective seconds, and the part of them during which no
    other leaf operation ran on that device.  The worst device is reported."""
    lo, hi = window(trace)
    worst = None
    for name, dev in trace["devices"].items():
        lv = leaves(dev["ops"])
        coll = union(clip([e for e in lv if is_collective(e[0])], lo, hi))
        comp = union(clip([e for e in lv if not is_collective(e[0])], lo, hi))
        hidden = 0.0
        j = 0
        for s, e in coll:
            while j < len(comp) and comp[j][1] <= s:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < e:
                hidden += min(e, comp[k][1]) - max(s, comp[k][0])
                k += 1
        rec = {"device": name, "collective_s": total(coll),
               "exposed_s": total(coll) - hidden, "window_s": hi - lo}
        if worst is None or rec["exposed_s"] > worst["exposed_s"]:
            worst = rec
    return worst


def top_ops(trace: dict, n: int = 10) -> list:
    """The leaf device operations that took most time, summed by name and
    averaged over the devices: ``[[name, seconds], ...]``."""
    sums: dict = {}
    ndev = max(len(trace["devices"]), 1)
    for dev in trace["devices"].values():
        for name, _s, d in leaves(dev["ops"] or dev["modules"]):
            sums[name] = sums.get(name, 0.0) + d
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    # a TPU trace names an operation by its whole HLO instruction: keep the head
    return [[name[:NAME_CHARS], secs / ndev] for name, secs in ranked]


def idle_gaps(trace: dict, n: int = 10, span_prefix: str = "bench.") -> list:
    """The longest idle gaps of the busiest-gapped device, summed by label:
    the benchmark's own host span (``bench.*``) that covers most of the gap,
    else the innermost other host span covering at least half of it, else
    ``unattributed``.  ``[[label, seconds], ...]``."""
    lo, hi = window(trace)
    if not trace["devices"]:
        return []
    # the device with the most idle time
    def merged(dev):
        return union(clip(dev["ops"] or dev["modules"], lo, hi))

    dev = min(trace["devices"].values(), key=lambda d: total(merged(d)))
    m = merged(dev)
    gaps = [[a[1], b[0]] for a, b in zip(m, m[1:]) if b[0] > a[1]]
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:200]
    host = sorted(trace["host"], key=lambda e: e[1])
    sums: dict = {}
    for gs, ge in gaps:
        best, best_key = "unattributed", None
        for name, s, d in host:
            if s >= ge:
                break
            ov = min(ge, s + d) - max(gs, s)
            if ov < 0.5 * (ge - gs):
                continue
            own = name.startswith(span_prefix)
            key = (own, -d)  # the benchmark's spans first, then the innermost
            if best_key is None or key > best_key:
                best, best_key = name, key
        sums[best] = sums.get(best, 0.0) + (ge - gs)
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def cut(trace: dict, seconds: float, max_host: int = 3000) -> dict:
    """The first ``seconds`` of the traced window, small enough to keep: every
    device event that starts in it, and the longest host spans."""
    lo, _hi = window(trace)
    hi = lo + seconds

    def inside(evs):
        return [e for e in evs if lo <= e[1] < hi]

    host = sorted(inside(trace["host"]), key=lambda e: -e[2])[:max_host]
    return {"devices": {n: {"ops": inside(d["ops"]), "modules": inside(d["modules"])}
                        for n, d in trace["devices"].items()},
            "host": sorted(host, key=lambda e: e[1])}


def find_xplane(trace_dir: str) -> str | None:
    import glob
    import os

    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    return found[-1] if found else None
