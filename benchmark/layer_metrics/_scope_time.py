"""Device time by named scope inside the launches of one program family.  (A
file whose name starts with ``_`` is not a metric.)

A trace names an operation by its HLO instruction (``%fusion.516 = bf16[64,
7680]{...} fusion(...)``), not by the ``jax.named_scope`` it was traced under.
The program says which scope every instruction of its compiled programs
belongs to: after a profile has ended, ``loads()["programs"]["scopes"]`` is
``{program key: {"family": "multi" | "step" | ..., "scopes": {scope: [head,
...]}}}`` (``smg_tpu/analysis/runtime_guards.ProgramAuditor.scope_map``), a
head being an instruction's name and result shape as the trace prints them,
``""`` the scope of what stands under none and ``"~" + scope`` that of an
unscoped instruction whose every reader stands under ``scope``.  A program
whose executable another commit compiled (the compile cache's key leaves
metadata out) says ``"stale": true`` and lists every head under none: its
launches read as unscoped time here and never as that commit's split.  Joined
here with the leaf operations inside each launch on the XLA Modules line.

Several programs of one family reuse instruction names (``fusion.7`` in two
decode programs, under different scopes), so a launch is first resolved to the
program whose heads cover most of its leaves, by the module's full name (the
number in ``jit_multi(430990784155923294)`` tells a family's programs apart on
the line; the program has no way to read it off its executable), and only
then are seconds summed by scope.  A launch that no map covers by half is
unscoped whole.  A program without the map (the parent of PR 53) gives None
everywhere.
"""

from __future__ import annotations

import bisect
import re

from _common import bench_module

#: a program family's name in the scope map by the harness's name for it
#: (``trace_reduce.PROGRAM_FAMILIES``: ``jit_multi*`` and ``jit_step*``)
FAMILY = {"decode": "multi", "prefill": "step"}

#: the shares a scope's seconds fall in, by the scope's family
PARTS = {
    "mixer": ("smg.attn", "smg.mla", "smg.linattn", "smg.ssm", "smg.kda"),
    "ffn": ("smg.mlp", "smg.moe", "smg.scmoe"),
    "head": ("smg.embed", "smg.lm_head", "smg.sample"),
    "frame": ("smg.frame",),
}
#: a part of ``ffn``: choosing the experts and carrying rows to and from them
ROUTING = ("smg.moe.route", "smg.moe.dispatch", "smg.moe.combine")

HEAD_CHARS = 160  # runtime_guards.HEAD_CHARS: a head is cut there on both sides

_NAME = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")


def head(event_name: str) -> str:
    """An event's head: the instruction's name and result shape, the text
    before the opcode, as the scope map has it."""
    m = _NAME.match(event_name)
    if m is None:
        return event_name[:HEAD_CHARS]
    i = m.end()
    if event_name.startswith("(", i):  # a tuple's shape
        depth, j = 0, i
        while j < len(event_name):
            depth += (event_name[j] == "(") - (event_name[j] == ")")
            j += 1
            if depth == 0:
                break
    else:
        j = event_name.find(" ", i)
        j = len(event_name) if j < 0 else j
    return f"{m.group(1)} = {event_name[i:j]}"[:HEAD_CHARS]


def under(scope: str, families) -> bool:
    return any(scope == f or scope.startswith(f + ".") for f in families)


def part_of(scope: str) -> str:
    """``mixer``, ``ffn``, ``head``, ``frame``, ``unscoped`` or ``other``."""
    if not scope:
        return "unscoped"
    return next((p for p, fams in PARTS.items() if under(scope, fams)), "other")


def launches_of(dev: dict, family: str) -> "tuple[float, dict]":
    """Of one device: the device seconds of the family's launches on the XLA
    Modules line, and ``{module name: [[leaf event, ...] of one launch,
    ...]}``, the leaf operations that start inside each.  A launch that goes
    on behind the device's last operation is left out of both: the trace (or
    the stretch of it that was kept) ends inside it."""
    tr = bench_module("trace_reduce")
    prefixes = tr.PROGRAM_FAMILIES[family]
    last = max((s + d for _n, s, d in dev["ops"]), default=0.0)
    spans = sorted((s, s + d, name) for name, s, d in dev["modules"]
                   if tr._base(name).startswith(prefixes) and s + d <= last + 1e-6)
    starts = [a for a, _b, _n in spans]
    inside = [[] for _ in spans]
    for ev in tr.leaves(dev["ops"]):
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < spans[i][1]:
            inside[i].append(ev)
    out: dict = {}
    for (_a, _b, name), evs in zip(spans, inside):
        out.setdefault(name, []).append(evs)
    return sum(b - a for a, b, _n in spans), out


def resolve(launches: list, programs: dict) -> "dict | None":
    """``{head: scope}`` of the program among ``programs`` (``{key: {head:
    scope}}``) whose heads cover most of the launches' leaf operations, None
    where none covers half of them."""
    heads = [head(ev[0]) for evs in launches for ev in evs]
    best, best_n = None, 0
    for lookup in programs.values():
        n = sum(1 for h in heads if h in lookup)
        if n > best_n:
            best, best_n = lookup, n
    return best if heads and 2 * best_n >= len(heads) else None


def split(trace: dict, scopes: dict, family: str, heads_under: str = "") -> "dict | None":
    """Where the device seconds of the family's launches went, averaged over
    the devices: ``family_s`` (the whole launches on the XLA Modules line),
    ``leaf_s`` (the leaf operations inside them; the rest is gaps inside a
    launch), ``scopes`` (``{scope: seconds}``, ``""`` the unscoped),
    ``adopted`` (the part of each scope's seconds that came by ``"~" +
    scope``), ``unresolved_s`` and ``stale_s`` (leaf seconds of launches no
    map covers, and of programs whose map is marked stale: both also in
    ``scopes[""]``) and ``heads`` (``{head: seconds}`` of the scope
    ``heads_under``: the unscoped, unless another is asked for).  None where
    the family did not run or the map has no program of it."""
    programs = {key: {h: sc for sc, hs in p["scopes"].items() for h in hs}
                for key, p in scopes.items() if p["family"] == FAMILY[family]}
    if not programs:
        return None
    stale = [programs[key] for key in programs if scopes[key].get("stale")]
    out = {"family_s": 0.0, "leaf_s": 0.0, "scopes": {}, "adopted": {},
           "unresolved_s": 0.0, "stale_s": 0.0, "heads": {}}

    def add(key: str, name: str, seconds: float) -> None:
        out[key][name] = out[key].get(name, 0.0) + seconds

    devices = list(trace["devices"].values())
    for dev in devices:
        seconds, by_module = launches_of(dev, family)
        out["family_s"] += seconds
        for launches in by_module.values():
            lookup = resolve(launches, programs)
            for name, _s, d in (ev for evs in launches for ev in evs):
                h = head(name)
                scope = lookup.get(h, "") if lookup is not None else ""
                out["leaf_s"] += d
                if lookup is None:
                    out["unresolved_s"] += d
                elif any(lookup is m for m in stale):
                    out["stale_s"] += d
                if scope.startswith("~"):
                    scope = scope[1:]
                    add("adopted", scope, d)
                add("scopes", scope, d)
                if scope == heads_under:
                    add("heads", h, d)
    if not out["family_s"]:
        return None
    n = len(devices)
    return {k: ({kk: vv / n for kk, vv in v.items()} if isinstance(v, dict) else v / n)
            for k, v in out.items()}


_KEPT: dict = {}  # (id(trace), family) -> (trace, split): nine readers, one walk a family


def split_of(ctx, family: str) -> "dict | None":
    """``split`` of the context's trace, None without a trace or a map."""
    trace = ctx.get("trace")
    scopes = ((ctx.get("loads_after") or {}).get("programs") or {}).get("scopes")
    if trace is None or not scopes:
        return None
    key = (id(trace), family)
    if key not in _KEPT or _KEPT[key][0] is not trace:
        _KEPT[key] = (trace, split(trace, scopes, family))
    return _KEPT[key][1]


def seconds_in(sp: dict, part: str) -> float:
    """Seconds of ``split`` ``sp`` in one part (``PARTS``' names, ``routing``,
    ``unscoped`` or ``other``)."""
    if part == "routing":
        return sum(s for sc, s in sp["scopes"].items() if under(sc, ROUTING))
    return sum(s for sc, s in sp["scopes"].items() if part_of(sc) == part)


def share(ctx, family: str, part: str) -> "float | None":
    """Percent of the family's device seconds in ``part``; None where there
    is no map, no launch of the family, or (but for ``unscoped``) no second
    under such a scope: a model without routed experts has no routing share.
    Where a stale executable ran, a part that reads nothing is 0 and not
    None: its seconds are there, under no scope."""
    sp = split_of(ctx, family)
    if sp is None:
        return None
    seconds = seconds_in(sp, part)
    if not seconds and part != "unscoped" and not sp["stale_s"]:
        return None
    return 100.0 * seconds / sp["family_s"]
