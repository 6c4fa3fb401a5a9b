"""Runtime complements to the static rules: transfer, recompile, and
lock-order guards.

Static analysis catches the patterns; these guards catch the *effects* on
the real engine, wired into ``tests/test_analysis.py``:

- :func:`no_implicit_transfers` — ``jax.transfer_guard("disallow")`` around
  the steady-state decode section.  The hot path performs its intended
  transfers explicitly (``jax.device_put`` uploads in
  ``runner.decode_multi_async``, ``jax.device_get`` fetches in
  ``scheduler._consume_frame``), so under the guard any IMPLICIT transfer —
  a stray ``.item()``, a numpy scalar leaking into device math, a host
  array hitting a jit boundary — raises instead of silently stalling the
  pipeline;
- :class:`CompileCounter` — counts XLA backend compiles via
  ``jax.monitoring``.  After warmup, steady-state decode must compile
  nothing: a nonzero count is a retrace regression even when throughput
  noise hides the stall;
- :class:`ProgramAuditor` / :func:`program_audit` — the compiled-program
  auditor behind the TRACEPURE/DONATE/SHARDDISC static rules.  The runner
  registers every cached jit family (``runner._compiled``) through
  :meth:`ProgramAuditor.wrap` together with its committed ``in_shardings``
  and intended ``donate_argnums``; once ARMED (post-warmup), each launch
  captures per-argument specs (shape/dtype/sharding/committed flag) at
  negligible overhead, and :func:`program_audit` then asserts from the
  lowered/compiled representation that (1) every steady-state input's
  sharding matches the mesh commitment — no implicit per-launch reshard,
  (2) every intended donation actually aliased an output
  (``input_output_alias`` in the compiled HLO — donation silently no-ops
  on mismatch), and (3) any recompile carries PROVENANCE: which argument's
  shape/dtype/sharding changed between the two launches (the compile
  counter says "a recompile happened"; this says why).  Surfaced via
  ``Engine.loads()["programs"]`` and the ``program_audit`` CI section;
- :func:`lock_order_sentinel` — lockdep-style dynamic lock-order tracking,
  the runtime twin of the LOCKORDER static rule.  The static rule sees only
  lexical nesting; the sentinel sees the real graph (an engine-lock holder
  calling into the recorder's lock crosses a function boundary no AST walk
  follows).  Locks created through :func:`make_lock` while the sentinel is
  armed (the context manager, or ``SMG_LOCK_SENTINEL=1`` in the
  environment) are wrapped in :class:`SentinelLock`; each first-depth
  acquisition records an order edge from every lock the thread already
  holds, with the acquiring stack captured on the edge's first observation.
  An edge whose reverse already exists is an inversion: it is recorded with
  BOTH stacks and, at context exit (or immediately under the env flag),
  raises :class:`LockOrderError`.  Identity is per *lock name* (lock class,
  lockdep-style), not per instance — the order contract "breaker before
  worker" is a class-level rule.  Unarmed, ``make_lock`` returns the plain
  ``threading`` primitive: zero overhead in production.

jax is imported lazily so the lint-only CLI stays jax-free.
"""

from __future__ import annotations

import os
import re
import threading
import traceback
from contextlib import contextmanager

# every XLA backend compile records this event (jax>=0.4 monitoring)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compile_count = 0
_listener_installed = False
#: ``.scope_map`` is set on the thread that makes a program's scope map: should
#: that lower and compile (the launch's own lowering is found as a rule), it is
#: no program's first compile and no retrace
_thread = threading.local()


def _on_event(name: str, *_args, **_kw) -> None:
    global _compile_count
    if _COMPILE_EVENT in name and not getattr(_thread, "scope_map", False):
        _compile_count += 1


def _ensure_listener() -> None:
    """Install the monitoring listener once per process.  jax.monitoring has
    no unregister API short of clearing ALL listeners, so the module keeps a
    single monotonic counter and :class:`CompileCounter` instances snapshot
    it."""
    global _listener_installed
    if _listener_installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event)
    _listener_installed = True


def _logger():
    from smg_tpu.utils import get_logger

    return get_logger("analysis.runtime_guards")


def compile_count() -> int:
    """Monotonic count of XLA backend compiles observed so far (0 until the
    first guard/counter installs the listener)."""
    return _compile_count


class CompileCounter:
    """Context manager counting XLA compiles inside the ``with`` block::

        with CompileCounter() as cc:
            engine.step()
        assert cc.count == 0, "steady-state decode recompiled"
    """

    def __init__(self) -> None:
        self._start = 0
        self.count = 0

    def __enter__(self) -> "CompileCounter":
        _ensure_listener()
        self._start = _compile_count
        return self

    def __exit__(self, *exc) -> None:
        self.count = _compile_count - self._start


@contextmanager
def no_implicit_transfers():
    """Raise on any implicit host↔device transfer inside the block.

    Explicit ``jax.device_put`` / ``jax.device_get`` — the forms the hot
    path uses for its intended per-step traffic — stay allowed, so this is
    precisely "no transfer the code didn't ask for by name"."""
    import jax

    with jax.transfer_guard("disallow"):
        yield


@contextmanager
def steady_state_guard(max_compiles: int = 0):
    """Both guards at once, for wrapping post-warmup decode steps::

        with steady_state_guard() as cc:
            for _ in range(8):
                engine.step()

    Raises RuntimeError when the block compiled more than ``max_compiles``
    XLA programs; implicit transfers raise from inside jax at the offending
    call (with a stack trace pointing at the violator — better than any
    after-the-fact count)."""
    with no_implicit_transfers():
        with CompileCounter() as cc:
            yield cc
    if cc.count > max_compiles:
        raise RuntimeError(
            f"steady-state section compiled {cc.count} XLA program(s) "
            f"(budget {max_compiles}): a jit signature changed per step — "
            "see the RETRACE rule docs in smg_tpu/analysis/rules/retrace.py"
        )


# ---- compiled-program auditor (program_audit) ----


def _describe_args(args):
    """Flatten a launch's argument tree into (signature, leaf-entries,
    spec-tree).  Each array leaf entry records path / shape / dtype /
    sharding (object + repr) / committed flag / device ids; non-array
    leaves are recorded as host-static.  The spec tree mirrors ``args``
    with ``ShapeDtypeStruct`` (a committed array's sharding attached) in
    place of arrays, so the auditor can re-lower the program later without
    holding buffers."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(args)
    entries = []
    spec_leaves = []
    for path, leaf in flat:
        pstr = jax.tree_util.keystr(path) or "<root>"
        if isinstance(leaf, jax.Array):
            sh = leaf.sharding
            entries.append({
                "path": pstr,
                "shape": tuple(leaf.shape),
                "dtype": str(leaf.dtype),
                "sharding": repr(sh),
                "committed": bool(getattr(leaf, "committed", True)),
                "devices": tuple(sorted(d.id for d in sh.device_set)),
                "_sharding": sh,
            })
            # an uncommitted array's placement is no part of the program's
            # signature: with it left out, ``fn.lower(*specs)`` finds the
            # lowering the launch itself made and ``.compile()`` the
            # executable that runs, and nothing is lowered or compiled again
            spec_leaves.append(jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=sh if entries[-1]["committed"] else None))
        else:
            entries.append({
                "path": pstr, "shape": None, "dtype": type(leaf).__name__,
                "sharding": None, "committed": True, "devices": (),
                "_sharding": None,
            })
            spec_leaves.append(leaf)
    sig = tuple(
        (e["path"], e["shape"], e["dtype"], e["sharding"]) for e in entries
    )
    return sig, entries, jax.tree_util.tree_unflatten(treedef, spec_leaves)


def _sig_diff(old: list[dict], new: list[dict]) -> list[dict]:
    """Which argument changed between two launch signatures — the
    recompile's PROVENANCE.  Compares leaf-wise; a structural change
    (different leaf count) is reported as such."""
    if len(old) != len(new):
        return [{"arg": "<tree>", "field": "structure",
                 "before": len(old), "after": len(new)}]
    out = []
    for o, n in zip(old, new):
        for field in ("shape", "dtype", "sharding"):
            if o[field] != n[field]:
                out.append({
                    "arg": n["path"], "field": field,
                    "before": o[field], "after": n[field],
                })
    return out


def _count_output_aliases(hlo_text: str) -> int:
    """Number of aliased entries in the compiled module's
    ``input_output_alias={...}`` attribute (brace-matched — the entries
    themselves contain nested ``{}``)."""
    marker = "input_output_alias={"
    start = hlo_text.find(marker)
    if start < 0:
        return 0
    i = start + len(marker)
    depth = 1
    buf = []
    while i < len(hlo_text) and depth:
        c = hlo_text[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        if depth:
            buf.append(c)
        i += 1
    # entries look like `{0}: (0, {}, may-alias)` / `{1}: (4, {}, ...)`
    return len(re.findall(r"\{[0-9, ]*\}\s*:", "".join(buf)))


#: a head is cut here: a ``while``'s result shape names every buffer it carries
HEAD_CHARS = 160

#: opcodes that compute nothing and so are no event of a trace
_NO_EVENT = frozenset(("parameter", "constant", "get-tuple-element", "tuple", "bitcast"))

#: opcodes that run other computations
_RUNS_OTHERS = frozenset(("while", "conditional", "call"))

_INSTRUCTION = re.compile(r"\s*(?:ROOT )?(%?[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOCATION = re.compile(r'loc\("([^"]*)"')
_SCOPE = re.compile(r"smg\.[\w.]+")
_FUSED = re.compile(r"\bfusion\(.*\bcalls=(%?[\w.\-]+)")
_APPLIED = re.compile(r"\bto_apply=(%?[\w.\-]+)")
_OPERAND = re.compile(r"%[\w.\-]+")


class _Frozen(dict):
    """A dict that ``json`` writes as one and that nobody changes: a scope
    map is handed out by reference."""

    def _refuse(self, *_a, **_kw):
        raise TypeError("a scope map is immutable")

    __setitem__ = __delitem__ = _refuse
    clear = pop = popitem = setdefault = update = __ior__ = _refuse


def _closing(text: str, i: int) -> int:
    """The index behind the parenthesis that closes the one at ``i``."""
    depth = 0
    for j in range(i, len(text)):
        depth += (text[j] == "(") - (text[j] == ")")
        if depth == 0:
            return j + 1
    return len(text)


def instruction_head(text: str) -> "tuple[str, str, str] | None":
    """``(head, name, rest)`` of one HLO instruction as ``as_text()`` and a
    trace's event name print it: the head is the name and the result shape
    with its layout (``%fusion.7 = bf16[64,128]{1,0:T(8,128)(2,1)}``), the text
    before the opcode, cut at ``HEAD_CHARS``; ``rest`` starts at the opcode.
    None for a line that is no instruction."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return None
    i = m.end()
    if text.startswith("(", i):  # a tuple's shape
        j = _closing(text, i)
    else:
        j = text.find(" ", i)
        if j < 0:
            return None
    head = f"{m.group(1).lstrip('%')} = {text[i:j]}"[:HEAD_CHARS]
    return head, m.group(1).lstrip("%"), text[j:].lstrip()


def scope_of(op_name: str) -> str:
    """The innermost ``smg.*`` component of an instruction's ``op_name``
    (``jit(multi)/while/body/smg.mlp/dot_general`` -> ``smg.mlp``), else ""."""
    for part in reversed(op_name.split("/")):
        m = _SCOPE.search(part)
        if m is not None:
            return m.group(0).rstrip(".")
    return ""


def _operands(rest: str) -> list:
    """The names of an instruction's operands (``rest`` starts at its opcode)."""
    i = rest.find("(")
    if i < 0:
        return []
    return [m.lstrip("%") for m in _OPERAND.findall(rest[i:_closing(rest, i)])]


def scope_names(names) -> frozenset:
    """Every ``smg.*`` component of the operation names ``names``, the outer
    ones with the innermost."""
    return frozenset(m.rstrip(".") for name in names for m in _SCOPE.findall(name))


def scopes_of_hlo(hlo_text: str) -> dict:
    """``{scope: (heads, ...)}`` of a compiled module's text, ``""`` holding
    the heads under no scope: every instruction of the entry computation, of
    ``while`` bodies and conditions and of called computations.  The insides
    of fused computations and of the scalar computations a reduction applies
    are left out (a trace shows the fusion, not its parts), and so is what
    computes nothing (``_NO_EVENT``).  A Pallas custom call that carries its
    scope as its own name maps to it.  An instruction under no scope whose
    readers all stand under one scope is listed under ``"~" + scope``: a
    relayout before a product, a weight's copy into the fast memory before
    the fusion that reads it (a reader that computes nothing and names no
    scope is seen through).  Nothing else is guessed: what the program's
    result alone reads, and what a loop under no scope is handed, stay under
    no scope."""
    computations: dict = {}  # name -> [instruction line, ...], in the text's order
    inner: set = set()  # fused and applied computations
    body = None
    for line in hlo_text.splitlines():
        if body is None:
            if line.endswith("{") and ") -> " in line:
                name = line.split("(", 1)[0].split()[-1]
                body = computations.setdefault(name.lstrip("%"), [])
            continue
        if line.startswith("}"):
            body = None
            continue
        body.append(line)
        fused = _FUSED.search(line)
        if fused is not None:
            inner.add(fused.group(1).lstrip("%"))
        elif " call(" not in line:
            applied = _APPLIED.search(line)
            if applied is not None:
                inner.add(applied.group(1).lstrip("%"))
    # instruction names are the module's own: one table for all computations
    scope_of_name: dict = {}  # instruction -> the scope its own metadata names, or ""
    head_of: dict = {}  # of the instructions a trace can show, in the text's order
    consumers: dict = {}
    runs_others: set = set()  # loops, branches, calls: their events enclose others
    for name, lines in computations.items():
        if name in inner:
            continue
        for line in lines:
            parsed = instruction_head(line)
            if parsed is None:
                continue
            head, iname, rest = parsed
            for operand in _operands(rest):
                consumers.setdefault(operand, []).append(iname)
            op_name = _OP_NAME.search(rest)
            scope = scope_of(op_name.group(1)) if op_name is not None else ""
            if not scope and iname.startswith("smg."):
                scope = re.sub(r"\.\d+$", "", iname)
            scope_of_name[iname] = scope
            opcode = rest.split("(", 1)[0]
            if opcode not in _NO_EVENT:
                head_of[iname] = head
            if opcode in _RUNS_OTHERS:
                runs_others.add(iname)
    adopted: dict = {}

    def readers(iname) -> set:
        """The scopes of what reads ``iname``, "" among them for a reader
        under no scope."""
        found = set()
        for c in consumers.get(iname, ()):
            sc = scope_of_name.get(c, "")
            if sc or c in head_of:
                found.add(sc or adopted.get(c, ""))
            else:
                found |= readers(c)
        return found

    # a consumer stands behind its operands: the readers are settled first.  A
    # loop takes no reader's scope (what reads its results says nothing of
    # what it is handed), so nothing reaches a scope through one
    for iname in reversed(head_of):
        if not scope_of_name[iname] and iname not in runs_others:
            found = readers(iname)
            if len(found) == 1 and "" not in found:
                adopted[iname] = found.pop()
    out: dict = {"": []}
    for iname, head in head_of.items():
        scope = scope_of_name[iname] or ("~" + adopted[iname] if iname in adopted else "")
        out.setdefault(scope, []).append(head)
    return _Frozen({scope: tuple(heads) for scope, heads in out.items()})


class _ProgramRecord:
    __slots__ = ("key", "fn", "donate", "in_shardings", "launches",
                 "recompiles", "last_sig", "last_entries", "last_specs",
                 "provenance", "first_specs", "scopes", "stale")

    def __init__(self, key, fn, donate, in_shardings):
        self.key = key
        self.fn = fn
        self.donate = tuple(donate or ())
        self.in_shardings = in_shardings
        self.launches = 0
        self.recompiles = 0
        self.last_sig = None
        self.last_entries = None
        self.last_specs = None
        self.provenance: list[dict] = []
        self.first_specs = None  # the first launch's argument specs
        self.scopes = None  # scope_map()'s result, built once
        self.stale = False  # the executable carries another commit's scopes


class ProgramAuditor:
    """Registry + launch interceptor for every cached compiled program.

    The runner routes each jit family through :meth:`wrap` at cache-fill
    time, declaring the family's intended donation positions and (in mesh
    mode) the committed input shardings.  Always, the wrapper counts the
    launch and reads the process compile counter on either side of it (two
    integer reads and an add), so ``launches``, ``recompiles`` and the
    snapshot's ``compiles`` mean something on a server nobody armed: a
    program that compiles on any launch after its first has been retraced.
    Armed (:meth:`arm`, post-warmup), each launch also snapshots the
    argument tree's shapes/dtypes/shardings BEFORE dispatch (donation
    invalidates input buffers afterwards) — so a steady-state launch that
    compiles gets a provenance entry naming exactly which argument's
    shape/dtype/sharding differed from the previous launch.

    :meth:`audit` then re-lowers each captured program from its specs and
    checks the compiled representation itself: committed-sharding
    conformance for every input, and ``input_output_alias`` coverage for
    every intended donation.
    """

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._records: dict = {}
        self.armed = False
        # what publish_scopes() has made: {repr(key): {"family", "scopes"}},
        # replaced whole and never changed, so snapshot() hands it out as it is
        self._published = _Frozen()
        self.scope_lowerings = 0
        self.scope_stale = 0  # of them: programs whose executable names other scopes
        self.scope_seconds = 0.0
        _ensure_listener()  # jax is imported wherever a program is built

    # ---- registration / launch path ----

    def wrap(self, key, fn, *, donate=(), in_shardings=None):
        """Register compiled-program ``fn`` under ``key`` and return the
        launch wrapper the runner caches in its place."""
        rec = _ProgramRecord(key, fn, donate, in_shardings)
        with self._mu:
            self._records[key] = rec

        def launch(*args):
            if not self.armed:
                # launches come from the step thread alone; a compile on
                # another thread inside the call would be miscounted here,
                # which the armed pass (with provenance) is there to settle
                # (the scope maps' own lowerings are not counted at all)
                if rec.launches:
                    pre = _compile_count
                    out = fn(*args)
                    rec.recompiles += _compile_count - pre
                else:
                    # the first launch, in warm-up: what scope_map() lowers
                    # the program from, taken before donation ends the buffers
                    rec.first_specs = _describe_args(args)[2]
                    out = fn(*args)
                rec.launches += 1
                return out
            sig, entries, specs = _describe_args(args)
            pre = _compile_count
            out = fn(*args)
            compiled = _compile_count - pre
            with self._mu:
                rec.launches += 1
                if compiled and rec.last_sig is not None:
                    rec.recompiles += compiled
                    changed = _sig_diff(rec.last_entries, entries)
                    rec.provenance.append({
                        "key": repr(key),
                        "compiles": compiled,
                        "changed": changed or
                        [{"arg": "<none>", "field": "unknown",
                          "before": None, "after": None}],
                    })
                rec.last_sig = sig
                rec.last_entries = entries
                rec.last_specs = specs
            return out

        launch.__wrapped__ = fn
        return launch

    def arm(self) -> None:
        """Start capturing launch signatures (call after warmup)."""
        _ensure_listener()
        self.armed = True

    def disarm(self) -> None:
        self.armed = False

    def forget(self, keys) -> None:
        """Drop records for invalidated programs (runner cache eviction)."""
        with self._mu:
            for k in list(keys):
                self._records.pop(k, None)

    # ---- reporting ----

    def snapshot(self) -> dict:
        """Cheap JSON-safe summary for ``Engine.loads()["programs"]`` —
        no lowering, no compilation."""
        with self._mu:
            programs = [
                {
                    "key": repr(rec.key),
                    "launches": rec.launches,
                    "recompiles": rec.recompiles,
                    "donate": list(rec.donate),
                    "audited": rec.last_sig is not None,
                }
                for rec in self._records.values()
            ]
        out = {
            "armed": self.armed,
            # every XLA compile of the process since this auditor's listener
            # went in, the programs' first compiles included; what a scope
            # map lowered again is counted apart
            "compiles": _compile_count,
            "recompiles": sum(p["recompiles"] for p in programs),
            "programs": programs,
            "scope_lowerings": self.scope_lowerings,
            "scope_stale": self.scope_stale,
            "scope_seconds": self.scope_seconds,
        }
        if self._published:
            out["scopes"] = self._published  # by reference: nothing is copied
        return out

    def launch_counts(self) -> dict:
        """``{key: launches}`` of every registered program."""
        with self._mu:
            return {k: rec.launches for k, rec in self._records.items()}

    def scope_map(self, key) -> "dict | None":
        """``{scope: (heads, ...)}`` of program ``key`` (``scopes_of_hlo``):
        which ``smg.*`` named scope each instruction of the compiled program
        was traced under, by the head a trace prints for it.  Read off
        ``lower(*specs).compile().as_text()`` as ``audit()`` reads a
        donation, from the specs of the program's first launch (an armed
        auditor's last): those find the launch's own lowering and the
        executable that runs, so as a rule nothing is lowered or compiled
        again, and where something is, the thread is marked and it counts as
        no program's recompile and not among ``compiles``.

        **A stale executable names nothing.**  The compile cache's key leaves
        metadata out, so the executable that runs may be one an earlier commit
        compiled, with that commit's scopes.  The lowering is this process's
        own: where the scope names in its locations are not the names in the
        executable's text, the program counts in ``scope_stale``, is logged,
        and every head of it is listed under no scope, so its launches read as
        unscoped time and never as another commit's split.

        Once: the map is kept, and a second call hands out the same object.
        Tenths of a second a program on a chip (the text is megabytes): never
        call it on the step thread.  None for a program that was never
        launched."""
        with self._mu:
            rec = self._records.get(key)
        if rec is None:
            return None
        if rec.scopes is None:
            specs = rec.last_specs if rec.last_specs is not None else rec.first_specs
            if specs is None:
                return None
            _thread.scope_map = True
            try:
                lowered = rec.fn.lower(*specs)
                text = lowered.compile().as_text()
            finally:
                _thread.scope_map = False
            scopes = scopes_of_hlo(text)
            traced = scope_names(_LOCATION.findall(lowered.as_text(debug_info=True)))
            runs = scope_names(_OP_NAME.findall(text)) | scope_names(
                s for s in scopes if not s.startswith("~"))  # a kernel named by its scope
            if traced != runs:
                _logger().warning(
                    "program %r runs an executable of another commit (the compile cache's "
                    "key has no metadata): it names %s and not %s; its launches read as "
                    "unscoped", key, sorted(runs - traced), sorted(traced - runs))
                scopes = _Frozen({"": tuple(h for heads in scopes.values() for h in heads)})
                rec.stale = True
                self.scope_stale += 1
            rec.scopes = scopes
            self.scope_lowerings += 1
        return rec.scopes

    def publish_scopes(self, keys) -> dict:
        """Make the scope maps of the programs ``keys`` and put them in
        ``snapshot()["scopes"]`` beside what is there: ``{repr(key):
        {"family", "scopes"}}``, with ``"stale": True`` for a program whose
        executable another commit compiled (``scope_map``).  A program whose
        lowering fails is left out and logged: a reader then finds its
        launches covered by no map, which shows.  Returns what it added."""
        import time

        t0 = time.monotonic()
        added = {}
        for key in keys:
            try:
                scopes = self.scope_map(key)
            except Exception:
                _logger().exception("no scope map of program %r", key)
                continue
            if scopes is not None:
                with self._mu:
                    rec = self._records[key]
                entry = {"family": getattr(rec.fn, "__name__", ""), "scopes": scopes}
                if rec.stale:
                    entry["stale"] = True
                added[repr(key)] = _Frozen(entry)
        if added:
            self._published = _Frozen({**self._published, **added})
        self.scope_seconds += time.monotonic() - t0
        return added

    def audit(self, *, check_donation: bool = True) -> dict:
        """Walk every captured program and verify it from the compiled
        representation.  Returns a report dict; ``report["clean"]`` is the
        single go/no-go bit (0 uncommitted/mismatched inputs, every
        intended donation verified-aliased)."""
        import jax

        with self._mu:
            records = list(self._records.values())
        programs = []
        uncommitted = mismatched = unverified = recompiles = 0
        for rec in records:
            entry: dict = {
                "key": repr(rec.key),
                "launches": rec.launches,
                "recompiles": rec.recompiles,
                "provenance": list(rec.provenance),
                "audited": rec.last_sig is not None,
            }
            recompiles += rec.recompiles
            if rec.last_sig is None:
                programs.append(entry)
                continue
            array_entries = [e for e in rec.last_entries
                             if e["_sharding"] is not None]
            bad_inputs = []
            if rec.in_shardings is not None:
                committed = [
                    s for s in jax.tree_util.tree_leaves(rec.in_shardings)
                    if isinstance(s, jax.sharding.Sharding)
                ]
                if len(committed) != len(array_entries):
                    entry["sharding_check"] = (
                        f"structure mismatch: {len(committed)} committed "
                        f"shardings vs {len(array_entries)} array inputs"
                    )
                    mismatched += 1
                else:
                    for e, want in zip(array_entries, committed):
                        if not e["committed"]:
                            bad_inputs.append({
                                "arg": e["path"], "why": "uncommitted",
                                "sharding": e["sharding"],
                            })
                            uncommitted += 1
                        elif not e["_sharding"].is_equivalent_to(
                            want, len(e["shape"])
                        ):
                            bad_inputs.append({
                                "arg": e["path"],
                                "why": "sharding mismatch (implicit reshard "
                                       "at every launch)",
                                "sharding": e["sharding"],
                                "committed": repr(want),
                            })
                            mismatched += 1
            else:
                # single-device mode: every input must sit on ONE device
                # and all inputs on the SAME one — anything else is a
                # cross-device transfer per launch
                placements = {e["devices"] for e in array_entries
                              if e["devices"]}
                if len(placements) > 1 or any(
                    len(d) > 1 for d in placements
                ):
                    for e in array_entries:
                        if len(e["devices"]) != 1:
                            bad_inputs.append({
                                "arg": e["path"],
                                "why": "spans multiple devices in "
                                       "single-device mode",
                                "sharding": e["sharding"],
                            })
                            mismatched += 1
            if bad_inputs:
                entry["bad_inputs"] = bad_inputs
            if check_donation and rec.donate:
                try:
                    lowered = rec.fn.lower(*rec.last_specs)
                    intended = sum(
                        1 for ai in jax.tree_util.tree_leaves(
                            lowered.args_info)
                        if getattr(ai, "donated", False)
                    )
                    aliased = _count_output_aliases(
                        lowered.compile().as_text()
                    )
                    verified = aliased >= intended
                    entry["donation"] = {
                        "declared": list(rec.donate),
                        "intended": intended,
                        "aliased": aliased,
                        "verified": verified,
                    }
                    if not verified:
                        unverified += 1
                except Exception as exc:  # pragma: no cover - defensive
                    entry["donation"] = {
                        "declared": list(rec.donate),
                        "error": f"{type(exc).__name__}: {exc}",
                        "verified": False,
                    }
                    unverified += 1
            programs.append(entry)
        return {
            "armed": self.armed,
            "programs": programs,
            "uncommitted_inputs": uncommitted,
            "sharding_mismatches": mismatched,
            "donation_unverified": unverified,
            "recompiles": recompiles,
            "clean": not (uncommitted or mismatched or unverified),
        }


def program_audit(target, *, check_donation: bool = True) -> dict:
    """Audit every cached compiled program of ``target`` — a
    :class:`ProgramAuditor`, or anything exposing one as ``_programs``
    (the runner) or ``runner._programs`` (the engine)::

        eng.warmup(); eng.runner._programs.arm()
        ...steady-state traffic...
        report = program_audit(eng)
        assert report["clean"], report

    Asserts from the lowered/compiled representation: committed-sharding
    conformance for every captured input, ``input_output_alias`` coverage
    for every intended donation, and recompile provenance for any
    signature change observed while armed."""
    auditor = target
    for attr in ("runner", "_programs"):
        nxt = getattr(auditor, attr, None)
        if nxt is not None and not isinstance(auditor, ProgramAuditor):
            auditor = nxt
    if not isinstance(auditor, ProgramAuditor):
        raise TypeError(
            f"program_audit: no ProgramAuditor reachable from {target!r}"
        )
    return auditor.audit(check_donation=check_donation)


# ---- lock-order sentinel (the LOCKORDER rule's runtime twin) ----

#: env flag arming a process-global sentinel that raises AT THE ACQUISITION
#: that completes an inversion — turning any test that trips one into a
#: loud failure with both stacks, no harness changes needed
SENTINEL_ENV = "SMG_LOCK_SENTINEL"


class LockOrderError(RuntimeError):
    """A lock-order inversion, reported with both acquisition stacks."""


class LockOrderSentinel:
    """Dynamic lock-order graph: nodes are lock NAMES, an edge A->B means
    some thread acquired B while holding A.  The reverse edge appearing is
    an inversion (a 2-cycle — the classic ABBA deadlock shape); it is
    recorded with the stack that created the first edge and the stack that
    closed the cycle.  The graph and inversion list live under a plain
    internal lock (never a SentinelLock — the sentinel must not watch
    itself)."""

    def __init__(self, raise_on_inversion: bool = False):
        self.raise_on_inversion = raise_on_inversion
        self._mu = threading.Lock()
        # (holder, acquired) -> stack captured when the edge first appeared
        self._edges: dict[tuple[str, str], str] = {}
        self.inversions: list[dict] = []
        self._held = threading.local()

    # ---- per-acquisition hooks (called by SentinelLock at depth 0/1) ----

    def note_acquire(self, name: str) -> None:
        held: list[str] = getattr(self._held, "names", None)
        if held is None:
            held = self._held.names = []
        # racy fast-path pre-check, re-verified under self._mu below: a
        # stale miss only costs one extra stack capture, never a lost edge
        new_edges = [(h, name) for h in held if h != name
                     and (h, name) not in self._edges]  # smglint: disable=GUARDED benign pre-check, rechecked under _mu
        held.append(name)
        if not new_edges:
            return
        stack = "".join(traceback.format_stack(limit=16)[:-2])
        fresh = 0
        with self._mu:
            for edge in new_edges:
                if edge in self._edges:
                    continue
                self._edges[edge] = stack
                rev = self._edges.get((edge[1], edge[0]))
                if rev is not None:
                    fresh += 1
                    self.inversions.append({
                        "first": f"{edge[1]} -> {edge[0]}",
                        "first_stack": rev,
                        "second": f"{edge[0]} -> {edge[1]}",
                        "second_stack": stack,
                    })
        if fresh and self.raise_on_inversion:
            raise LockOrderError(self.format_inversions())

    def note_release(self, name: str) -> None:
        held = getattr(self._held, "names", None)
        if held:
            # remove the LAST occurrence: releases unwind LIFO, and an
            # out-of-order release of an aliased name must not strip the
            # wrong hold
            for i in range(len(held) - 1, -1, -1):
                if held[i] == name:
                    del held[i]
                    break

    def format_inversions(self) -> str:
        with self._mu:
            inversions = list(self.inversions)
        parts = [f"{len(inversions)} lock-order inversion(s):"]
        for inv in inversions:
            parts.append(
                f"\n=== {inv['second']} (conflicts with {inv['first']}) ===\n"
                f"--- stack that established {inv['first']} ---\n"
                f"{inv['first_stack']}"
                f"--- stack that closed the cycle ({inv['second']}) ---\n"
                f"{inv['second_stack']}"
            )
        return "".join(parts)


class SentinelLock:
    """Drop-in wrapper over a ``threading`` lock that reports first-depth
    acquisitions/releases to a :class:`LockOrderSentinel`.  Re-entrant
    acquisitions (RLock) are depth-counted and not re-reported.  Implements
    the ``threading.Condition`` owner protocol (``_release_save`` /
    ``_acquire_restore`` / ``_is_owned``) so a Condition built on a
    sentinel-wrapped (R)Lock keeps working — a ``wait()`` fully releases
    the hold and re-registers it on wakeup."""

    def __init__(self, name: str, inner, sentinel: LockOrderSentinel):
        self._name = name
        self._inner = inner
        self._sentinel = sentinel
        self._local = threading.local()

    # ---- core lock protocol ----

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            depth = getattr(self._local, "depth", 0)
            self._local.depth = depth + 1
            if depth == 0:
                try:
                    self._sentinel.note_acquire(self._name)
                except LockOrderError:
                    # raise-on-inversion mode: leave the lock UNHELD so the
                    # failing test's unwinding doesn't wedge other threads
                    self._local.depth = depth
                    self._sentinel.note_release(self._name)
                    self._inner.release()
                    raise
        return ok

    def release(self) -> None:
        self._inner.release()
        depth = getattr(self._local, "depth", 1) - 1
        self._local.depth = depth
        if depth == 0:
            self._sentinel.note_release(self._name)

    def __enter__(self) -> "SentinelLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._inner.locked()

    # ---- Condition owner protocol ----

    def _release_save(self):
        depth = getattr(self._local, "depth", 0)
        self._local.depth = 0
        if depth:
            self._sentinel.note_release(self._name)
        if hasattr(self._inner, "_release_save"):
            return (self._inner._release_save(), depth)
        self._inner.release()
        return (None, depth)

    def _acquire_restore(self, state) -> None:
        inner_state, depth = state
        if hasattr(self._inner, "_acquire_restore"):
            self._inner._acquire_restore(inner_state)
        else:
            self._inner.acquire()
        self._local.depth = depth
        if depth:
            self._sentinel.note_acquire(self._name)

    def _is_owned(self) -> bool:
        if hasattr(self._inner, "_is_owned"):
            return self._inner._is_owned()
        return getattr(self._local, "depth", 0) > 0


_ambient_sentinel: LockOrderSentinel | None = None


def _active_sentinel() -> LockOrderSentinel | None:
    global _ambient_sentinel
    if _ambient_sentinel is not None:
        return _ambient_sentinel
    if os.environ.get(SENTINEL_ENV, "").strip() not in ("", "0"):
        # env-armed: one process-global sentinel, inversions raise at the
        # offending acquisition (the test holding it fails with both stacks)
        _ambient_sentinel = LockOrderSentinel(raise_on_inversion=True)
        return _ambient_sentinel
    return None


def make_lock(name: str, *, reentrant: bool = False):
    """The adoption point: concurrency-critical locks (engine, flight
    recorder, breaker/worker/registry, route observability, SLO tracker)
    are created through this instead of ``threading.Lock()`` directly.
    Unarmed it returns the bare primitive — identical behavior, zero
    overhead; armed it returns a :class:`SentinelLock` participating in
    order tracking under ``name`` (the lock CLASS — instances share it)."""
    inner = threading.RLock() if reentrant else threading.Lock()
    sentinel = _active_sentinel()
    if sentinel is None:
        return inner
    return SentinelLock(name, inner, sentinel)


@contextmanager
def lock_order_sentinel(*, raise_on_inversion: bool = False):
    """Arm lock-order tracking for the block: locks created via
    :func:`make_lock` inside it are sentinel-wrapped.  Yields the
    :class:`LockOrderSentinel`; on exit, any recorded inversion raises
    :class:`LockOrderError` with both acquisition stacks::

        with lock_order_sentinel() as s:
            eng = build_engine(); run_workload(eng)
        # raises here if any two lock classes were taken in both orders

    ``raise_on_inversion=True`` raises at the acquisition that closes the
    cycle instead (pinpoints the offending call in the failing test's own
    traceback)."""
    global _ambient_sentinel
    prev = _ambient_sentinel
    sentinel = LockOrderSentinel(raise_on_inversion=raise_on_inversion)
    _ambient_sentinel = sentinel
    try:
        yield sentinel
    finally:
        _ambient_sentinel = prev
    if sentinel.inversions:
        raise LockOrderError(sentinel.format_inversions())
