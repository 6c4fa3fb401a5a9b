"""DONATE: use-after-donate on ``jit(..., donate_argnums=...)`` buffers.

Donation invalidates the caller's buffer AT DISPATCH: the runtime aliases
the input's memory to an output, and any later read sees deleted-array
errors on GPU/TPU — or, on CPU PJRT, blocks dispatch entirely (the trap
``engine/donation.py`` encodes as policy).  Donation is also silent about
mistakes: a ``donate_argnums`` position that doesn't exist, or one whose
shape/layout mismatch makes XLA drop the alias, simply no-ops.

For every jit site carrying ``donate_argnums`` the rule resolves the
donated positions (literal tuples, or the union of literal assignments to
a policy variable like ``donate = (4, 5) ... donate = ()``), finds the
dispatch call sites — immediate invocation, a local ``fn = jax.jit(...)``
then ``fn(...)``, or the runner's factory shape (``fn = self._prefill_fn(...)``
resolved through the defining class; the decode frame's positions are computed
from the frame's description in ``ModelRunner._decode_frame_fn`` and are held
by ``program_audit()``'s aliasing check instead), including ``fn(*args)`` against a
literal ``args = [...]`` prefix — and maps donated positions back to the
caller's argument expressions.  It flags:

- a read of a donated name or ``self.``-attribute after dispatch with no
  intervening reassignment (the use-after-donate itself);
- a donated ``self.``-resident buffer never reassigned after dispatch —
  the holder retains a deleted array for the NEXT caller to trip on
  (reassigning from the program outputs, ``..., self.k_cache, self.v_cache
  = out``, is the sanctioned pattern);
- donating a buffer reached through a non-self parameter (a DecodeState /
  shared-state object the caller does not own — the owner still holds it);
- ``donate_argnums`` positions past the callee's positional arity.
"""

from __future__ import annotations

import ast
from typing import Iterator

from smg_tpu.analysis.core import Finding, ModuleContext, dotted_name
from smg_tpu.analysis.rules.jaxcommon import (
    JIT_WRAPPERS,
    positional_arity,
    resolve_argnums,
    resolve_callable,
)


def _stmt_of(ctx: ModuleContext, node: ast.AST) -> ast.AST:
    """Nearest ancestor that is a statement (member of some body list)."""
    cur = node
    for anc in ctx.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module,
                            ast.If, ast.For, ast.While, ast.With, ast.Try)):
            return cur
        cur = anc
    return cur


def _enclosing_class(ctx: ModuleContext, node: ast.AST) -> ast.ClassDef | None:
    for anc in ctx.ancestors(node):
        if isinstance(anc, ast.ClassDef):
            return anc
    return None


def _span(node: ast.AST) -> tuple[tuple[int, int], tuple[int, int]]:
    return (
        (node.lineno, node.col_offset),
        (getattr(node, "end_lineno", node.lineno),
         getattr(node, "end_col_offset", node.col_offset)),
    )


def _literal_prefix(
    caller: ast.AST, name: str, before_line: int
) -> list[ast.AST] | None:
    """Elements of the last ``name = [e0, e1, ...]`` literal assignment
    before ``before_line`` in ``caller`` — the runner's ``args = [...]``
    then ``fn(*args)`` idiom.  Later ``args += [...]`` extensions stay
    unknown (positions past the prefix are skipped, not guessed)."""
    best: list[ast.AST] | None = None
    best_line = -1
    for n in ast.walk(caller):
        if (isinstance(n, ast.Assign) and n.lineno < before_line
                and best_line < n.lineno
                and any(isinstance(t, ast.Name) and t.id == name
                        for t in n.targets)
                and isinstance(n.value, (ast.List, ast.Tuple))):
            best, best_line = list(n.value.elts), n.lineno
    return best


def _donated_arg_exprs(
    caller: ast.AST, call: ast.Call, positions: set[int]
) -> list[tuple[int, ast.AST]]:
    """(donated position, caller argument expression) pairs that are
    statically mappable at this dispatch call."""
    out: list[tuple[int, ast.AST]] = []
    concrete: list[ast.AST | None] = []
    for a in call.args:
        if isinstance(a, ast.Starred):
            if isinstance(a.value, ast.Name):
                prefix = _literal_prefix(caller, a.value.id, call.lineno)
                if prefix is None:
                    return out
                concrete.extend(prefix)
            else:
                return out
        else:
            concrete.append(a)
    for p in sorted(positions):
        if p < len(concrete) and concrete[p] is not None:
            out.append((p, concrete[p]))
    return out


class DonateRule:
    id = "DONATE"
    description = "use-after-donate / invalid donation on a jit buffer"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) not in JIT_WRAPPERS:
                continue
            kw = next((k for k in node.keywords
                       if k.arg == "donate_argnums"), None)
            if kw is None:
                continue
            positions = resolve_argnums(ctx, node, kw.value)
            if positions is None or not positions:
                continue
            yield from self._check_site(ctx, node, positions)

    # ---- per-jit-site analysis ----

    def _check_site(
        self, ctx: ModuleContext, site: ast.Call, positions: set[int]
    ) -> Iterator[Finding]:
        target = site.args[0] if site.args else None
        body = resolve_callable(ctx, site, target) if target is not None else None
        if body is not None:
            arity = positional_arity(body)
            if arity is not None:
                label = getattr(body, "name", "<lambda>")
                for p in sorted(positions):
                    if p >= arity:
                        yield ctx.finding(
                            self.id, site,
                            f"donate_argnums position {p} does not exist: "
                            f"'{label}' takes {arity} positional arg(s) — "
                            "the donation silently no-ops",
                        )
        for caller, call in self._dispatch_sites(ctx, site):
            yield from self._check_dispatch(ctx, caller, call, positions)

    def _dispatch_sites(
        self, ctx: ModuleContext, site: ast.Call
    ) -> Iterator[tuple[ast.AST, ast.Call]]:
        """Dispatch calls of the jit built at ``site``: immediate invocation,
        local-name calls, and class-factory calls (``x = self.M(...)`` where
        method ``M`` builds and returns the jit)."""
        parent = ctx.parent(site)
        if isinstance(parent, ast.Call) and parent.func is site:
            caller = ctx.enclosing_function(site) or ctx.tree
            yield caller, parent
            return
        enclosing = ctx.enclosing_function(site)
        bound: str | None = None
        if isinstance(parent, ast.Assign):
            for t in parent.targets:
                if isinstance(t, ast.Name):
                    bound = t.id
        if bound and enclosing is not None:
            for n in ast.walk(enclosing):
                if (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                        and n.func.id == bound and n is not site):
                    yield enclosing, n
        # factory: callers elsewhere in the class do `x = self.M(...); x(...)`
        cls = _enclosing_class(ctx, site)
        if cls is None or enclosing is None:
            return
        mname = enclosing.name
        for method in cls.body:
            if not isinstance(method, ast.FunctionDef) or method is enclosing:
                continue
            handles: set[str] = set()
            for n in ast.walk(method):
                if (isinstance(n, ast.Assign) and isinstance(n.value, ast.Call)
                        and dotted_name(n.value.func) == f"self.{mname}"):
                    for t in n.targets:
                        if isinstance(t, ast.Name):
                            handles.add(t.id)
            if not handles:
                continue
            for n in ast.walk(method):
                if (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                        and n.func.id in handles):
                    yield method, n

    # ---- per-dispatch analysis ----

    def _check_dispatch(
        self, ctx: ModuleContext, caller: ast.AST, call: ast.Call,
        positions: set[int],
    ) -> Iterator[Finding]:
        stmt = _stmt_of(ctx, call)
        call_start, call_end = _span(call)
        stmt_end_line = getattr(stmt, "end_lineno", stmt.lineno)
        fn_params = set()
        if isinstance(caller, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_params = {a.arg for a in caller.args.args} - {"self"}
        for pos, expr in _donated_arg_exprs(caller, call, positions):
            name = dotted_name(expr)
            if not name:
                continue  # fresh temporary (e.g. _dev(...)) — caller holds no alias
            root = name.split(".", 1)[0]
            if "." in name and root in fn_params:
                yield ctx.finding(
                    self.id, expr,
                    f"donating '{name}' reached through parameter '{root}' — "
                    "the owner (DecodeState/shared state) still holds the "
                    "buffer and will read a deleted array; donate only "
                    "buffers this object owns",
                )
                continue
            yield from self._scan_after(
                ctx, caller, call, name, pos,
                call_start, call_end, stmt_end_line,
            )

    def _scan_after(
        self, ctx: ModuleContext, caller: ast.AST, call: ast.Call,
        name: str, pos: int, call_start, call_end, stmt_end_line: int,
    ) -> Iterator[Finding]:
        stmt_start_line = _stmt_of(ctx, call).lineno
        killed_in_stmt = False
        later: list[tuple[tuple[int, int], str, ast.AST]] = []
        for n in ast.walk(caller):
            if not isinstance(n, (ast.Name, ast.Attribute)):
                continue
            if dotted_name(n) != name:
                continue
            npos = (n.lineno, n.col_offset)
            if call_start <= npos <= call_end:
                continue  # the donated argument occurrence itself
            kind = ("store" if isinstance(n.ctx, (ast.Store, ast.Del))
                    else "load")
            if npos[0] < stmt_start_line:
                continue  # before dispatch — irrelevant
            if npos[0] <= stmt_end_line:
                # same statement as the dispatch: an LHS store
                # (`self.k_cache, ... = fn(...)`) kills the alias at once
                if kind == "store":
                    killed_in_stmt = True
                continue
            later.append((npos, kind, n))
        if killed_in_stmt:
            return
        later.sort(key=lambda e: e[0])
        for _pos, kind, n in later:
            if kind == "store":
                return  # reassigned before any read — the sanctioned pattern
            yield ctx.finding(
                self.id, n,
                f"'{name}' read after being donated (position {pos}) to a "
                "jit dispatch — donated buffers are invalidated at dispatch; "
                "reassign from the program outputs before any read",
            )
            return
        if "." in name and name.split(".", 1)[0] == "self":
            yield ctx.finding(
                self.id, call,
                f"donated buffer '{name}' is never reassigned after dispatch "
                "— the object retains a deleted array for the next caller; "
                "rebind it from the program outputs "
                "(`..., self.k_cache, self.v_cache = out`)",
            )
