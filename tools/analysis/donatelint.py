"""The ``--donate`` use-after-donate pass (ISSUE 20).

``donate_argnums`` hands buffer ownership to the jit: after the call the
donated arrays are invalidated, and a later read sees garbage (or a
runtime error at best) — the PR-8 hazard that made `checkpoint_sessions`
copy session columns instead of exposing the pump's live carry.  The
ring/packed paths thread donated carries on purpose; this pass checks
that discipline mechanically from the jit manifest's donation registry.

Rules (docs/STATIC_ANALYSIS.md catalog):

* ``use-after-donate``     — a name passed in a donated position of a
  registered donating call (`jit_manifest.DONATING_CALLS`) is read
  later in the same scope with no rebind in between.  Both straight-
  line reads after the call and loop-carried reads (the next
  iteration's call re-donates the same name) are checked; a rebind
  anywhere on the path (including the donating statement's own
  assignment targets — the threading idiom) clears the hazard.
  Donated values re-exposed through the sanctioned copy points
  (`checkpoint_sessions` / `_serve_ckpt` ``jnp.copy``, the stager
  hand-off) live in other scopes and take fresh references, so they
  never trip this rule.  Suppress one line with
  ``# donate-ok: <reason>``.
* ``donate-unregistered``  — a call with a non-empty literal
  ``donate_argnums`` whose (file, enclosing scope) is not in
  `jit_manifest.DONATED_JIT_SITES`: donation without a registered
  ownership story.
* ``donate-site-stale``    — a DONATED_JIT_SITES / DONATING_CALLS entry
  that no longer resolves (scope gone, no matching call): drop or fix.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from analysis.common import Finding, iter_source_files, parse_suppressions

DONATE_ROOTS = ("vpp_tpu", "tests")


def _callee_repr(func) -> Optional[str]:
    """'step' for Name, 'self._step' / 'dp.process' for Attribute."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        base = _callee_repr(func.value)
        return f"{base}.{func.attr}" if base else None
    return None


def _name_events(scope_body, kinds) -> List[Tuple[str, int, str]]:
    """(name, line, 'load'|'store') events in a scope, nested function
    bodies excluded (closures get fresh references at call time; the
    sanctioned copy points live there)."""
    events = []

    def visit(node):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Load):
                events.append((node.id, node.lineno, "load"))
            elif isinstance(node.ctx, (ast.Store, ast.Del)):
                events.append((node.id, node.lineno, "store"))
        for child in ast.iter_child_nodes(node):
            visit(child)

    for s in scope_body:
        visit(s)
    return [e for e in events if e[2] in kinds]


class DonatePass:
    def __init__(self, repo: Path, roots=DONATE_ROOTS, manifest=None):
        self.repo = repo
        self.roots = roots
        if manifest is None:
            from analysis import jit_manifest as manifest
        self.jit_sites: Dict[Tuple[str, str], str] = dict(
            manifest.DONATED_JIT_SITES)
        self.calls: Dict[Tuple[str, str, str], Tuple[tuple, str]] = dict(
            manifest.DONATING_CALLS)
        self.findings: List[Finding] = []

    def run(self) -> List[Finding]:
        seen_jit: Set[Tuple[str, str]] = set()
        seen_calls: Set[Tuple[str, str, str]] = set()
        for relpath, path in iter_source_files(self.repo, self.roots):
            src = path.read_text()
            try:
                tree = ast.parse(src, filename=relpath)
            except SyntaxError:
                continue
            sup = parse_suppressions(src, relpath)
            self.findings.extend(sup.problems)
            self._scan_file(relpath, tree, sup, seen_jit, seen_calls)
        for key, _reason in sorted(self.jit_sites.items()):
            if key not in seen_jit:
                self.findings.append(Finding(
                    key[0], 1, "donate-site-stale",
                    f"DONATED_JIT_SITES entry {key[1]!r} has no "
                    f"donating jit left in {key[0]}: drop or fix it"))
        for key, _spec in sorted(self.calls.items()):
            if key not in seen_calls:
                self.findings.append(Finding(
                    key[0], 1, "donate-site-stale",
                    f"DONATING_CALLS entry {key[1]!r} -> {key[2]!r} "
                    f"matches no call in {key[0]}: drop or fix it"))
        return self.findings

    # ------------------------------------------------------------------
    def _scan_file(self, relpath, tree, sup, seen_jit, seen_calls):
        def walk(node, stack):
            qual = ".".join(stack) or "<module>"
            self._check_scope(relpath, qual, node.body, sup, seen_jit,
                              seen_calls)
            for ch in node.body:
                if isinstance(ch, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                    walk(ch, stack + [ch.name])
                elif isinstance(ch, ast.ClassDef):
                    for m in ch.body:
                        if isinstance(m, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                            walk(m, stack + [ch.name, m.name])

        walk(tree, [])

    def _check_scope(self, relpath, qual, body, sup, seen_jit,
                     seen_calls):
        # --- donate-unregistered: literal non-empty donate_argnums ----
        def scan_jits(stmts):
            for s in stmts:
                for node in ast.walk(s):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        continue  # inner scopes checked separately
                    if not isinstance(node, ast.Call):
                        continue
                    for kw in node.keywords:
                        if kw.arg != "donate_argnums":
                            continue
                        if isinstance(kw.value, (ast.Tuple, ast.List)) \
                                and not kw.value.elts:
                            continue  # empty: nothing donated
                        seen_jit.add((relpath, qual))
                        if (relpath, qual) not in self.jit_sites and \
                                node.lineno not in sup.donate:
                            self.findings.append(Finding(
                                relpath, node.lineno,
                                "donate-unregistered",
                                f"jit with donate_argnums in {qual}() "
                                f"is not registered in jit_manifest."
                                f"DONATED_JIT_SITES: donation needs an "
                                f"ownership story"))

        # only this scope's own statements (nested defs are their own
        # scopes in the walk)
        own = [s for s in body if not isinstance(
            s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))]
        scan_jits(own)

        # --- use-after-donate over registered calls -------------------
        entries = {callee: spec for (rp, q, callee), spec
                   in self.calls.items()
                   if rp == relpath and q == qual}
        if not entries:
            return
        events = _name_events(own, ("load", "store"))
        calls = []  # (lineno, callee, donated (argnum, name)s, loop)
        excl = []   # mutually-exclusive (if-body, else-body) line spans

        def record_calls(s, loop):
            span = (s.lineno, s.end_lineno or s.lineno)
            for node in ast.walk(s):
                if isinstance(node, ast.Call):
                    rep = _callee_repr(node.func)
                    if rep in entries:
                        argnums = entries[rep][0]
                        # a *args expansion makes positions after the
                        # star unknowable at the AST level — only track
                        # donated names left of the first Starred
                        star = next(
                            (i for i, a in enumerate(node.args)
                             if isinstance(a, ast.Starred)),
                            len(node.args))
                        names = [
                            (i, node.args[i].id) for i in argnums
                            if i < star
                            and isinstance(node.args[i], ast.Name)]
                        calls.append(
                            (node.lineno, span, rep, names, loop))

        def collect_calls(stmts, loop):
            for s in stmts:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                if isinstance(s, (ast.For, ast.While)):
                    collect_calls(s.body + s.orelse,
                                  (s.lineno, s.end_lineno or s.lineno))
                elif isinstance(s, ast.If):
                    record_calls(s.test, loop)
                    if s.body and s.orelse:
                        excl.append((
                            (s.body[0].lineno,
                             s.body[-1].end_lineno or s.body[-1].lineno),
                            (s.orelse[0].lineno,
                             s.orelse[-1].end_lineno
                             or s.orelse[-1].lineno)))
                    collect_calls(s.body + s.orelse, loop)
                elif isinstance(s, ast.With):
                    for item in s.items:
                        record_calls(item.context_expr, loop)
                    collect_calls(s.body, loop)
                elif isinstance(s, ast.Try):
                    collect_calls(s.body + s.orelse + s.finalbody, loop)
                    for h in s.handlers:
                        collect_calls(h.body, loop)
                else:
                    record_calls(s, loop)

        collect_calls(own, None)
        for callee in entries:
            if any(c[2] == callee for c in calls):
                seen_calls.add((relpath, qual, callee))

        stores = {}
        loads = {}
        for name, line, kind in events:
            (stores if kind == "store" else loads).setdefault(
                name, []).append(line)

        def exclusive(a: int, b: int) -> bool:
            return any(
                (p[0] <= a <= p[1] and q[0] <= b <= q[1])
                or (q[0] <= a <= q[1] and p[0] <= b <= p[1])
                for p, q in excl)

        for call_line, (st_lo, st_hi), callee, names, loop in calls:
            for argnum, d in names:
                d_stores = stores.get(d, [])
                d_loads = loads.get(d, [])
                # straight-line: loads after the donating STATEMENT
                # (its own arg reads evaluate before donation lands,
                # its own targets are the threading rebind)
                for r in sorted(d_loads):
                    if r <= st_hi or exclusive(call_line, r):
                        continue
                    if any(st_lo <= s <= r for s in d_stores
                           if not exclusive(call_line, s)):
                        break  # rebound before this (and later) reads
                    self._emit(relpath, r, d, callee, call_line,
                               argnum, sup)
                    break  # one finding per donated name is enough
                # loop-carried: next iteration reads d before a rebind
                if loop is None:
                    continue
                lo, hi = loop
                carried = [r for r in d_loads if lo <= r <= st_hi]
                for r in sorted(carried):
                    killed = any(
                        (st_lo <= s <= hi) or (lo <= s < r)
                        for s in d_stores)
                    if killed:
                        break
                    self._emit(relpath, r, d, callee, call_line,
                               argnum, sup,
                               carried=True)
                    break

    def _emit(self, relpath, line, name, callee, call_line, argnum,
              sup, carried=False) -> None:
        if line in sup.donate:
            return
        how = ("read by the NEXT iteration's donating call"
               if carried else "read after the donating call")
        self.findings.append(Finding(
            relpath, line, "use-after-donate",
            f"'{name}' is donated to {callee}() (argnum {argnum}, "
            f"line {call_line}) and {how}: the buffer is invalidated "
            f"— rebind from the result or jnp.copy before donating"))


def donate_lint(repo=None, roots=DONATE_ROOTS,
                manifest=None) -> List[Finding]:
    """Run the pass; returns unsuppressed findings (empty == clean)."""
    if repo is None:
        repo = Path(__file__).resolve().parents[2]
    return DonatePass(Path(repo), roots, manifest).run()
