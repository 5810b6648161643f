"""The ``--transfers`` device-boundary fetch pass (ISSUE 20).

The regression class fixed by hand in PRs 6, 8 and 12: table-scale
device values materialized on host (``np.asarray``, ``jax.device_get``,
``.item()`` / ``int()``) on the control path — "~270 MB crosses the
transport".  The pass taints every value reachable from a
`DataplaneTables` pytree (``X.tables`` attribute loads, parameters named
``tables`` or annotated ``DataplaneTables``, the persistent pump's
table-carry slots) and flags host-materialization sinks on tainted
values, unless the enclosing function is an approved fetch site in
`transfer_manifest.TRANSFER_SITES` (snapshot drains, the packed-result
fetch).

Taint propagates through names, attribute/subscript access, tuple
packing, arithmetic, and device-side calls (``jnp.*`` / ``jax.lax.*``);
it does NOT survive host metadata access (``.shape`` / ``.dtype`` /
``.ndim`` / ``.size`` / ``.nbytes``) — shapes live on host already.
Closures inherit the enclosing scope's taint (the pump's fetch workers).

Rules (docs/STATIC_ANALYSIS.md catalog):

* ``transfer-host-fetch`` — host materialization of a tables-reachable
  device value outside an approved site.  Suppress one line with
  ``# transfer-ok: <reason>``; add a site to the manifest when the whole
  function IS a sanctioned drain (docs/STATIC_ANALYSIS.md "how to add an
  approved transfer site").
* ``transfer-site-stale`` — a TRANSFER_SITES entry that no longer
  resolves to a scanned function (file gone, function renamed): drop or
  fix it, dead allowlist entries hide future regressions.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Set, Tuple

from analysis.common import Finding, iter_source_files, parse_suppressions

TRANSFER_ROOTS = ("vpp_tpu",)

# attribute names that hold a DataplaneTables pytree
TAINT_ATTRS = {"tables", "_tables0", "_tables_pending", "_tables_final"}
# parameter names that carry one
TAINT_PARAMS = {"tables", "tbl", "tables0"}
# host-metadata access does not move array bytes
HOST_ATTRS = {"shape", "dtype", "ndim", "size", "nbytes", "sharding"}
# numpy module aliases and materializing constructors
NP_NAMES = {"np", "numpy", "_np"}
NP_SINKS = {"asarray", "array", "ascontiguousarray"}
# device-side module aliases: calls through these keep values on device
DEVICE_MODS = {"jnp", "jax", "lax"}


def _qual(stack: List[str]) -> str:
    return ".".join(stack) if stack else "<module>"


class TransferPass:
    def __init__(self, repo: Path, roots=TRANSFER_ROOTS, manifest=None):
        self.repo = repo
        self.roots = roots
        if manifest is None:
            from analysis import transfer_manifest as manifest
        self.sites: Dict[Tuple[str, str], str] = dict(
            manifest.TRANSFER_SITES)
        self.findings: List[Finding] = []
        self._seen_scopes: Set[Tuple[str, str]] = set()

    def run(self) -> List[Finding]:
        scanned_files = set()
        for relpath, path in iter_source_files(self.repo, self.roots):
            scanned_files.add(relpath)
            src = path.read_text()
            try:
                tree = ast.parse(src, filename=relpath)
            except SyntaxError:
                continue
            sup = parse_suppressions(src, relpath)
            self.findings.extend(sup.problems)
            self._scan_scope(relpath, tree.body, [], set(), sup)
        for (relpath, qualname), _reason in sorted(self.sites.items()):
            if relpath not in scanned_files:
                self.findings.append(Finding(
                    relpath, 1, "transfer-site-stale",
                    f"TRANSFER_SITES entry ({relpath!r}, {qualname!r}) "
                    f"names a file outside the scanned tree"))
            elif qualname != "*" and \
                    (relpath, qualname) not in self._seen_scopes:
                self.findings.append(Finding(
                    relpath, 1, "transfer-site-stale",
                    f"TRANSFER_SITES entry {qualname!r} does not "
                    f"resolve to a function in {relpath}: drop or fix "
                    f"it (dead allowlist entries hide regressions)"))
        return self.findings

    # ------------------------------------------------------------------
    def _allowed(self, relpath: str, stack: List[str]) -> bool:
        if (relpath, "*") in self.sites:
            return True
        # an inner closure is covered by its enclosing approved site
        for i in range(len(stack), 0, -1):
            if (relpath, ".".join(stack[:i])) in self.sites:
                return True
        return False

    def _scan_scope(self, relpath, body, stack, inherited, sup) -> None:
        """One lexical scope: collect tainted names, then find sinks.
        Nested functions recurse with the outer taint inherited."""
        self._seen_scopes.add((relpath, _qual(stack)))
        tainted: Set[str] = set(inherited)
        nested = []

        def is_tainted(expr) -> bool:
            if isinstance(expr, ast.Name):
                return expr.id in tainted
            if isinstance(expr, ast.Attribute):
                if expr.attr in HOST_ATTRS:
                    return False
                if expr.attr in TAINT_ATTRS:
                    return True
                return is_tainted(expr.value)
            if isinstance(expr, ast.Subscript):
                return is_tainted(expr.value)
            if isinstance(expr, ast.Starred):
                return is_tainted(expr.value)
            if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
                return any(is_tainted(e) for e in expr.elts)
            if isinstance(expr, ast.BinOp):
                return is_tainted(expr.left) or is_tainted(expr.right)
            if isinstance(expr, ast.UnaryOp):
                return is_tainted(expr.operand)
            if isinstance(expr, ast.Compare):
                return is_tainted(expr.left) or \
                    any(is_tainted(c) for c in expr.comparators)
            if isinstance(expr, ast.IfExp):
                return is_tainted(expr.body) or is_tainted(expr.orelse)
            if isinstance(expr, ast.NamedExpr):
                return is_tainted(expr.value)
            if isinstance(expr, ast.Call):
                f = expr.func
                # getattr(tables, name) reaches a column
                if isinstance(f, ast.Name) and f.id == "getattr" and \
                        expr.args and is_tainted(expr.args[0]):
                    return True
                # device-side transforms keep the value on device:
                # jnp.sum(tables.x), jax.lax.*, tainted.method(...)
                if isinstance(f, ast.Attribute):
                    root = f.value
                    while isinstance(root, ast.Attribute):
                        root = root.value
                    if isinstance(root, ast.Name) and \
                            root.id in DEVICE_MODS:
                        # device_get is the sink itself: its RESULT is
                        # a host array, not a tainted device value
                        if f.attr == "device_get":
                            return False
                        return any(is_tainted(a) for a in expr.args)
                    if f.attr not in ("item",) and is_tainted(f.value):
                        # tainted.astype(...)/.sum()/.reshape(...):
                        # still a device value
                        return True
                return False
            return False

        def seed_args(fn) -> Set[str]:
            out = set()
            args = fn.args
            for a in (args.posonlyargs + args.args + args.kwonlyargs +
                      [args.vararg, args.kwarg]):
                if a is None:
                    continue
                ann = a.annotation
                ann_name = ""
                if isinstance(ann, ast.Name):
                    ann_name = ann.id
                elif isinstance(ann, ast.Attribute):
                    ann_name = ann.attr
                elif isinstance(ann, ast.Constant) and \
                        isinstance(ann.value, str):
                    ann_name = ann.value.split(".")[-1]
                if a.arg in TAINT_PARAMS or \
                        ann_name == "DataplaneTables":
                    out.add(a.arg)
            return out

        # --- taint fixpoint over assignments in this scope ------------
        def collect(stmts):
            for s in stmts:
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if isinstance(s, ast.Assign):
                    if is_tainted(s.value):
                        for t in s.targets:
                            _taint_target(t)
                    elif isinstance(s.value, ast.Tuple) and len(
                            s.targets) == 1 and isinstance(
                            s.targets[0], ast.Tuple) and len(
                            s.targets[0].elts) == len(s.value.elts):
                        for t, v in zip(s.targets[0].elts, s.value.elts):
                            if is_tainted(v):
                                _taint_target(t)
                elif isinstance(s, (ast.AnnAssign, ast.AugAssign)):
                    if s.value is not None and is_tainted(s.value):
                        _taint_target(s.target)
                elif isinstance(s, ast.For):
                    if is_tainted(s.iter):
                        _taint_target(s.target)
                    collect(s.body + s.orelse)
                elif isinstance(s, ast.With):
                    for item in s.items:
                        if item.optional_vars is not None and \
                                is_tainted(item.context_expr):
                            _taint_target(item.optional_vars)
                    collect(s.body)
                elif isinstance(s, (ast.If,)):
                    collect(s.body + s.orelse)
                elif isinstance(s, ast.While):
                    collect(s.body + s.orelse)
                elif isinstance(s, ast.Try):
                    collect(s.body + s.orelse + s.finalbody)
                    for h in s.handlers:
                        collect(h.body)

        def _taint_target(t):
            if isinstance(t, ast.Name):
                tainted.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    _taint_target(e)
            elif isinstance(t, ast.Starred):
                _taint_target(t.value)

        for _ in range(3):
            before = len(tainted)
            collect(body)
            if len(tainted) == before:
                break

        # --- sink detection -------------------------------------------
        allowed = self._allowed(relpath, stack)

        def visit(node):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested.append(node)
                return
            if isinstance(node, ast.ClassDef):
                return  # methods scanned as Class.method scopes below
            if isinstance(node, ast.Call):
                self._check_sink(relpath, stack, node, is_tainted,
                                 allowed, sup)
            for child in ast.iter_child_nodes(node):
                visit(child)

        for s in body:
            visit(s)

        for fn in nested:
            inner = set(tainted) | seed_args(fn)
            self._scan_scope(relpath, fn.body, stack + [fn.name],
                             inner, sup)

        # class bodies: methods are scopes named Class.method
        for s in body:
            if isinstance(s, ast.ClassDef):
                for m in s.body:
                    if isinstance(m, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                        self._scan_scope(
                            relpath, m.body, stack + [s.name, m.name],
                            seed_args(m), sup)

    def _check_sink(self, relpath, stack, call, is_tainted, allowed,
                    sup) -> None:
        f = call.func
        sink = None
        if isinstance(f, ast.Name) and f.id in ("int", "float", "bool"):
            if call.args and is_tainted(call.args[0]):
                sink = f"{f.id}() on a device value"
        elif isinstance(f, ast.Attribute):
            base = f.value
            if isinstance(base, ast.Name) and base.id in NP_NAMES and \
                    f.attr in NP_SINKS:
                if any(is_tainted(a) for a in call.args):
                    sink = f"np.{f.attr}() host materialization"
            elif f.attr == "device_get":
                if any(is_tainted(a) for a in call.args):
                    sink = "jax.device_get() host materialization"
            elif f.attr == "item" and not call.args and \
                    is_tainted(base):
                sink = ".item() device sync"
        if sink is None:
            return
        if allowed:
            return
        if call.lineno in sup.transfer:
            return
        self.findings.append(Finding(
            relpath, call.lineno, "transfer-host-fetch",
            f"{sink} of a DataplaneTables-reachable value in "
            f"{_qual(stack)}(): table-scale device->host fetch outside "
            f"the approved sites (tools/analysis/transfer_manifest.py)"))


def transfers_lint(repo=None, roots=TRANSFER_ROOTS,
                   manifest=None) -> List[Finding]:
    """Run the pass; returns unsuppressed findings (empty == clean)."""
    if repo is None:
        repo = Path(__file__).resolve().parents[2]
    return TransferPass(Path(repo), roots, manifest).run()
