"""The ``--uploads`` upload-group consistency pass (ISSUE 20).

The incremental-upload contract of ``vpp_tpu/pipeline/tables.py``: every
`DataplaneTables` field ships through exactly one `_UPLOAD_GROUPS` entry,
or is carried by reference across swaps via a state ledger
(`SESSION_FIELDS`, `TELEMETRY_FIELDS`, `TENANCY_STATE_FIELDS`,
`FIB_STATE_FIELDS` — the sweep cursors live inside `SESSION_FIELDS`).
A `TableBuilder` mutator that writes a staged attribute must mark the
owning group dirty on every non-raising path, or the next `to_device()`
silently ships a stale device plane — the PR-4/PR-19 hand-review hazard.

Rules (docs/STATIC_ANALYSIS.md catalog):

* ``upload-field-unplaced``   — DataplaneTables field in no group and no
  ledger: nobody decided how it ships.
* ``upload-field-multi``      — field claimed by more than one placement.
* ``upload-group-stale``      — a group/ledger entry names a field that
  no longer exists on DataplaneTables.
* ``upload-manifest-missing`` — field absent from
  `upload_manifest.FIELD_PLACEMENTS` (a new field needs a reviewed
  placement decision, not just a group edit).
* ``upload-manifest-stale``   — manifest entry for a non-existent field.
* ``upload-manifest-mismatch``— manifest placement disagrees with what
  tables.py actually says.
* ``upload-mark-missing``     — a TableBuilder method writes a staged
  attribute (`upload_manifest.STAGED_ATTRS`) and some non-raising path
  reaches an exit without marking that attribute's group dirty.  The
  dataflow follows `self._mark(g)`, `self._dirty.add/update(...)`,
  whole-set re-marks (`self._dirty = set(_UPLOAD_GROUPS)`), and
  self-method calls (helper summaries to fixpoint); branches merge by
  union of still-pending groups; paths ending in `raise` are dropped
  (the builder re-stages on the next successful mutation).
* ``upload-dirty-field-foreign`` — a literal field added to a sub-dirty
  set (`_fib_dirty`, `_bv_dirty`) that is not a member of the owning
  group: it would never be consulted by the incremental uploader.
* ``upload-extern-write``     — staged builder attributes written from
  outside TableBuilder (``dp.builder.if_local_table[...] = ...``):
  external writers bypass the dirty-marking discipline entirely and
  must go through a mutator.
* ``upload-exempt-stale``     — `EXEMPT_METHODS` names a method that no
  longer exists.

Suppress one line with ``# upload-ok: <reason>``.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from analysis.common import Finding, iter_source_files, parse_suppressions

TABLES_REL = "vpp_tpu/pipeline/tables.py"
# roots scanned for external writes to staged builder attributes
UPLOAD_ROOTS = ("vpp_tpu",)

LEDGER_NAMES = ("SESSION_FIELDS", "TELEMETRY_FIELDS",
                "TENANCY_STATE_FIELDS", "FIB_STATE_FIELDS")

# sub-dirty sets -> the group whose fields they may name
SUB_DIRTY = {"_fib_dirty": "fib", "_bv_dirty": "glb_bv"}

# calls on a staged dict/list attr that mutate it in place
_MUTATING = {"pop", "clear", "update", "append", "extend", "add",
             "remove", "setdefault", "insert"}

_RAISED, _RETURNED = "raised", "returned"


def _self_attr(expr) -> Optional[str]:
    """'x' for ``self.x`` — None otherwise (first hop only)."""
    parts = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name) and expr.id == "self" and parts:
        return parts[-1]
    return None


def _peel(target):
    """Strip Subscript layers: ``self.acl[t]["src"][i]`` -> ``self.acl``."""
    while isinstance(target, ast.Subscript):
        target = target.value
    return target


def _str_elts(node) -> Optional[List[str]]:
    """Literal string elements of a Constant/Tuple/List/Set, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                return None
        return out
    return None


class _TablesModel:
    """What the AST of tables.py actually declares."""

    def __init__(self):
        self.fields: Dict[str, int] = {}          # field -> lineno
        self.groups: Dict[str, List[str]] = {}    # group -> fields
        self.groups_line = 1
        self.ledgers: Dict[str, List[str]] = {}   # ledger -> fields
        self.field_sets: Dict[str, Set[str]] = {}  # module field listings
        self.builder: Optional[ast.ClassDef] = None


def _load_model(tree: ast.Module) -> _TablesModel:
    model = _TablesModel()

    def record(name, value, lineno):
        if name == "_UPLOAD_GROUPS" and isinstance(value, ast.Dict):
            model.groups_line = lineno
            for k, v in zip(value.keys, value.values):
                if isinstance(k, ast.Constant):
                    model.groups[k.value] = _str_elts(v) or []
        elif name in LEDGER_NAMES and isinstance(value, ast.Dict):
            model.ledgers[name] = [
                k.value for k in value.keys if isinstance(k, ast.Constant)]
        elif isinstance(value, (ast.Tuple, ast.List)):
            elts = _str_elts(value)
            if elts is not None:
                model.field_sets[name] = set(elts)
        elif isinstance(value, ast.Dict):
            acc: Set[str] = set()
            for v in value.values:
                elts = _str_elts(v)
                if elts is None:
                    return
                acc.update(elts)
            model.field_sets[name] = acc

    for node in tree.body:
        if isinstance(node, ast.ClassDef):
            if node.name == "DataplaneTables":
                for st in node.body:
                    if isinstance(st, ast.AnnAssign) and \
                            isinstance(st.target, ast.Name):
                        model.fields[st.target.id] = st.lineno
            elif node.name == "TableBuilder":
                model.builder = node
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name) and node.value is not None:
            record(node.target.id, node.value, node.lineno)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            record(node.targets[0].id, node.value, node.lineno)
    return model


class _Summary:
    """Effect of calling a TableBuilder method on the pending state."""

    def __init__(self):
        # group -> (attr, line): written-but-unmarked at some exit
        self.pending: Dict[str, Tuple[str, int]] = {}
        self.marks: Set[str] = set()   # marked on every non-raising path

    def key(self):
        return (frozenset(self.pending), frozenset(self.marks))


class _State:
    def __init__(self):
        self.pending: Dict[str, Tuple[str, int]] = {}
        self.marked: Set[str] = set()

    def copy(self) -> "_State":
        st = _State()
        st.pending = dict(self.pending)
        st.marked = set(self.marked)
        return st


def _merge(states: List[_State]) -> Optional[_State]:
    live = [s for s in states if s is not None]
    if not live:
        return None
    out = live[0].copy()
    for s in live[1:]:
        for g, site in s.pending.items():
            out.pending.setdefault(g, site)
        out.marked &= s.marked
    # a group pending on ONE branch is pending, even if marked on another
    for g in list(out.marked):
        if g in out.pending:
            out.marked.discard(g)
    return out


class UploadPass:
    def __init__(self, repo: Path, tables_rel: str = TABLES_REL,
                 roots=UPLOAD_ROOTS, manifest=None):
        self.repo = repo
        self.tables_rel = tables_rel
        self.roots = roots
        if manifest is None:
            from analysis import upload_manifest as manifest
        self.placements: Dict[str, str] = dict(manifest.FIELD_PLACEMENTS)
        self.staged: Dict[str, str] = dict(manifest.STAGED_ATTRS)
        self.exempt: Dict[str, str] = dict(manifest.EXEMPT_METHODS)
        self.findings: List[Finding] = []

    # ------------------------------------------------------------------
    def run(self) -> List[Finding]:
        path = self.repo / self.tables_rel
        src = path.read_text()
        try:
            tree = ast.parse(src, filename=self.tables_rel)
        except SyntaxError:
            return self.findings  # the style pass reports parse failures
        sup = parse_suppressions(src, self.tables_rel)
        self.findings.extend(sup.problems)
        model = _load_model(tree)
        self._check_placements(model, sup)
        if model.builder is not None:
            self._check_marks(model, sup)
        self._check_extern_writes(sup_tables=sup)
        return self.findings

    def _emit(self, relpath, line, rule, msg, sup) -> None:
        if line in sup.upload:
            return
        self.findings.append(Finding(relpath, line, rule, msg))

    # --- placement rules ----------------------------------------------
    def _check_placements(self, model: _TablesModel, sup) -> None:
        rel = self.tables_rel
        placed: Dict[str, List[str]] = {}
        for g, fields in model.groups.items():
            for f in fields:
                placed.setdefault(f, []).append(f"group:{g}")
        for ledger, fields in model.ledgers.items():
            for f in fields:
                placed.setdefault(f, []).append(f"ledger:{ledger}")

        for f, line in model.fields.items():
            got = placed.get(f, [])
            if not got:
                self._emit(rel, line, "upload-field-unplaced",
                           f"DataplaneTables.{f} is in no _UPLOAD_GROUPS "
                           f"entry and no state ledger: decide how it "
                           f"ships (stale-plane hazard)", sup)
            elif len(got) > 1:
                self._emit(rel, line, "upload-field-multi",
                           f"DataplaneTables.{f} has {len(got)} "
                           f"placements ({', '.join(sorted(got))}); "
                           f"exactly one owns the upload", sup)
        for f, wheres in placed.items():
            if f not in model.fields:
                self._emit(rel, model.groups_line, "upload-group-stale",
                           f"'{f}' ({wheres[0]}) is not a "
                           f"DataplaneTables field any more", sup)

        # manifest <-> AST diff
        for f, line in model.fields.items():
            want = placed.get(f, [None])[0]
            have = self.placements.get(f)
            if have is None:
                self._emit(rel, line, "upload-manifest-missing",
                           f"DataplaneTables.{f} has no entry in "
                           f"tools/analysis/upload_manifest.py "
                           f"FIELD_PLACEMENTS: record the reviewed "
                           f"placement decision", sup)
            elif want is not None and have != want:
                self._emit(rel, line, "upload-manifest-mismatch",
                           f"manifest places {f} at '{have}' but "
                           f"tables.py says '{want}'", sup)
        for f in sorted(self.placements):
            if f not in model.fields:
                self._emit(rel, model.groups_line, "upload-manifest-stale",
                           f"FIELD_PLACEMENTS entry '{f}' is not a "
                           f"DataplaneTables field: drop it", sup)

    # --- mark-dataflow over TableBuilder ------------------------------
    def _check_marks(self, model: _TablesModel, sup) -> None:
        cls = model.builder
        methods = {m.name: m for m in cls.body
                   if isinstance(m, ast.FunctionDef)}
        for name in sorted(self.exempt):
            if name not in methods:
                self._emit(self.tables_rel, cls.lineno,
                           "upload-exempt-stale",
                           f"EXEMPT_METHODS names TableBuilder.{name}() "
                           f"which does not exist", sup)
        summaries: Dict[str, _Summary] = {n: _Summary() for n in methods}
        self._sub_dirty_findings: List[Tuple[int, str]] = []
        for _ in range(8):  # fixpoint over helper summaries
            changed = False
            for name, m in methods.items():
                if name == "__init__" or name in self.exempt:
                    continue
                new = self._analyze_method(m, model, summaries,
                                           emit=False)
                if new.key() != summaries[name].key():
                    summaries[name] = new
                    changed = True
            if not changed:
                break
        seen: Set[Tuple[str, str, int]] = set()
        for name, m in sorted(methods.items()):
            if name == "__init__" or name in self.exempt:
                continue
            # private helpers propagate their pending groups to callers
            # (the caller's call line is the finding anchor); only
            # public mutators must mark on every path themselves
            emit = not name.startswith("_")
            summ = self._analyze_method(m, model, summaries, emit=emit,
                                        seen=seen, sup=sup)
            summaries[name] = summ
        for line, msg in sorted(set(self._sub_dirty_findings)):
            self._emit(self.tables_rel, line,
                       "upload-dirty-field-foreign", msg, sup)

    def _analyze_method(self, method, model, summaries, emit,
                        seen=None, sup=None) -> _Summary:
        exits: List[_State] = []
        all_groups = set(model.groups)

        def mark(st: _State, group: str) -> None:
            st.pending.pop(group, None)
            st.marked.add(group)

        def staged_write(st: _State, target, lineno) -> None:
            if isinstance(target, (ast.Tuple, ast.List)):
                for e in target.elts:
                    staged_write(st, e, lineno)
                return
            base = _peel(target)
            attr = _self_attr(base)
            if attr in self.staged:
                st.pending.setdefault(self.staged[attr], (attr, lineno))

        def handle_call(st: _State, call: ast.Call) -> None:
            f = call.func
            if not isinstance(f, ast.Attribute):
                return
            owner = _self_attr(f.value)
            # self._mark("g")
            if _self_attr(f) == "_mark" and call.args:
                lits = _str_elts(call.args[0])
                if lits:
                    mark(st, lits[0])
                return
            # self._dirty.add/update(...)
            if owner == "_dirty" and f.attr in ("add", "update"):
                for a in call.args:
                    lits = _str_elts(a)
                    if lits is None:
                        if isinstance(a, ast.Call) and \
                                isinstance(a.func, ast.Name) and \
                                a.func.id == "set" and a.args and \
                                isinstance(a.args[0], ast.Name) and \
                                a.args[0].id == "_UPLOAD_GROUPS":
                            for g in all_groups:
                                mark(st, g)
                        continue
                    for g in lits:
                        mark(st, g)
                return
            # sub-dirty field hygiene: _fib_dirty/_bv_dirty.add/update
            if owner in SUB_DIRTY and f.attr in ("add", "update"):
                group = SUB_DIRTY[owner]
                members = set(model.groups.get(group, ()))
                for a in call.args:
                    lits = _str_elts(a)
                    if lits is None:
                        node = a
                        while isinstance(node, ast.Subscript):
                            node = node.value
                        if isinstance(node, ast.Name) and \
                                node.id in model.field_sets:
                            lits = sorted(model.field_sets[node.id])
                        else:
                            continue
                    for fld in lits:
                        if members and fld not in members:
                            self._sub_dirty_findings.append((
                                call.lineno,
                                f"'{fld}' added to self.{owner} but it "
                                f"is not in _UPLOAD_GROUPS['{group}']: "
                                f"the incremental uploader will never "
                                f"consult it"))
                return
            # in-place mutation of a staged dict/list: self.ml.clear()
            fattr = _self_attr(f)
            if fattr is None:
                return
            if owner in self.staged and f.attr in _MUTATING:
                st.pending.setdefault(self.staged[owner],
                                      (owner, call.lineno))
                return
            # self.helper(...) -> apply its summary
            if owner is None and f.attr in summaries and \
                    isinstance(f.value, ast.Name) and f.value.id == "self":
                summ = summaries[f.attr]
                for g, (attr, _line) in summ.pending.items():
                    st.pending.setdefault(g, (attr, call.lineno))
                for g in summ.marks:
                    mark(st, g)

        def scan_expr(st: _State, expr) -> None:
            for node in ast.walk(expr):
                if isinstance(node, ast.Call):
                    handle_call(st, node)

        def flow(stmts, st: _State):
            """Returns the fall-through state, or a sentinel."""
            for s in stmts:
                if st in (_RAISED, _RETURNED):
                    return st
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    continue
                if isinstance(s, ast.Return):
                    if s.value is not None:
                        scan_expr(st, s.value)
                    exits.append(st)
                    return _RETURNED
                if isinstance(s, ast.Raise):
                    return _RAISED
                if isinstance(s, (ast.Assign, ast.AugAssign,
                                  ast.AnnAssign)):
                    value = s.value
                    if value is not None:
                        scan_expr(st, value)
                    targets = s.targets if isinstance(s, ast.Assign) \
                        else [s.target]
                    # whole-set re-mark: self._dirty = set(_UPLOAD_GROUPS)
                    tattr = _self_attr(targets[0]) if targets else None
                    if tattr == "_dirty" and isinstance(value, ast.Call) \
                            and isinstance(value.func, ast.Name) \
                            and value.func.id == "set" and value.args \
                            and isinstance(value.args[0], ast.Name) \
                            and value.args[0].id == "_UPLOAD_GROUPS":
                        for g in all_groups:
                            mark(st, g)
                        continue
                    for t in targets:
                        staged_write(st, t, s.lineno)
                    continue
                if isinstance(s, ast.Delete):
                    for t in s.targets:
                        staged_write(st, t, s.lineno)
                    continue
                if isinstance(s, ast.Expr):
                    scan_expr(st, s.value)
                    continue
                if isinstance(s, ast.If):
                    scan_expr(st, s.test)
                    a = flow(s.body, st.copy())
                    b = flow(s.orelse, st.copy())
                    nxt = _merge([x if isinstance(x, _State) else None
                                  for x in (a, b)])
                    if nxt is None:
                        return _RETURNED if _RETURNED in (a, b) \
                            else _RAISED
                    st = nxt
                    continue
                if isinstance(s, (ast.For, ast.While)):
                    if isinstance(s, ast.For):
                        scan_expr(st, s.iter)
                    else:
                        scan_expr(st, s.test)
                    body = flow(s.body + s.orelse, st.copy())
                    nxt = _merge([st, body if isinstance(body, _State)
                                  else None])
                    st = nxt if nxt is not None else st
                    continue
                if isinstance(s, ast.With):
                    for item in s.items:
                        scan_expr(st, item.context_expr)
                    r = flow(s.body, st)
                    if r in (_RAISED, _RETURNED):
                        return r
                    st = r
                    continue
                if isinstance(s, ast.Try):
                    body = flow(s.body, st.copy())
                    body_st = body if isinstance(body, _State) else None
                    # a handler may run with any prefix of the body done
                    h_entry = _merge([st, body_st]) or st
                    outs = [body_st]
                    for h in s.handlers:
                        outs.append(
                            r if isinstance(
                                r := flow(h.body, h_entry.copy()),
                                _State) else None)
                    nxt = _merge(outs)
                    if nxt is None:
                        return body if body in (_RAISED, _RETURNED) \
                            else _RAISED
                    r = flow(s.finalbody, nxt)
                    if r in (_RAISED, _RETURNED):
                        return r
                    st = r
                    continue
                if isinstance(s, (ast.Assert,)):
                    scan_expr(st, s.test)
                    continue
                for node in ast.walk(s):
                    if isinstance(node, ast.Call):
                        handle_call(st, node)
            return st

        end = flow(method.body, _State())
        if isinstance(end, _State):
            exits.append(end)

        summ = _Summary()
        if exits:
            summ.marks = set.intersection(*(e.marked for e in exits))
            for e in exits:
                for g, site in e.pending.items():
                    summ.pending.setdefault(g, site)
            summ.marks -= set(summ.pending)
        if emit:
            for g, (attr, line) in sorted(summ.pending.items()):
                key = (method.name, g, line)
                if key in seen:
                    continue
                seen.add(key)
                self._emit(
                    self.tables_rel, line, "upload-mark-missing",
                    f"TableBuilder.{method.name}() writes staged attr "
                    f"self.{attr} (group '{g}') but a path reaches an "
                    f"exit without marking the group dirty: the next "
                    f"to_device() ships a stale plane", sup)
        return summ

    # --- external writers ---------------------------------------------
    def _check_extern_writes(self, sup_tables) -> None:
        for relpath, path in iter_source_files(self.repo, self.roots):
            if relpath == self.tables_rel:
                continue
            src = path.read_text()
            try:
                tree = ast.parse(src, filename=relpath)
            except SyntaxError:
                continue
            sup = parse_suppressions(src, relpath)
            self.findings.extend(sup.problems)
            for node in ast.walk(tree):
                targets = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                elif isinstance(node, ast.Delete):
                    targets = node.targets
                for t in targets:
                    base = _peel(t)
                    if not isinstance(base, ast.Attribute) or \
                            base.attr not in self.staged:
                        continue
                    owner = base.value
                    if isinstance(owner, ast.Attribute) and \
                            owner.attr == "builder":
                        self._emit(
                            relpath, base.lineno, "upload-extern-write",
                            f"write to builder.{base.attr} (staged, "
                            f"group '{self.staged[base.attr]}') from "
                            f"outside TableBuilder bypasses dirty-"
                            f"marking: go through a mutator", sup)


def uploads_lint(repo=None, tables_rel: str = TABLES_REL,
                 roots=UPLOAD_ROOTS, manifest=None) -> List[Finding]:
    """Run the pass; returns unsuppressed findings (empty == clean)."""
    if repo is None:
        repo = Path(__file__).resolve().parents[2]
    return UploadPass(Path(repo), tables_rel, roots, manifest).run()
