#!/usr/bin/env python
"""In-tree analysis CLI (the metalinter CI stage analog of the
reference — README.md:36-40, docker/development Dockerfile.metalinter —
grown from a single-file linter into the tools/analysis/ package of
project-specific passes; ISSUE 5 tentpole).

Always runs the base style pass (parse, unused imports, bare except,
tabs/trailing whitespace, mutable defaults) over ROOTS. Flags add:

  --jax       tracer/recompile hygiene over vpp_tpu/{ops,pipeline,
              parallel}: host syncs inside traced code, Python control
              flow on tracers, per-instance jit closures (the PR-4 bug
              class), float64 drift, and the jit-registry manifest
              check (every jax.jit site enumerated in
              tools/analysis/jit_manifest.py). Suppress one line with
              `# jax-ok: <reason>`.
  --threads   lock discipline over io/pump.py, io/cluster_pump.py,
              kvstore/, stats/, trace/, pipeline/txn.py: attributes
              written under `with self._lock` must be accessed under
              it everywhere (`__init__` and `*_locked` methods
              exempt), and lock-nesting order must be acyclic.
              Suppress one line with `# unlocked: <reason>`.
  --metrics   Prometheus registry hygiene (imports jax; tier-1 runs it
              via tests/test_exposition.py).
  --counters  StepStats <-> Prometheus family parity (imports jax).
  --tables    BV classifier table invariants (imports jax; tier-1 runs
              it via tests/test_acl_bv.py).
  --partitions partition-rule completeness (ISSUE 12): every
              DataplaneTables field resolves to an explicit
              vpp_tpu/parallel/partition.py rule (sharded or
              replicated-by-design), no stale rules. Tier-1 runs it
              via tests/test_partition.py; `make lint` includes it.
  --uploads   upload-group consistency over pipeline/tables.py and
              its callers (ISSUE 20): every DataplaneTables field
              placed in exactly one _UPLOAD_GROUPS entry or state
              ledger (manifest: tools/analysis/upload_manifest.py),
              and every TableBuilder staged-attr write marks its
              group dirty on every path. Suppress one line with
              `# upload-ok: <reason>`.
  --transfers host materialization of table-scale device values
              (np.asarray / jax.device_get / .item() / int() on
              DataplaneTables-reachable values) outside the approved
              fetch sites (tools/analysis/transfer_manifest.py).
              Suppress with `# transfer-ok: <reason>`.
  --donate    use-after-donate over the registered donating jit call
              sites (jit_manifest.DONATING_CALLS), plus unregistered
              non-empty donate_argnums detection. Suppress with
              `# donate-ok: <reason>`.

Exit code 1 if anything fires. `make lint` runs the base + --jax +
--threads + --uploads + --transfers + --donate (the pure-AST passes).
Rule catalog + suppression syntax: docs/STATIC_ANALYSIS.md.
"""

from __future__ import annotations

import sys
from pathlib import Path

_TOOLS = Path(__file__).resolve().parent
if str(_TOOLS) not in sys.path:  # lint.py is loaded by path from tests
    sys.path.insert(0, str(_TOOLS))

from analysis.imports import style_problems  # noqa: E402
from analysis.jaxlint import jax_lint  # noqa: E402
from analysis.registries import (  # noqa: E402  (re-exported: tier-1
    counters_lint,                 # loads lint.py by path and calls
    metrics_lint,                  # these directly)
    partitions_lint,
    tables_lint,
)
from analysis.threadlint import threads_lint  # noqa: E402
from analysis.uploadlint import uploads_lint  # noqa: E402
from analysis.transferlint import transfers_lint  # noqa: E402
from analysis.donatelint import donate_lint  # noqa: E402

ROOTS = ("vpp_tpu", "tests", "chip_smoke.py", "__graft_entry__.py",
         "tools")


def lint_file(path: Path) -> list:
    """Base style pass on one file (kept as the public per-file API)."""
    return style_problems(path)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    repo = Path(__file__).resolve().parent.parent
    files = []
    for root in ROOTS:
        p = repo / root
        if p.is_file():
            files.append(p)
        else:
            files.extend(sorted(p.rglob("*.py")))
    all_problems = []
    for f in files:
        if "__pycache__" in str(f):
            continue
        all_problems.extend(lint_file(f))
    if "--jax" in argv:
        all_problems.extend(str(f) for f in jax_lint(repo))
    if "--threads" in argv:
        all_problems.extend(str(f) for f in threads_lint(repo))
    if "--uploads" in argv:
        all_problems.extend(str(f) for f in uploads_lint(repo))
    if "--transfers" in argv:
        all_problems.extend(str(f) for f in transfers_lint(repo))
    if "--donate" in argv:
        all_problems.extend(str(f) for f in donate_lint(repo))
    if "--metrics" in argv:
        all_problems.extend(metrics_lint())
    if "--counters" in argv:
        all_problems.extend(counters_lint())
    if "--tables" in argv:
        all_problems.extend(tables_lint())
    if "--partitions" in argv:
        all_problems.extend(partitions_lint())
    # --jax and --threads both report bare suppressions; dedupe
    seen, unique = set(), []
    for p in all_problems:
        if str(p) not in seen:
            seen.add(str(p))
            unique.append(p)
    for p in unique:
        print(p)
    print(f"lint: {len(files)} files, {len(unique)} problems")
    return 1 if unique else 0


if __name__ == "__main__":
    sys.exit(main())
