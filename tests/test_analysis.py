"""The in-tree analysis framework (ISSUE 5): fixture suite for every
``--jax`` and ``--threads`` rule (known-bad firing + suppressed twin),
the ImportCollector gap regressions, the clean-tree tier-1 hooks (the
same pattern test_exposition.py uses for --metrics/--counters), and the
runtime jit-compile guard — including the deliberately-recompiling
dataplane fixture the compile-budget guard must fail.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
TOOLS = REPO / "tools"
if str(TOOLS) not in sys.path:
    sys.path.insert(0, str(TOOLS))

from analysis.common import parse_suppressions  # noqa: E402
from analysis.imports import ImportCollector, style_problems  # noqa: E402
from analysis.jaxlint import jax_lint  # noqa: E402
from analysis.threadlint import threads_lint  # noqa: E402

MOD = "pkg/m.py"
SITE_MODULE = {(MOD, "<module>"): "test fixture"}


def run_jax(tmp_path, src, manifest=None, traced=None):
    (tmp_path / "pkg").mkdir(exist_ok=True)
    (tmp_path / "pkg" / "m.py").write_text(src)
    return jax_lint(tmp_path, roots=("pkg",),
                    jit_sites=manifest if manifest is not None else {},
                    traced_roots=traced if traced is not None else set())


def run_threads(tmp_path, src):
    (tmp_path / "pkg").mkdir(exist_ok=True)
    (tmp_path / "pkg" / "m.py").write_text(src)
    return threads_lint(tmp_path, roots=("pkg",))


def rules_of(findings):
    return sorted({f.rule for f in findings})


# --- tier-1 hooks: the passes must be CLEAN on the live tree ---------

def test_jax_lint_clean_tree():
    """Zero unsuppressed jax-pass findings on vpp_tpu/{ops,pipeline,
    parallel} — every host sync / tracer branch / jit site is either
    fixed or carries a reasoned `# jax-ok:` (ISSUE 5 acceptance)."""
    assert [str(f) for f in jax_lint(REPO)] == []


def test_threads_lint_clean_tree():
    """Zero unsuppressed lock-discipline findings on the concurrent
    modules — every shared attribute is locked, `_locked`-suffixed, or
    carries a reasoned `# unlocked:` (ISSUE 5 acceptance)."""
    assert [str(f) for f in threads_lint(REPO)] == []


# --- suppression syntax ----------------------------------------------

def test_bare_suppression_is_a_finding(tmp_path):
    src = "import threading\n# unlocked:\nX = 1\n"
    assert "bare-suppression" in rules_of(run_threads(tmp_path, src))
    src = "# jax-ok\nX = 1\n"
    assert "bare-suppression" in rules_of(run_jax(tmp_path, src))


def test_comment_block_suppression_covers_next_code_line():
    sup = parse_suppressions(
        "x = 1\n# jax-ok: spans the block\n# more words\ny = 2\n")
    assert 2 in sup.jax and 4 in sup.jax and 1 not in sup.jax


def test_suppression_token_in_string_literal_ignored():
    """A suppression-shaped token inside a STRING must not register —
    it would silently mask findings on that line (and a bare one must
    not fire the bare-suppression rule either)."""
    sup = parse_suppressions(
        'HELP = "annotate with # jax-ok: reason"\n'
        'MSG = "see # unlocked"\n')
    assert sup.jax == {} and sup.unlocked == {} and sup.problems == []


# --- --jax rules: firing + suppressed fixture per rule ---------------

KERNEL_ITEM = """\
import jax
import jax.numpy as jnp
def kernel(x):
    return x.item(){sup}
k = jax.jit(kernel)
"""


def test_jax_host_sync_item(tmp_path):
    bad = run_jax(tmp_path, KERNEL_ITEM.format(sup=""),
                  manifest=SITE_MODULE)
    assert rules_of(bad) == ["host-sync"]
    ok = run_jax(tmp_path,
                 KERNEL_ITEM.format(sup="  # jax-ok: test probe"),
                 manifest=SITE_MODULE)
    assert ok == []


def test_jax_host_sync_int_of_tracer(tmp_path):
    src = ("import jax\nimport jax.numpy as jnp\n"
           "def kernel(x):\n"
           "    y = jnp.sum(x)\n"
           "    return int(y){sup}\n"
           "k = jax.jit(kernel)\n")
    bad = run_jax(tmp_path, src.format(sup=""), manifest=SITE_MODULE)
    assert rules_of(bad) == ["host-sync"]
    ok = run_jax(tmp_path, src.format(sup="  # jax-ok: diagnostics"),
                 manifest=SITE_MODULE)
    assert ok == []
    # int() of a HOST value in traced code is fine
    good = ("import jax\n"
            "def kernel(x):\n"
            "    n = int(x.shape[0])\n"
            "    return x[:n]\n"
            "k = jax.jit(kernel)\n")
    assert run_jax(tmp_path, good, manifest=SITE_MODULE) == []


def test_jax_host_sync_np_asarray(tmp_path):
    src = ("import jax\nimport jax.numpy as jnp\nimport numpy as np\n"
           "def kernel(x):\n"
           "    z = jnp.abs(x)\n"
           "    return np.asarray(z){sup}\n"
           "k = jax.jit(kernel)\n")
    bad = run_jax(tmp_path, src.format(sup=""), manifest=SITE_MODULE)
    assert rules_of(bad) == ["host-sync"]
    ok = run_jax(tmp_path, src.format(sup="  # jax-ok: boundary copy"),
                 manifest=SITE_MODULE)
    assert ok == []
    # np.asarray of host constants in traced code is constant folding
    good = ("import jax\nimport numpy as np\n"
            "def kernel(x):\n"
            "    w = np.asarray([1, 2, 3])\n"
            "    return x + w.sum()\n"
            "k = jax.jit(kernel)\n")
    assert run_jax(tmp_path, good, manifest=SITE_MODULE) == []


def test_jax_tracer_branch(tmp_path):
    src = ("import jax\n"
           "def kernel(x):\n"
           "    if x > 0:{sup}\n"
           "        return x\n"
           "    return -x\n"
           "k = jax.jit(kernel)\n")
    bad = run_jax(tmp_path, src.format(sup=""), manifest=SITE_MODULE)
    assert rules_of(bad) == ["tracer-branch"]
    ok = run_jax(tmp_path,
                 src.format(sup="  # jax-ok: unit-test only path"),
                 manifest=SITE_MODULE)
    assert ok == []
    # `is None` is static at trace time — never a tracer branch
    good = ("import jax\n"
            "def kernel(x, now=None):\n"
            "    if now is not None:\n"
            "        x = x + now\n"
            "    return x\n"
            "k = jax.jit(kernel)\n")
    assert run_jax(tmp_path, good, manifest=SITE_MODULE) == []


def test_jax_tracer_while(tmp_path):
    src = ("import jax\nimport jax.numpy as jnp\n"
           "def kernel(x):\n"
           "    while jnp.any(x > 0):{sup}\n"
           "        x = x - 1\n"
           "    return x\n"
           "k = jax.jit(kernel)\n")
    bad = run_jax(tmp_path, src.format(sup=""), manifest=SITE_MODULE)
    assert rules_of(bad) == ["tracer-branch"]
    ok = run_jax(tmp_path, src.format(sup="  # jax-ok: bounded probe"),
                 manifest=SITE_MODULE)
    assert ok == []


def test_jax_host_sync_inside_except_handler(tmp_path):
    """except-handler bodies are traced code too (ast.excepthandler is
    neither stmt nor expr — a naive walker skips them)."""
    src = ("import jax\nimport jax.numpy as jnp\n"
           "def kernel(x):\n"
           "    try:\n"
           "        y = jnp.sum(x)\n"
           "    except ValueError:\n"
           "        return x.item()\n"
           "    return y\n"
           "k = jax.jit(kernel)\n")
    assert rules_of(run_jax(tmp_path, src,
                            manifest=SITE_MODULE)) == ["host-sync"]


PER_INSTANCE = """\
import jax
class Pump:
    def build(self):
        def loop(t):
            return t + self.k
        self.f = jax.jit(loop){sup}
"""


def test_jax_per_instance_jit(tmp_path):
    manifest = {(MOD, "Pump.build"): "test fixture"}
    bad = run_jax(tmp_path, PER_INSTANCE.format(sup=""),
                  manifest=manifest)
    assert rules_of(bad) == ["per-instance-jit"]
    ok = run_jax(
        tmp_path,
        PER_INSTANCE.format(sup="  # jax-ok: singleton by design"),
        manifest=manifest)
    assert ok == []
    # a module-level target resolved through the same method is fine
    good = ("import jax\n"
            "def chain(t):\n"
            "    return t\n"
            "class Pump:\n"
            "    def build(self):\n"
            "        self.f = jax.jit(chain)\n")
    assert run_jax(tmp_path, good, manifest=manifest) == []


def test_jax_jit_unregistered(tmp_path):
    src = ("import jax\n"
           "def kernel(x):\n"
           "    return x\n"
           "k = jax.jit(kernel){sup}\n")
    bad = run_jax(tmp_path, src.format(sup=""), manifest={})
    assert rules_of(bad) == ["jit-unregistered"]
    ok = run_jax(tmp_path,
                 src.format(sup="  # jax-ok: scratch experiment"),
                 manifest={})
    assert ok == []


def test_jax_manifest_stale(tmp_path):
    src = "import jax\nX = 1\n"
    bad = run_jax(tmp_path, src,
                  manifest={(MOD, "gone_factory"): "was removed"})
    assert rules_of(bad) == ["jit-manifest-stale"]
    bad = run_jax(tmp_path, src, traced={(MOD, "gone_kernel")})
    assert rules_of(bad) == ["jit-manifest-stale"]
    # stale entries anchor to line 1 of the named module: suppressible
    ok = run_jax(tmp_path, "# jax-ok: migration in flight\nX = 1\n",
                 manifest={(MOD, "gone_factory"): "was removed"})
    assert ok == []


def test_jax_float_literal_dtype(tmp_path):
    src = ("import jax\nimport jax.numpy as jnp\n"
           "def kernel(x):\n"
           "    return x * jnp.full((4,), 0.5){sup}\n"
           "k = jax.jit(kernel)\n")
    bad = run_jax(tmp_path, src.format(sup=""), manifest=SITE_MODULE)
    assert rules_of(bad) == ["float-literal-dtype"]
    ok = run_jax(tmp_path,
                 src.format(sup="  # jax-ok: f32-only test host"),
                 manifest=SITE_MODULE)
    assert ok == []
    good = ("import jax\nimport jax.numpy as jnp\n"
            "def kernel(x):\n"
            "    return x * jnp.full((4,), 0.5, dtype=jnp.float32)\n"
            "k = jax.jit(kernel)\n")
    assert run_jax(tmp_path, good, manifest=SITE_MODULE) == []
    # any float64 reference in the traced roots is drift
    bad = run_jax(tmp_path, "import jax.numpy as jnp\nD = jnp.float64\n")
    assert rules_of(bad) == ["float-literal-dtype"]


def test_jax_lru_cache_method(tmp_path):
    src = ("import functools\n"
           "class A:\n"
           "    @functools.lru_cache(maxsize=None)\n"
           "    def step(self, n):{sup}\n"
           "        return n\n")
    bad = run_jax(tmp_path, src.format(sup=""))
    assert rules_of(bad) == ["lru-cache-method"]
    ok = run_jax(tmp_path,
                 src.format(sup="  # jax-ok: frozen singleton"))
    # the finding anchors to the def line; the suppression rides it
    assert ok == []


def test_jax_unhashable_arg(tmp_path):
    src = ("import functools\n"
           "@functools.lru_cache(maxsize=None)\n"
           "def make(key):\n"
           "    return key\n"
           "make([1, 2]){sup}\n")
    bad = run_jax(tmp_path, src.format(sup=""))
    assert rules_of(bad) == ["unhashable-arg"]
    ok = run_jax(tmp_path, src.format(sup="  # jax-ok: raises in test"))
    assert ok == []
    good = src.replace("make([1, 2]){sup}\n", "make((1, 2))\n")
    assert run_jax(tmp_path, good) == []


# --- --threads rules: firing + suppressed fixture per rule -----------

UNLOCKED = """\
import threading
class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0
    def inc(self):
        with self._lock:
            self.n += 1
    def peek(self):
        return self.n{sup}
"""


def test_threads_unlocked_access(tmp_path):
    bad = run_threads(tmp_path, UNLOCKED.format(sup=""))
    assert rules_of(bad) == ["unlocked-access"]
    assert "C.n" in str(bad[0])
    ok = run_threads(
        tmp_path,
        UNLOCKED.format(sup="  # unlocked: monotonic counter peek"))
    assert ok == []


def test_threads_subscripted_access_still_seen(tmp_path):
    """`self._buf[0].x` / `self._buf[:n].any()` — the protected attr
    sits under a Subscript, so the OUTER attribute chain doesn't root
    at self; the inner access must still be recorded."""
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._buf = [0]\n"
           "    def put(self):\n"
           "        with self._lock:\n"
           "            self._buf = [1]\n"
           "    def peek(self):\n"
           "        return self._buf[0].bit_length()\n")
    bad = run_threads(tmp_path, src)
    assert rules_of(bad) == ["unlocked-access"]
    assert "C._buf" in str(bad[0])


def test_threads_unlocked_write(tmp_path):
    src = UNLOCKED.format(sup="") + (
        "    def reset(self):\n"
        "        self.n = 0\n")
    bad = run_threads(tmp_path, src)
    lines = [str(f) for f in bad]
    assert any("write in reset()" in s for s in lines)


def test_threads_locked_suffix_and_init_exempt(tmp_path):
    good = ("import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.n = 0\n"   # __init__ write: exempt
            "    def inc(self):\n"
            "        with self._lock:\n"
            "            self.n += 1\n"
            "    def _drain_locked(self):\n"  # caller holds the lock
            "        return self.n\n")
    assert run_threads(tmp_path, good) == []


def test_threads_closure_resets_held_locks(tmp_path):
    # a worker closure defined under `with self._lock` runs LATER —
    # its unlocked access must still be flagged
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.n = 0\n"
           "    def go(self):\n"
           "        with self._lock:\n"
           "            self.n += 1\n"
           "            def worker():\n"
           "                return self.n\n"
           "            return worker\n")
    bad = run_threads(tmp_path, src)
    assert rules_of(bad) == ["unlocked-access"]


LOCK_ORDER = """\
import threading
class D:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
    def f(self):
        with self._a:
            with self._b:{sup}
                pass
    def g(self):
        with self._b:
            with self._a:
                pass
"""


def test_threads_lock_order(tmp_path):
    bad = run_threads(tmp_path, LOCK_ORDER.format(sup=""))
    assert rules_of(bad) == ["lock-order"]
    ok = run_threads(
        tmp_path,
        LOCK_ORDER.format(sup="  # unlocked: g() is shutdown-only"))
    assert ok == []
    consistent = LOCK_ORDER.format(sup="").replace(
        "with self._b:\n            with self._a:",
        "with self._a:\n            with self._b:")
    assert run_threads(tmp_path, consistent) == []


def test_threads_lock_alias_followed(tmp_path):
    # commit_lock = self._lock (the Dataplane idiom): acquiring the
    # alias counts as holding the lock
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.commit_lock = self._lock\n"
           "        self.n = 0\n"
           "    def inc(self):\n"
           "        with self._lock:\n"
           "            self.n += 1\n"
           "    def inc2(self):\n"
           "        with self.commit_lock:\n"
           "            self.n += 1\n")
    bad = run_threads(tmp_path, src)
    # self.n is written under two DIFFERENT lock names; the majority
    # lock wins and the alias access is reported — unless the aliasing
    # is recognized. Either zero findings (alias unified) or none on
    # the locked sites; what must NOT happen is a false positive on
    # inc(). Current implementation treats the alias as its own lock
    # object, so inc2 keeps its own edge — assert no findings against
    # inc() itself.
    assert not any("inc()" in str(f) for f in bad)


# --- ImportCollector gap regressions (ISSUE 5 satellite) -------------

def _unused(src: str, tmp_path) -> list:
    p = tmp_path / "s.py"
    p.write_text(src)
    return [x for x in style_problems(p) if "unused import" in x]


def test_imports_string_annotation_counts_as_use(tmp_path):
    src = ("import collections\n"
           "def f(x: \"collections.OrderedDict\") -> None:\n"
           "    return None\n")
    assert _unused(src, tmp_path) == []
    src = ("from os import path\n"
           "def f() -> \"path\":\n"
           "    return None\n")
    assert _unused(src, tmp_path) == []


def test_imports_all_tuple_and_augassign(tmp_path):
    assert _unused("import os\n__all__ = (\"os\",)\n", tmp_path) == []
    assert _unused(
        "import os\n__all__ = []\n__all__ += [\"os\"]\n", tmp_path) == []
    assert _unused(
        "import os\n__all__: tuple = (\"os\",)\n", tmp_path) == []
    # a genuinely unused import still fires
    assert _unused("import os\n__all__ = (\"sys\",)\n", tmp_path) != []


def test_imports_dotted_alias_binds_alias(tmp_path):
    assert _unused("import os.path as p\nX = p.sep\n", tmp_path) == []
    out = _unused("import os.path as p\nX = 1\n", tmp_path)
    assert len(out) == 1 and "'p'" in out[0]


def test_imports_decorator_only_use(tmp_path):
    src = ("import functools\n"
           "@functools.lru_cache(maxsize=None)\n"
           "def f():\n"
           "    return 1\n")
    assert _unused(src, tmp_path) == []


# --- runtime jit-compile guard ---------------------------------------

def _tiny_dp():
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig

    dp = Dataplane(DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=8,
        fib_slots=16, sess_slots=64, nat_mappings=2, nat_backends=4))
    dp.add_uplink()
    dp.swap()
    return dp


def _pkts(n):
    from vpp_tpu.pipeline.vector import make_packet_vector

    return make_packet_vector(
        [{"src": "10.1.0.1", "dst": "10.1.1.2", "proto": 6,
          "sport": 1000, "dport": 80, "rx_if": 1}], n=n)


def test_compile_once_across_instances():
    """Two dataplanes with identical config share every step compile
    (the process-wide _JIT_STEPS cache): the second instance spends 0."""
    from vpp_tpu.pipeline import dataplane as dpmod

    dp1 = _tiny_dp()
    pkts = _pkts(8)
    dp1.process(pkts)  # warm (may compile if this shape is first)
    dp2 = _tiny_dp()
    with dpmod.jit_compile_budget(0) as guard:
        dp2.process(pkts)
    assert guard.spent == 0
    assert dpmod.jit_recompiles() == {}


def test_compile_guard_fails_recompiling_dataplane():
    """The deliberately-recompiling dataplane fixture (ISSUE 5
    acceptance): simulate the PR-4 fresh-closure bug by clearing the
    process-wide step cache between two identical-shape dataplanes —
    the SAME (variant, shape) traces twice, and the compile-budget
    guard must fail. Counter + cache state is restored so the
    end-of-session compile-once check sees the real tree, not this
    sabotage."""
    from vpp_tpu.pipeline import dataplane as dpmod

    steps_snap = dict(dpmod._JIT_STEPS)
    with dpmod._JIT_COMPILES_LOCK:
        counts_snap = dict(dpmod._JIT_COMPILES)
    try:
        pkts = _pkts(8)
        dp1 = _tiny_dp()
        dpmod._JIT_STEPS.clear()  # cold start, warm or not
        with pytest.raises(dpmod.JitBudgetExceeded) as exc:
            with dpmod.jit_compile_budget(1):
                dp1.process(pkts)          # the one budgeted compile
                dpmod._JIT_STEPS.clear()   # the PR-4 bug, simulated
                dp2 = _tiny_dp()
                dp2.process(pkts)          # same key+shape: re-trace
        assert "budget" in str(exc.value)
        # the contract break is independently visible to the runtime
        assert dpmod.jit_recompiles() != {}
    finally:
        dpmod._JIT_STEPS.clear()
        dpmod._JIT_STEPS.update(steps_snap)
        with dpmod._JIT_COMPILES_LOCK:
            dpmod._JIT_COMPILES.clear()
            dpmod._JIT_COMPILES.update(counts_snap)


@pytest.mark.jit_budget(4)
def test_compile_budget_fixture_green(jit_compile_budget):
    """The opt-in fixture in its intended green mode: a test that
    declares a budget and stays inside it passes (two same-shape steps
    cost at most one auto-variant compile)."""
    dp = _tiny_dp()
    pkts = _pkts(8)
    dp.process(pkts)
    dp.process(pkts)
    assert jit_compile_budget.spent <= 4


def test_jit_compiles_exported_and_surfaced():
    """vpp_tpu_jit_compiles_total{step=} reaches the scrape output and
    `show io` prints the compile-once summary (ISSUE 5 tentpole #3)."""
    from vpp_tpu.cli import DebugCLI
    from vpp_tpu.stats.collector import StatsCollector

    dp = _tiny_dp()
    dp.process(_pkts(8))
    coll = StatsCollector(dp)
    coll.publish()
    out = coll.registry.render("/stats")
    assert "vpp_tpu_jit_compiles_total" in out
    assert 'step="' in out
    cli = DebugCLI(dp)
    io_out = cli.run("show io")
    assert "jit compiles:" in io_out
    assert "RECOMPILED" not in io_out


def test_debug_jit_page_json():
    """/debug/jit serves the guard's full state (agent debug page)."""
    import json

    from vpp_tpu.cmd.agent import ContivAgent

    dp = _tiny_dp()
    dp.process(_pkts(8))
    page = json.loads(ContivAgent.debug_jit_json())
    assert set(page) == {"totals", "compiles", "recompiled"}
    assert page["recompiled"] == []
    assert any(c["count"] >= 1 for c in page["compiles"])


# =====================================================================
# ISSUE 20: device-boundary dataflow passes (--uploads / --transfers /
# --donate) and the runtime device-transfer guard.
# =====================================================================

from types import SimpleNamespace  # noqa: E402

from analysis.donatelint import donate_lint  # noqa: E402
from analysis.transferlint import transfers_lint  # noqa: E402
from analysis.uploadlint import uploads_lint  # noqa: E402

TBL = "pkg/tables.py"

# A minimal but complete tables.py: two groups, one ledger field, a
# TableBuilder whose base methods mark correctly. Fixture variants
# append methods / perturb groups from this known-clean core.
MINI_HEAD = '''\
_UPLOAD_GROUPS = {
    "acl": ("acl_rules", "acl_count"),
    "fib": ("fib_next_hop",),
}
SESSION_FIELDS = {"sess_key0": "u32"}


class DataplaneTables:
    acl_rules: object
    acl_count: object
    fib_next_hop: object
    sess_key0: object


class TableBuilder:
    def __init__(self):
        self.acl = []
        self.fib_next_hop = {}
        self._dirty = set(_UPLOAD_GROUPS)
        self._fib_dirty = set()

    def add_rule(self, r):
        self.acl.append(r)
        self._mark("acl")

    def _mark(self, group):
        self._dirty.add(group)
'''

MINI_PLACEMENTS = {
    "acl_rules": "group:acl",
    "acl_count": "group:acl",
    "fib_next_hop": "group:fib",
    "sess_key0": "ledger:SESSION_FIELDS",
}
MINI_STAGED = {"acl": "acl", "fib_next_hop": "fib"}


def _upload_ns(placements=MINI_PLACEMENTS, staged=MINI_STAGED,
               exempt=None):
    return SimpleNamespace(FIELD_PLACEMENTS=dict(placements),
                           STAGED_ATTRS=dict(staged),
                           EXEMPT_METHODS=dict(exempt or {}))


def _mini(body):
    return MINI_HEAD + "\n" + body


def run_uploads(tmp_path, tables_src, extra="", manifest=None):
    (tmp_path / "pkg").mkdir(exist_ok=True)
    (tmp_path / "pkg" / "tables.py").write_text(tables_src)
    (tmp_path / "pkg" / "other.py").write_text(extra)
    if manifest is None:
        manifest = _upload_ns()
    return uploads_lint(tmp_path, tables_rel=TBL, roots=("pkg",),
                        manifest=manifest)


def run_transfers(tmp_path, src, sites=None):
    (tmp_path / "pkg").mkdir(exist_ok=True)
    (tmp_path / "pkg" / "m.py").write_text(src)
    return transfers_lint(
        tmp_path, roots=("pkg",),
        manifest=SimpleNamespace(TRANSFER_SITES=dict(sites or {})))


def run_donate(tmp_path, src, jit_sites=None, calls=None):
    (tmp_path / "pkg").mkdir(exist_ok=True)
    (tmp_path / "pkg" / "m.py").write_text(src)
    return donate_lint(
        tmp_path, roots=("pkg",),
        manifest=SimpleNamespace(
            DONATED_JIT_SITES=dict(jit_sites or {}),
            DONATING_CALLS=dict(calls or {})))


# --- tier-1 hooks: the new passes must be CLEAN on the live tree -----

def test_uploads_lint_clean_tree():
    """Zero unsuppressed upload-placement/staleness findings: every
    DataplaneTables field has exactly one reviewed placement and every
    TableBuilder mutator marks its group on every path (ISSUE 20)."""
    assert [str(f) for f in uploads_lint(REPO)] == []


def test_transfers_lint_clean_tree():
    """Zero unsuppressed device->host fetches outside the approved
    transfer manifest (ISSUE 20)."""
    assert [str(f) for f in transfers_lint(REPO)] == []


def test_donate_lint_clean_tree():
    """Zero unsuppressed use-after-donate hazards through the
    registered donating jit sites (ISSUE 20)."""
    assert [str(f) for f in donate_lint(REPO)] == []


@pytest.mark.parametrize("module,name", [
    ("lint", "ROOTS"),
    ("analysis.uploadlint", "UPLOAD_ROOTS"),
    ("analysis.transferlint", "TRANSFER_ROOTS"),
    ("analysis.donatelint", "DONATE_ROOTS"),
])
def test_lint_roots_exist(module, name):
    """Every path a lint root tuple names exists: a missing root is
    scanned as an empty tree, so a deleted file would silently drop
    out of the lint instead of failing it."""
    import importlib

    roots = getattr(importlib.import_module(module), name)
    assert [r for r in roots if not (REPO / r).exists()] == []


# --- --uploads: mark dataflow ----------------------------------------

def test_upload_mini_fixture_clean(tmp_path):
    body = ("    def set_route(self, i, nh):\n"
            "        self.fib_next_hop[i] = nh\n"
            "        self._mark(\"fib\")\n")
    assert run_uploads(tmp_path, _mini(body)) == []


def test_upload_mark_missing_fires(tmp_path):
    """The deliberately-stale-group TableBuilder: a staged write whose
    method forgets to mark the group dirty."""
    body = ("    def set_route(self, i, nh):\n"
            "        self.fib_next_hop[i] = nh\n")
    f = run_uploads(tmp_path, _mini(body))
    assert rules_of(f) == ["upload-mark-missing"]
    assert "'fib'" in str(f[0])


def test_upload_mark_missing_suppressed(tmp_path):
    body = ("    def set_route(self, i, nh):\n"
            "        self.fib_next_hop[i] = nh  # upload-ok: fixture\n")
    assert run_uploads(tmp_path, _mini(body)) == []


def test_upload_mark_on_one_branch_only(tmp_path):
    """A path-sensitive miss: marked on the if-branch, forgotten on
    fall-through — still a stale-group hazard."""
    body = ("    def set_route(self, i, nh, flag):\n"
            "        self.fib_next_hop[i] = nh\n"
            "        if flag:\n"
            "            self._mark(\"fib\")\n")
    assert rules_of(run_uploads(tmp_path, _mini(body))) == \
        ["upload-mark-missing"]


def test_upload_raise_path_not_counted(tmp_path):
    """Paths that raise never reach to_device(): no finding."""
    body = ("    def set_route(self, i, nh):\n"
            "        self.fib_next_hop[i] = nh\n"
            "        if i < 0:\n"
            "            raise ValueError(i)\n"
            "        self._mark(\"fib\")\n")
    assert run_uploads(tmp_path, _mini(body)) == []


def test_upload_mark_all_assignment(tmp_path):
    """`self._dirty = set(_UPLOAD_GROUPS)` re-marks every group."""
    body = ("    def reset(self):\n"
            "        self.fib_next_hop = {}\n"
            "        self.acl = []\n"
            "        self._dirty = set(_UPLOAD_GROUPS)\n")
    assert run_uploads(tmp_path, _mini(body)) == []


def test_upload_dirty_field_foreign(tmp_path):
    """A field pushed into a sub-dirty set that its group does not
    own is never consulted by the incremental uploader."""
    body = ("    def poke(self):\n"
            "        self._fib_dirty.add(\"acl_rules\")\n")
    f = run_uploads(tmp_path, _mini(body))
    assert rules_of(f) == ["upload-dirty-field-foreign"]
    good = ("    def poke(self):\n"
            "        self._fib_dirty.add(\"fib_next_hop\")\n")
    assert run_uploads(tmp_path, _mini(good)) == []


# --- --uploads: placement + manifest rules ---------------------------

def test_upload_field_unplaced(tmp_path):
    src = _mini("    pass\n").replace(
        "    sess_key0: object",
        "    sess_key0: object\n    orphan: object")
    rules = rules_of(run_uploads(tmp_path, src))
    assert "upload-field-unplaced" in rules
    assert "upload-manifest-missing" in rules


def test_upload_field_multi(tmp_path):
    src = _mini("    pass\n").replace(
        '"fib": ("fib_next_hop",),',
        '"fib": ("fib_next_hop", "acl_rules"),')
    assert rules_of(run_uploads(tmp_path, src)) == ["upload-field-multi"]


def test_upload_group_stale(tmp_path):
    src = _mini("    pass\n").replace(
        '"fib": ("fib_next_hop",),',
        '"fib": ("fib_next_hop", "ghost"),')
    assert rules_of(run_uploads(tmp_path, src)) == ["upload-group-stale"]


def test_upload_manifest_stale_and_mismatch(tmp_path):
    man = _upload_ns(placements={**MINI_PLACEMENTS,
                                 "ghost": "group:acl",
                                 "fib_next_hop": "group:acl"})
    f = run_uploads(tmp_path, _mini("    pass\n"), manifest=man)
    assert rules_of(f) == ["upload-manifest-mismatch",
                           "upload-manifest-stale"]


def test_upload_exempt_stale(tmp_path):
    man = _upload_ns(exempt={"gone": "was removed"})
    f = run_uploads(tmp_path, _mini("    pass\n"), manifest=man)
    assert rules_of(f) == ["upload-exempt-stale"]


def test_upload_extern_write(tmp_path):
    """Writes to builder staged attrs from OUTSIDE TableBuilder bypass
    dirty-marking entirely."""
    extra = "def hack(dp):\n    dp.builder.acl[0] = 1\n"
    f = run_uploads(tmp_path, _mini("    pass\n"), extra=extra)
    assert rules_of(f) == ["upload-extern-write"]
    ok = ("def hack(dp):\n"
          "    dp.builder.acl[0] = 1  # upload-ok: fixture\n")
    assert run_uploads(tmp_path, _mini("    pass\n"), extra=ok) == []


def test_upload_seeded_mutation_dropped_mark(tmp_path):
    """ISSUE 20 acceptance: drop ONE dirty-mark from the real
    TableBuilder (a copy) — the pass must catch it with the default
    manifest. The unmutated tree is clean (clean-tree hook above)."""
    real = (REPO / "vpp_tpu" / "pipeline" / "tables.py").read_text()
    assert 'self._mark("acl")' in real
    mutated = real.replace('self._mark("acl")', "pass", 1)
    dst = tmp_path / "vpp_tpu" / "pipeline"
    dst.mkdir(parents=True)
    (dst / "tables.py").write_text(mutated)
    f = uploads_lint(tmp_path, roots=())
    assert "upload-mark-missing" in rules_of(f)
    assert any("'acl'" in str(x) for x in f)


# --- --transfers: host materialization of table columns --------------

PROBE = ("import numpy as np\n"
         "\n"
         "\n"
         "def probe(tables):\n"
         "    return np.asarray(tables.sess_key0)\n")


def test_transfer_host_fetch_fires(tmp_path):
    """ISSUE 20 acceptance: the seeded `np.asarray(tables.sess_key0)`
    mutation is a finding when its site is not in the manifest."""
    f = run_transfers(tmp_path, PROBE)
    assert rules_of(f) == ["transfer-host-fetch"]
    assert "probe" in str(f[0])


def test_transfer_host_fetch_suppressed(tmp_path):
    src = PROBE.replace(
        "np.asarray(tables.sess_key0)",
        "np.asarray(tables.sess_key0)  # transfer-ok: fixture")
    assert run_transfers(tmp_path, src) == []


def test_transfer_approved_site(tmp_path):
    assert run_transfers(
        tmp_path, PROBE, sites={(MOD, "probe"): "fixture"}) == []
    assert run_transfers(
        tmp_path, PROBE, sites={(MOD, "*"): "fixture"}) == []


def test_transfer_metadata_not_tainted(tmp_path):
    """shape/dtype/nbytes are host metadata, not device values."""
    src = ("import numpy as np\n"
           "\n"
           "\n"
           "def probe(tables):\n"
           "    return np.asarray(tables.sess_key0.shape)\n")
    assert run_transfers(tmp_path, src) == []


def test_transfer_scalar_sinks(tmp_path):
    """int()/.item() on a tables-reachable value sync the device too —
    taint flows through the local assignment."""
    src = ("def probe(tables):\n"
           "    a = tables.sess_time\n"
           "    return int(a), a.item()\n")
    f = run_transfers(tmp_path, src)
    assert rules_of(f) == ["transfer-host-fetch"]
    assert len(f) == 2


def test_transfer_site_stale(tmp_path):
    src = "def noop():\n    return 0\n"
    f = run_transfers(tmp_path, src,
                      sites={(MOD, "gone"): "x",
                             ("pkg/no.py", "*"): "x"})
    assert rules_of(f) == ["transfer-site-stale"]
    assert len(f) == 2


# --- --donate: use-after-donate --------------------------------------

DONATING = {(MOD, "run", "step"): ((0,), "fixture")}

USE_AFTER = ("def run(step, tables, x):\n"
             "    out = step(tables, x)\n"
             "    return out + tables.sum()\n")


def test_use_after_donate_fires(tmp_path):
    f = run_donate(tmp_path, USE_AFTER, calls=DONATING)
    assert rules_of(f) == ["use-after-donate"]
    assert "'tables'" in str(f[0])


def test_use_after_donate_suppressed(tmp_path):
    src = USE_AFTER.replace(
        "    return out + tables.sum()\n",
        "    return out + tables.sum()  # donate-ok: fixture\n")
    assert run_donate(tmp_path, src, calls=DONATING) == []


def test_use_after_donate_rebind_clears(tmp_path):
    """The threading idiom — rebinding from the call's result — is the
    sanctioned way to keep using the name."""
    src = ("def run(step, tables, x):\n"
           "    tables = step(tables, x)\n"
           "    return tables.sum()\n")
    assert run_donate(tmp_path, src, calls=DONATING) == []


def test_use_after_donate_loop_carried(tmp_path):
    """The NEXT iteration's call re-donates a buffer the first
    iteration already invalidated."""
    src = ("def run(step, tables):\n"
           "    for _ in range(3):\n"
           "        out = step(tables)\n"
           "    return out\n")
    f = run_donate(tmp_path, src, calls=DONATING)
    assert rules_of(f) == ["use-after-donate"]
    assert "NEXT iteration" in str(f[0])
    rebound = ("def run(step, tables):\n"
               "    for _ in range(3):\n"
               "        tables = step(tables)\n"
               "    return tables\n")
    assert run_donate(tmp_path, rebound, calls=DONATING) == []


JITSRC = ("import jax\n"
          "\n"
          "\n"
          "def build(g):\n"
          "    return jax.jit(g, donate_argnums=(0,))\n")


def test_donate_unregistered(tmp_path):
    f = run_donate(tmp_path, JITSRC)
    assert rules_of(f) == ["donate-unregistered"]
    assert run_donate(tmp_path, JITSRC,
                      jit_sites={(MOD, "build"): "fixture"}) == []
    empty = JITSRC.replace("donate_argnums=(0,)", "donate_argnums=()")
    assert run_donate(tmp_path, empty) == []


def test_donate_unregistered_suppressed(tmp_path):
    src = JITSRC.replace(
        "    return jax.jit(g, donate_argnums=(0,))\n",
        "    return jax.jit(g, donate_argnums=(0,))"
        "  # donate-ok: fixture\n")
    assert run_donate(tmp_path, src) == []


def test_donate_site_stale(tmp_path):
    src = "def noop():\n    return 0\n"
    f = run_donate(tmp_path, src,
                   jit_sites={(MOD, "gone"): "x"},
                   calls={(MOD, "noop", "step"): ((0,), "x")})
    assert rules_of(f) == ["donate-site-stale"]
    assert len(f) == 2


# --- runtime device-transfer guard -----------------------------------

def test_transfer_counter_and_totals():
    """count_device_transfer sums tree-leaf nbytes per site (8 B for
    leaves without nbytes, e.g. python scalars)."""
    import numpy as np

    from vpp_tpu.pipeline import dataplane as dpm

    with dpm._TRANSFER_LOCK:
        saved = dict(dpm._TRANSFER_BYTES)
        dpm._TRANSFER_BYTES.clear()
    try:
        dpm.count_device_transfer("t.site", np.zeros(4, np.uint32))
        dpm.count_device_transfer("t.site", (np.zeros(2, np.uint8), 7))
        assert dpm.device_transfer_totals()["t.site"] == 16 + 2 + 8
    finally:
        with dpm._TRANSFER_LOCK:
            dpm._TRANSFER_BYTES.clear()
            dpm._TRANSFER_BYTES.update(saved)


def test_transfer_budget_green():
    """An approved snapshot fetch under a generous budget: counted,
    inside budget, spent visible on the guard."""
    from vpp_tpu.pipeline import dataplane as dpm

    dp = _tiny_dp()
    with dpm.transfer_budget(1 << 20) as guard:
        snap = dp.fib_snapshot()
    assert snap is not None
    assert guard.spent > 0


def test_transfer_budget_oversized_fetch_fails():
    """ISSUE 20 acceptance: the deliberately-oversized fetch trips the
    budget with per-site attribution; process counters are restored."""
    from vpp_tpu.pipeline import dataplane as dpm

    dp = _tiny_dp()
    with dpm._TRANSFER_LOCK:
        saved = dict(dpm._TRANSFER_BYTES)
    try:
        with pytest.raises(dpm.TransferBudgetExceeded) as ei:
            with dpm.transfer_budget(4):
                dp.fib_snapshot()
        assert "fib.snapshot" in str(ei.value)
    finally:
        with dpm._TRANSFER_LOCK:
            dpm._TRANSFER_BYTES.clear()
            dpm._TRANSFER_BYTES.update(saved)


@pytest.mark.transfer_budget(1 << 20)
def test_transfer_budget_fixture(transfer_budget):
    """The opt-in pytest fixture mirrors jit_compile_budget: the
    marker sets the byte budget, exceeding it fails the test."""
    dp = _tiny_dp()
    dp.fib_snapshot()
    assert transfer_budget.spent > 0


def test_transfer_bytes_exported_and_cli():
    """vpp_tpu_device_transfer_bytes_total{site=} reaches the scrape
    output and `show io` prints the per-site transfer summary."""
    from vpp_tpu.cli import DebugCLI
    from vpp_tpu.stats.collector import StatsCollector

    dp = _tiny_dp()
    dp.fib_snapshot()
    coll = StatsCollector(dp)
    coll.publish()
    out = coll.registry.render("/stats")
    assert "vpp_tpu_device_transfer_bytes_total" in out
    assert 'site="' in out
    cli = DebugCLI(dp)
    assert "device transfer bytes:" in cli.run("show io")


def test_pump_window_fetch_is_rider_sized():
    """ISSUE 20 acceptance: a wire window through the pump fetches the
    packed descriptor rows + aux summary — never the VEC x snap payload
    matrix — proven with the runtime transfer budget around the run."""
    import time as _time

    import numpy as np
    from wire import make_frame

    from vpp_tpu.io import DataplanePump, IORingPair
    from vpp_tpu.native.pktio import PacketCodec
    from vpp_tpu.pipeline import dataplane as dpm
    from vpp_tpu.pipeline.dataplane import Dataplane, packed_input_zeros
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import VEC, Disposition

    dp = Dataplane(DataplaneConfig())
    a = dp.add_pod_interface(("default", "a"))
    b = dp.add_pod_interface(("default", "b"))
    dp.builder.add_route("10.1.1.2/32", a, Disposition.LOCAL)
    dp.builder.add_route("10.1.1.3/32", b, Disposition.LOCAL)
    dp.swap()
    dp.process_packed(packed_input_zeros(256))  # compile outside
    codec = PacketCodec()
    rings = IORingPair(n_slots=32)
    scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
    n_frames, per = 4, 8
    for k in range(n_frames):
        frames = [make_frame("10.1.1.2", "10.1.1.3", proto=17,
                             sport=20000 + k, dport=1000 + j)
                  for j in range(per)]
        cols, n = codec.parse(frames, a, scratch)
        assert rings.rx.push(cols, n, payload=scratch)
    payload_scale = VEC * rings.rx.snap  # one window of raw packet bytes
    with dpm.transfer_budget(64 * 1024) as guard:
        pump = DataplanePump(dp, rings).start()
        try:
            got = 0
            deadline = _time.monotonic() + 60
            while got < n_frames and _time.monotonic() < deadline:
                if rings.tx.peek() is None:
                    _time.sleep(0.005)
                    continue
                got += 1
                rings.tx.release()
            assert got == n_frames
        finally:
            pump.stop()
            rings.close()
    assert 0 < guard.spent < payload_scale
