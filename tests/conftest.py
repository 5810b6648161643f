"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Real TPU hardware in CI is a single chip; multi-chip sharding is validated
on virtual CPU devices (the driver separately dry-runs the multi-chip path
via __graft_entry__.dryrun_multichip).
"""

import os

# Must be set before jax is imported anywhere.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests run on the CPU backend whatever the host carries.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# `make test-race`: amplify thread interleavings by forcing preemption
# every few microseconds (default 5 ms) — the Go `-race` analog for the
# concurrency stress tests; races surface as corrupted ring/table state.
if os.environ.get("VPP_TPU_RACE"):
    import sys

    sys.setswitchinterval(5e-6)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "jit_budget(n): with the jit_compile_budget fixture, fail the "
        "test if it triggers more than n pipeline-step XLA compiles "
        "(pipeline/dataplane.py runtime jit-compile guard, ISSUE 5)",
    )
    config.addinivalue_line(
        "markers",
        "transfer_budget(n): with the transfer_budget fixture, fail "
        "the test if the counted fetch sites move more than n "
        "device->host bytes (pipeline/dataplane.py runtime "
        "device-transfer guard, ISSUE 20)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection schedule (tests/test_chaos.py; "
        "vpp_tpu/testing/faults.py). Bounded runtime; `make chaos` "
        "runs the suite; also marked slow so the tier-1 `-m 'not "
        "slow'` timing budget never pays for it",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` run "
        "(ROADMAP.md); run explicitly (e.g. `make chaos`)",
    )


@pytest.fixture
def jit_compile_budget(request):
    """Opt-in compile-budget guard: a test that requests this fixture
    declares (via ``@pytest.mark.jit_budget(n)``, default 0) how many
    pipeline-step compiles it is allowed to trigger; exceeding the
    budget fails the test. Budget 0 == "my shapes and variants are
    already warm" — the regression fence for the PR-4 bug class."""
    from vpp_tpu.pipeline import dataplane as _dp

    marker = request.node.get_closest_marker("jit_budget")
    budget = int(marker.args[0]) if marker and marker.args else 0
    guard = _dp.jit_compile_budget(budget)
    guard.__enter__()
    yield guard
    try:
        guard.__exit__(None, None, None)
    except _dp.JitBudgetExceeded as e:
        pytest.fail(str(e))


@pytest.fixture
def transfer_budget(request):
    """Opt-in device-transfer budget guard: a test that requests this
    fixture declares (via ``@pytest.mark.transfer_budget(n)``, default
    0) how many device->host bytes its counted fetch sites may move;
    exceeding the budget fails the test. The runtime face of the
    static ``--transfers`` pass: the manifest pins WHERE fetches
    happen, this pins HOW MUCH they move."""
    from vpp_tpu.pipeline import dataplane as _dp

    marker = request.node.get_closest_marker("transfer_budget")
    budget = int(marker.args[0]) if marker and marker.args else 0
    guard = _dp.transfer_budget(budget)
    guard.__enter__()
    yield guard
    try:
        guard.__exit__(None, None, None)
    except _dp.TransferBudgetExceeded as e:
        pytest.fail(str(e))


def pytest_sessionfinish(session, exitstatus):
    """The process-wide compile-once contract, verified over the WHOLE
    tier-1 run: every pipeline-step variant compiles at most once per
    (impl, skip, fast, form, call-shape) key per process. Consults the
    counter only if the dataplane was imported — this hook must not
    pull jax into a run that never used it."""
    import sys

    dp = sys.modules.get("vpp_tpu.pipeline.dataplane")
    if dp is None:
        return
    recompiled = dp.jit_recompiles()
    if recompiled:
        lines = [
            f"  {label} @ {n} compiles, shapes {sig!r}"
            for (label, sig), n in sorted(recompiled.items())
        ]
        print(
            "\njit-compile guard: compile-once contract BROKEN — "
            "step variants re-traced at identical call shapes (the "
            "PR-4 fresh-closure regression class):\n"
            + "\n".join(lines),
            file=sys.stderr,
        )
        session.exitstatus = 1
