"""Shared Pallas availability / backend-dispatch helper (ISSUE 16).

The four kernel modules (ops/acl_mxu.py, ops/acl_bv.py, ops/lpm.py,
ops/session.py) all follow the same shape: a ``pl.pallas_call`` kernel
behind a backend dispatch with a bit-exact jnp reference rung. This
module is the ONE place that decides availability and dispatch, so the
modules can never disagree about when the compiled kernel serves:

- ``pallas_available()``: the jax.experimental.pallas import succeeds.
  Checked lazily and cached — a CPU-only run must never pay (or crash
  on) the Pallas import at module load, which is exactly what the old
  module-level import in acl_mxu.py did.
- ``get_pallas()``: the lazy import itself, raising an intelligible
  error naming the kernel caller instead of a bare ImportError deep
  inside a jit trace.
- ``use_pallas()``: the dispatch predicate — run the compiled kernel
  only on a real TPU backend; everywhere else (CPU harness, tests,
  meshes of virtual devices) the jnp reference serves. On a TPU
  backend an unimportable Pallas is an error. Pallas
  *interpret* mode stays reachable for the differential suites by
  passing ``interpret=True`` to the kernel entry points directly.

Selection is a separate concern: the impl ladders
(vpp_tpu/parallel/partition.py select_impl / select_fib_impl /
select_session_impl) take a ``pallas_ok`` eligibility bit that callers
resolve from ``use_pallas()`` AND their own structural gates (VMEM
fit, bv_ok/lpm_ok, standalone vs mesh) — the dispatch here is only the
last-line safety net that keeps an explicitly-knobbed pallas rung
bit-exact on a CPU run.
"""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=1)
def pallas_available() -> bool:
    """Whether jax.experimental.pallas imports in this environment.
    Cached: the probe runs at most once per process."""
    try:
        from jax.experimental import pallas  # noqa: F401
        from jax.experimental.pallas import tpu  # noqa: F401
    except Exception:  # noqa: BLE001 — any import failure = unavailable
        return False
    return True


def get_pallas(caller: str = "pallas kernel"):
    """The lazy import: returns ``(pl, pltpu)`` or raises naming the
    caller — kernel modules import THROUGH here so no module-level
    Pallas import ever runs on a plain CPU code path."""
    if not pallas_available():
        raise RuntimeError(
            f"{caller}: jax.experimental.pallas is not importable in "
            "this environment — the jnp reference rung must serve "
            "(ops/_pallas.use_pallas() gates dispatch; the impl "
            "ladders should never have selected a pallas rung here)")
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def out_struct(shape, dtype, *inputs):
    """A ``pallas_call`` output shape that varies over every mesh axis
    its inputs vary over: under ``shard_map`` (the rule-sharded MXU
    classify) JAX requires the kernel to declare it; elsewhere the set
    is empty."""
    import jax

    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma or None)


def use_pallas() -> bool:
    """The ONE backend-dispatch predicate shared by all kernel modules:
    compiled Pallas kernels serve on a real TPU backend only. CPU (and
    anything else) takes the bit-exact jnp reference rung — interpret
    mode is for the differential suites, not production dispatch (it
    is orders of magnitude slower than the jnp rung on CPU). A TPU
    backend without an importable Pallas is a broken installation,
    not a reason to serve the jnp rung quietly: it raises."""
    import jax

    if jax.default_backend() != "tpu":
        return False
    if not pallas_available():
        raise RuntimeError(
            "TPU backend without an importable jax.experimental.pallas "
            "(and its tpu module): the installation is incomplete")
    return True
