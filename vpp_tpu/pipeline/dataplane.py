"""Dataplane: the host-side handle on the device packet pipeline.

Owns the staging TableBuilder, the live DataplaneTables epoch, the
interface registry (pod ↔ interface index) and the jitted pipeline step.
Mutators stage changes in the builder; ``swap()`` publishes a new table
epoch atomically (carrying live session state over), the functional
analog of VPP's config transactions hitting the running graph.

Reference analogs: the vswitch side of plugins/contiv (interface
creation per pod) + vpp-agent applying NB config to VPP.
"""

from __future__ import annotations

import threading
import time as _time

import numpy as np
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from vpp_tpu.ir.rule import PodID
from vpp_tpu.pipeline.graph import (
    StepResult,
    make_pipeline_step,
)
from vpp_tpu.pipeline.tables import (
    DataplaneConfig,
    DataplaneTables,
    InterfaceType,
    TableBuilder,
)
from vpp_tpu.ops.vxlan import vxlan_encap
from vpp_tpu.pipeline.vector import Disposition, PacketVector
from vpp_tpu.trace import spans
from vpp_tpu.trace.timed import Timed


def _packed_call(step, with_aux: bool = False, tel: str = "off"):
    """Wrap a pipeline step with a bit-packed IO boundary: ONE [5, B]
    int32 input and ONE [5, B] int32 output.

    Returns ``(written, out[, aux])``. ``written`` is a dict holding
    only the table leaves the traced step writes (``_written_leaves``):
    for the default config the reflective and NAT session columns,
    their two sweep cursors and ``fib_ecmp_c`` — 20 of 174. Every other
    leaf stays with the tables object the caller passed in, which the
    caller owns (nothing of it is donated): the program has no output
    for it, so XLA neither copies it nor allocates a buffer for it,
    and ``tables._replace(**written)`` is the step's new tables.
    ``_packed_tables`` is the same boundary returning the whole tables
    pytree, for the ring window program, whose carry is donated whole.

    ``with_aux=True`` additionally returns a ``[PACKED_AUX_ROWS]``
    int32 summary whose rows are named by ``PACKED_AUX_SCHEMA`` (the
    ONE schema constant every dispatch form — packed, chained, ring —
    derives its aux width from; widening the rider is an edit to that
    tuple plus one row expression below, never three hand-edited
    paths). Rows 3/4 sum the reflective + NAT tables, rows 5-7 are
    the per-packet ML stage's verdict counters (ISSUE 10), rows 8/9
    the device-telemetry counters (ISSUE 11). It rides the SAME
    device program and the same result fetch as the packed output
    (40 bytes, not a second round trip), so the pump can count
    fast-path batches, hit percentage, table congestion, ML verdicts
    and telemetry activity without widening the 20 B/packet boundary.

    ``tel`` (trace-time static — the step-factory gate of
    ops/telemetry.py) widens the call signature: below "off" the run
    is the classic ``(tables, flat, now)``; with telemetry on it is
    ``(tables, flat, now, rx_stamp, now_us)`` where ``rx_stamp`` is
    the batch's rx-enqueue microsecond stamp (the spare descriptor
    lane — 0 = unstamped, not observed) and ``now_us`` the dispatch
    clock; the wire latency ``now_us − rx_stamp`` is bucketed into
    the device-resident log2 histogram AFTER the step, inside the
    same program.

    Every host↔device transfer is a round trip; the unpacked path
    costs ~13 of them per frame (9 column uploads + 4 result
    fetches). r3 packed that into one [9,B] up / one [10,B] down
    transfer; this layout additionally bit-packs the
    sub-32-bit header fields so the boundary is 20 B/packet each way
    instead of 36/40 — on a bandwidth-limited transport
    bytes-per-packet IS the wire-path throughput ceiling.

    Input rows (uint32 bit layout):
      0: src_ip            1: dst_ip
      2: sport<<16 | dport
      3: pkt_len<<16 | proto<<8 | ttl
      4: rx_if<<8 | flags
    Output rows:
      0: src_ip            1: dst_ip
      2: sport<<16 | dport
      3: drop_cause<<28 | disp<<24 | ttl<<16 | tx_if
         (tx_if 0xFFFF == none/-1; disp < 16, drop_cause = DROP_* < 16 —
         the spare high nibble carries the error-drop attribution so the
         host IO path can generate ICMP errors, graph.py DROP_*)
      4: next_hop
    proto and pkt_len are invariant through the pipeline (NAT rewrites
    addresses/ports, never protocol or length), so the tx side reuses
    the rx ring columns for them — they don't travel back.
    """
    full = _packed_tables(step, with_aux=with_aux, tel=tel)

    def run(tables, *args):
        new_tables, *rest = full(tables, *args)
        return (_written_leaves(tables, new_tables), *rest)

    return run


def _written_leaves(tables, new_tables) -> dict:
    """The leaves of ``new_tables`` the traced step wrote, by field
    name: every output leaf that is not its own input. A leaf the step
    only reads comes back as the very tracer it went in as — the stages
    rebuild the tables with ``_replace`` and ``lax.cond`` forwards a
    pass-through operand — so its output var IS its input var in the
    traced program. Whatever cannot be told apart that way counts as
    written, which is the safe side: it is returned and committed."""
    return {f: b for f, a, b in zip(tables._fields, tables, new_tables)
            if b is not a}


def _packed_tables(step, with_aux: bool = False, tel: str = "off"):
    """``_packed_call``'s boundary returning the whole tables pytree
    (``(tables', out[, aux])``): what ``_packed_call`` filters, and the
    per-slot body of the ring window program."""

    def _core(tables, flat, now, rx_stamp, now_us):
        from jax import lax

        f = lax.bitcast_convert_type(flat, jnp.uint32)

        def i32(x):
            return x.astype(jnp.int32)

        pv = PacketVector(
            src_ip=f[0],
            dst_ip=f[1],
            proto=i32((f[3] >> 8) & 0xFF),
            sport=i32(f[2] >> 16),
            dport=i32(f[2] & 0xFFFF),
            ttl=i32(f[3] & 0xFF),
            pkt_len=i32(f[3] >> 16),
            rx_if=i32(f[4] >> 8),
            flags=i32(f[4] & 0xFF),
        )
        res = step(tables, pv, now)
        out_tables = res.tables
        tel_observed = jnp.int32(0)
        # jax-ok: tel is a trace-time-static step-factory gate (a
        # Python string baked into the jit key), not a tracer branch
        if tel != "off":
            from vpp_tpu.ops.telemetry import tel_latency_update

            # a zero stamp means "not stamped" (warm-up frames, ICMP
            # probes, chain padding); negative latency (clock wrap,
            # bogus stamp) is equally unobserved
            lat = now_us - rx_stamp
            observe = res.pkts.valid & (rx_stamp > 0) & (lat >= 0)
            out_tables, tel_observed = tel_latency_update(
                out_tables, observe,
                jnp.broadcast_to(lat, res.pkts.valid.shape))

        def u32(x):
            return x.astype(jnp.uint32)

        out = jnp.stack([
            res.pkts.src_ip,
            res.pkts.dst_ip,
            (u32(res.pkts.sport) << 16) | (u32(res.pkts.dport) & 0xFFFF),
            ((u32(res.drop_cause) & 0xF) << 28)
            | ((u32(res.disp) & 0xF) << 24)
            | ((u32(res.pkts.ttl) & 0xFF) << 16)
            | (u32(res.tx_if) & 0xFFFF),
            res.next_hop,
        ])
        packed = lax.bitcast_convert_type(out, jnp.int32)
        if with_aux:
            s = res.stats
            # row ORDER is PACKED_AUX_SCHEMA — keep the two in sync
            aux = jnp.stack([
                s.fastpath, s.rx, s.sess_hits,
                s.sess_insert_fail + s.natsess_insert_fail,
                (s.sess_evict_expired + s.sess_evict_victim
                 + s.natsess_evict_expired + s.natsess_evict_victim),
                s.ml_scored, s.ml_flagged, s.ml_drops,
                tel_observed, s.tel_sketched,
                s.tnt_limited, s.tnt_qfail,
            ]).astype(jnp.int32)
            return out_tables, packed, aux
        return out_tables, packed

    if tel == "off":
        # the pre-telemetry call signature: the off state adds no
        # arguments and no device work (the telemetry aux rows fold
        # to constants XLA keeps as two zero lanes of the rider)
        def run(tables, flat, now):
            return _core(tables, flat, now, jnp.int32(0), jnp.int32(0))

        return run
    return _core


def _chained_call(step, with_aux: bool = False, tel: str = "off"):
    """K packed steps in ONE device program: ``lax.scan`` over a
    [K, 5, B] stack of packed batches, session tables threaded
    batch-to-batch exactly as K separate dispatches would. One
    dispatch + one sync amortizes the per-step PJRT round trip across
    K frames —
    the 'K-chained device steps synced once' lever of docs/LATENCY.md
    (VERDICT r3 Next #4). Latency of the FIRST frame rises to the
    chain's span, so this serves throughput-with-bounded-sync, not
    single-frame latency. Returns ``(written, outs[, auxs])``: the
    table leaves one packed step writes (``_packed_call``) after the
    K-th step, the [K, 5, B] results and, with ``with_aux``, the
    stacked [K, PACKED_AUX_ROWS] aux summaries. Only the written
    leaves ride the scan carry; the rest are closed over, read-only.
    With ``tel`` on, the scan additionally carries per-sub-batch rx
    stamps ([K] int32 µs) and the dispatch clock, feeding the device
    latency histogram exactly like K separate packed dispatches
    would."""
    full = _packed_tables(step, with_aux=with_aux, tel=tel)

    def _scan(tables, xs, sub):
        from jax import lax

        # the written set of one sub-step, read off a trace of it
        x0 = jax.tree_util.tree_map(lambda a: a[0], xs)
        names = tuple(jax.eval_shape(
            lambda t, x: _written_leaves(t, sub(t, x)[0]), tables, x0))

        def body(carry, x):
            new_tables, *rest = sub(tables._replace(**carry), x)
            return {n: getattr(new_tables, n) for n in names}, tuple(rest)

        carry, ys = lax.scan(
            body, {n: getattr(tables, n) for n in names}, xs)
        return (carry, *ys)

    def run_off(tables, flats, now):
        return _scan(tables, flats, lambda tbl, flat: full(tbl, flat, now))

    def run_tel(tables, flats, now, rx_stamps, now_us):
        return _scan(tables, (flats, rx_stamps),
                     lambda tbl, xs: full(tbl, xs[0], now, xs[1], now_us))

    return run_off if tel == "off" else run_tel


# packed-boundary shape: [PACKED_IN_ROWS, B] in, [PACKED_OUT_ROWS_N, B] out
PACKED_IN_ROWS = 5
PACKED_OUT_ROWS_N = 5
# The aux-rider schema: row names of the per-batch int32 summary
# _packed_call(with_aux=True) returns, IN ORDER. This tuple is the ONE
# width authority for every dispatch form — packed, chained and the
# device-ring window program all derive their aux shape from it (and
# tests/test_telemetry.py pins all three against it), so widening the
# rider is an edit HERE plus the matching row expression in
# _packed_call, never three hand-edited paths. History: [3] (fastpath
# trio, PR 3) → [5] (+session pressure, PR 6) → [8] (+ML verdicts,
# PR 9) → [10] (+device telemetry, PR 10 / ISSUE 11) → [12]
# (+tenancy counters, ISSUE 14).
PACKED_AUX_SCHEMA = (
    "fastpath", "rx", "sess_hits",        # two-tier dispatch trio
    "insert_fails", "evictions",          # session-table pressure
    "ml_scored", "ml_flagged", "ml_drops",  # ML-stage verdicts
    "tel_observed", "tel_sketched",       # device telemetry (ISSUE 11)
    "tnt_limited", "tnt_qfail",           # tenancy (ISSUE 14): rate-
                                          # limit drops + slice quota
                                          # insert failures
)
PACKED_AUX_ROWS = len(PACKED_AUX_SCHEMA)


def _ring_call(step, slots: int, tel: str = "off"):
    """Device-resident descriptor-ring window program (ISSUE 7): ONE
    dispatch processes up to ``slots`` packed frames without any host
    callback in between.

    The host stages compacted [5, B] descriptors (20 B/packet, the
    ``_packed_call`` layout) into the slots of an rx ring window
    (io/rings.py DeviceDescRing) and ships the whole window as one
    transfer; on-device, a ``lax.while_loop`` polls the rx cursor
    against the shipped tail, runs the fused step per slot and appends
    the verdict descriptors + aux summaries to the device tx ring. The
    tx ring travels back in the window's ONE result fetch — the
    aux-rider pattern of PR 3/PR 6 generalized to the whole wire path —
    so the steady state of the persistent pump is io_callback-free:
    one host↔device exchange per window replaces the two ordered
    blocking callbacks per frame the r6 resident loop paid
    (pipeline/persistent.py holds the host half and the latency math).

    ``slots`` is config-static shape (``io.io_ring_slots``), part of
    the jit-cache key exactly like ``sess_ways`` rides the session
    arrays' shape. ``rx_now`` carries a per-slot timestamp so a window
    is bit-exact with the same frames issued as individual
    ``process_packed`` calls — the differential-test contract. The
    frame cursor is device-resident: it rides the window-to-window
    carry next to the session tables (the way sweep cursors ride the
    tables pytree), so consumed-frame accounting never costs a
    dedicated host sync.

    Signature (donations in the jit wrapper, ``_jitted_step``):
      (tables, cursor, rx_ring [S,5,B], rx_now [S], rx_tail) ->
      (tables', cursor + consumed, tx_ring [S,5,B],
       aux_ring [S, PACKED_AUX_ROWS])

    With ``tel`` on (ISSUE 11) the window additionally carries the
    per-slot rx-enqueue stamp lane ``rx_stamp [S]`` (µs — the pump
    stamps each frame at staging; one frame occupies one slot in
    persistent mode, so a slot-granular stamp IS per-frame) plus the
    dispatch clock ``now_us``; the program buckets each packet's
    ``now_us − rx_stamp`` into the device-resident latency histogram
    at tx-append, and the accumulated telemetry planes ride back as a
    widened aux rider (``pack_tel_rider``) in the window's ONE
    existing result fetch — ``io_callbacks`` stays 0 by construction:
      (tables, cursor, rx_ring, rx_now, rx_stamp [S], now_us,
       rx_tail) ->
      (tables', cursor + consumed, tx_ring, aux_ring,
       tel [tel_rider_width])
    """
    packed = _packed_tables(step, with_aux=True, tel=tel)

    def _loop(tables, cursor, rx_ring, rx_now, rx_stamp, now_us,
              rx_tail):
        from jax import lax

        tx_ring0 = jnp.zeros_like(rx_ring)
        aux_ring0 = jnp.zeros((slots, PACKED_AUX_ROWS), jnp.int32)

        def cond(carry):
            _tables, head, _tx, _aux = carry
            return head < rx_tail

        def body(carry):
            tbl, head, tx, auxs = carry
            flat = lax.dynamic_index_in_dim(rx_ring, head, 0,
                                            keepdims=False)
            # jax-ok: tel is a trace-time-static step-factory gate
            if tel == "off":
                tbl2, out, aux = packed(tbl, flat, rx_now[head])
            else:
                tbl2, out, aux = packed(tbl, flat, rx_now[head],
                                        rx_stamp[head], now_us)
            tx = lax.dynamic_update_index_in_dim(tx, out, head, 0)
            auxs = lax.dynamic_update_index_in_dim(auxs, aux, head, 0)
            return tbl2, head + jnp.int32(1), tx, auxs

        tables, head, tx_ring, aux_ring = lax.while_loop(
            cond, body, (tables, jnp.int32(0), tx_ring0, aux_ring0))
        return tables, cursor + head, tx_ring, aux_ring

    if tel == "off":
        def run(tables, cursor, rx_ring, rx_now, rx_tail):
            return _loop(tables, cursor, rx_ring, rx_now, None,
                         jnp.int32(0), rx_tail)

        return run

    def run_tel(tables, cursor, rx_ring, rx_now, rx_stamp, now_us,
                rx_tail):
        from vpp_tpu.ops.telemetry import pack_tel_rider

        tables, cursor, tx_ring, aux_ring = _loop(
            tables, cursor, rx_ring, rx_now, rx_stamp, now_us, rx_tail)
        return tables, cursor, tx_ring, aux_ring, pack_tel_rider(tables)

    return run_tel


# Jitted step variants, shared PROCESS-WIDE across Dataplane instances
# (keyed by the selection gates + call form): make_pipeline_step is
# memoized so the underlying function identity is stable, and sharing
# the jit wrappers too means N dataplanes in one process (tests, the
# benchmark, multi-instance agents) compile each variant once.
_JIT_STEPS: Dict[tuple, object] = {}

# Runtime jit-compile guard (ISSUE 5 tentpole): every TRACE of a step
# variant is counted per (step key, argument-shape signature). A healthy
# process compiles each (impl, skip, fast, form, call-shape) exactly
# once; a count of 2+ IS the PR-4 regression class (a fresh-closure
# factory silently re-tracing per instance) happening live. Exported as
# ``vpp_tpu_jit_compiles_total{step=}`` (stats/collector.py), shown by
# `show io` and /debug/jit, enforced by the tests/conftest.py
# jit_compile_budget fixture and the end-of-session recompile check.
_JIT_COMPILES: Dict[tuple, int] = {}
_JIT_COMPILES_LOCK = threading.Lock()


def _step_label(impl: str, skip_local: bool, fast: bool, form: str,
                sweep_stride: int, ring_slots: int = 0,
                ml_mode: str = "off", ml_kind: str = "mlp",
                tel_mode: str = "off", tnt_mode: str = "off",
                fib_impl: str = "dense",
                sess_impl: str = "gather",
                sess_hash: str = "fwd",
                overlay: str = "off") -> str:
    from vpp_tpu.pipeline.graph import SWEEP_STRIDE_DEFAULT

    return "{}{}{}{}{}{}{}{}{}{}{}_{}".format(
        impl, "_nolocal" if skip_local else "", "_auto" if fast else "",
        ("" if ml_mode == "off"
         else f"_ml{ml_mode}"
         + ("_forest" if ml_kind == "forest" else "")),
        "" if tel_mode == "off" else f"_tel{tel_mode}",
        "" if tnt_mode == "off" else "_tenancy",
        "" if fib_impl == "dense" else f"_fib{fib_impl}",
        "" if sess_impl == "gather" else f"_sess{sess_impl}",
        "" if sess_hash == "fwd" else f"_h{sess_hash}",
        "" if overlay == "off" else f"_o{overlay}",
        ("" if sweep_stride == SWEEP_STRIDE_DEFAULT
         else f"_sw{sweep_stride}"),
        f"{form}{ring_slots}" if form == "ring" else form)


def _shape_sig(args, kwargs) -> tuple:
    def leaf_sig(x):
        shape = getattr(x, "shape", None)
        if shape is None:
            return type(x).__name__
        return (tuple(shape), str(getattr(x, "dtype", "?")))

    leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
    return tuple(leaf_sig(x) for x in leaves)


def _counting(label: str, fn):
    """Wrap ``fn`` so each TRACE (the python body running under jit —
    once per compile, never on cache hits) bumps the compile counter.
    Must wrap the OUTERMOST callable handed to jax.jit: an inner
    function can legitimately re-run within one compile (lax.scan
    traces its body twice), which would double-count."""

    def traced(*args, **kwargs):
        key = (label, _shape_sig(args, kwargs))
        with _JIT_COMPILES_LOCK:
            _JIT_COMPILES[key] = _JIT_COMPILES.get(key, 0) + 1
        return fn(*args, **kwargs)

    traced.__name__ = getattr(fn, "__name__", label)
    return traced


def jit_compile_counts() -> Dict[tuple, int]:
    """Snapshot of {(step label, shape signature): compile count}."""
    with _JIT_COMPILES_LOCK:
        return dict(_JIT_COMPILES)


def jit_compile_totals() -> Dict[str, int]:
    """Compiles per step label (the ``step=`` axis of
    ``vpp_tpu_jit_compiles_total``)."""
    totals: Dict[str, int] = {}
    with _JIT_COMPILES_LOCK:
        for (label, _sig), n in _JIT_COMPILES.items():
            totals[label] = totals.get(label, 0) + n
    return totals


def jit_recompiles() -> Dict[tuple, int]:
    """The violations: (step label, shape signature) keys traced more
    than once in this process. Non-empty == the compile-once contract
    is broken (tests/conftest.py fails the session on it)."""
    with _JIT_COMPILES_LOCK:
        return {k: n for k, n in _JIT_COMPILES.items() if n > 1}


class JitBudgetExceeded(AssertionError):
    """Raised by jit_compile_budget() when a scope compiles more step
    programs than it declared."""


class _JitBudget:
    def __init__(self, budget: int):
        self.budget = budget
        self._before: Optional[Dict[tuple, int]] = None

    def __enter__(self) -> "_JitBudget":
        self._before = jit_compile_counts()
        return self

    @property
    def spent(self) -> int:
        before = self._before or {}
        return (sum(jit_compile_counts().values())
                - sum(before.values()))

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            return
        before = self._before or {}
        after = jit_compile_counts()
        new = {k: n - before.get(k, 0) for k, n in after.items()
               if n - before.get(k, 0) > 0}
        spent = sum(new.values())
        if spent > self.budget:
            detail = ", ".join(
                f"{label}@{n}x" for (label, _sig), n in sorted(new.items()))
            raise JitBudgetExceeded(
                f"pipeline-step jit compile budget exceeded: {spent} "
                f"compiles > declared budget {self.budget} ({detail})")


def jit_compile_budget(budget: int) -> _JitBudget:
    """Context manager: fail if the enclosed scope triggers more than
    ``budget`` pipeline-step compiles. The pytest fixture of the same
    name (tests/conftest.py) wraps a whole test in one."""
    return _JitBudget(budget)


# Runtime device-transfer guard (ISSUE 20): the static ``--transfers``
# pass pins WHERE device->host fetches may happen (the manifest in
# tools/analysis/transfer_manifest.py); this counter proves HOW MUCH
# each approved site actually moves at run time. Every sanctioned fetch
# point funnels its device_get result through count_device_transfer(),
# keyed by site. Exported as
# ``vpp_tpu_device_transfer_bytes_total{site=}`` (stats/collector.py),
# shown by `show io`, enforced per-test by the opt-in transfer_budget
# fixture (tests/conftest.py) — the wire/persistent paths must fetch
# rider/descriptor bytes per window, never table columns ("~270 MB
# crosses the transport" was the PR-6/8/12 regression class).
_TRANSFER_BYTES: Dict[str, int] = {}
_TRANSFER_LOCK = threading.Lock()


def count_device_transfer(site: str, fetched) -> None:
    """Charge ``fetched``'s array bytes (any pytree of host/device
    arrays; scalars count their itemsize) to ``site``. Call it on the
    device_get RESULT at every approved fetch point — the charge is
    the bytes that actually crossed the transport."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(fetched):
        nb = getattr(leaf, "nbytes", None)
        total += int(nb) if nb is not None else 8
    with _TRANSFER_LOCK:
        _TRANSFER_BYTES[site] = _TRANSFER_BYTES.get(site, 0) + total


def device_transfer_totals() -> Dict[str, int]:
    """Snapshot of {site: device->host bytes fetched} this process
    (the ``site=`` axis of ``vpp_tpu_device_transfer_bytes_total``)."""
    with _TRANSFER_LOCK:
        return dict(_TRANSFER_BYTES)


class TransferBudgetExceeded(AssertionError):
    """Raised by transfer_budget() when a scope fetches more
    device->host bytes than it declared."""


class _TransferBudget:
    def __init__(self, budget_bytes: int):
        self.budget = budget_bytes
        self._before: Optional[Dict[str, int]] = None

    def __enter__(self) -> "_TransferBudget":
        self._before = device_transfer_totals()
        return self

    @property
    def spent(self) -> int:
        before = self._before or {}
        return (sum(device_transfer_totals().values())
                - sum(before.values()))

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            return
        before = self._before or {}
        after = device_transfer_totals()
        new = {k: n - before.get(k, 0) for k, n in after.items()
               if n - before.get(k, 0) > 0}
        spent = sum(new.values())
        if spent > self.budget:
            detail = ", ".join(
                f"{site}={n}B" for site, n in sorted(new.items()))
            raise TransferBudgetExceeded(
                f"device->host transfer budget exceeded: {spent} bytes "
                f"> declared budget {self.budget} ({detail})")


def transfer_budget(budget_bytes: int) -> _TransferBudget:
    """Context manager: fail if the enclosed scope fetches more than
    ``budget_bytes`` device->host bytes through the counted sites. The
    opt-in pytest fixture of the same name (tests/conftest.py) wraps a
    test declaring ``@pytest.mark.transfer_budget(n)``."""
    return _TransferBudget(budget_bytes)


def _jitted_step(impl: str, skip_local: bool, fast: bool, form: str,
                 sweep_stride: Optional[int] = None,
                 ring_slots: int = 0,
                 ml_mode: str = "off", ml_kind: str = "mlp",
                 tel_mode: str = "off", tnt_mode: str = "off",
                 fib_impl: str = "dense", sess_impl: str = "gather",
                 sess_hash: str = "fwd", overlay: str = "off"):
    from vpp_tpu.pipeline.graph import SWEEP_STRIDE_DEFAULT

    if sweep_stride is None:
        sweep_stride = SWEEP_STRIDE_DEFAULT
    if overlay != "off" and form != "plain":
        # The packed [5, B]/ring/chain boundaries carry no lane for the
        # overlay's inner-vector sidecar (or the outer result pair);
        # the overlay rides the plain step form only — the documented
        # CPU-harness caveat (docs/OVERLAY.md). Widening the packed
        # layout is future work, not a silent misdecode.
        raise ValueError(
            f"overlay={overlay!r} supports only the plain step form "
            f"(the packed/ring boundaries carry no inner-header "
            f"sidecar); got form {form!r}")
    key = (impl, skip_local, fast, form, sweep_stride, ring_slots,
           ml_mode, ml_kind, tel_mode, tnt_mode, fib_impl, sess_impl,
           sess_hash, overlay)
    step = _JIT_STEPS.get(key)
    if step is None:
        fn = make_pipeline_step(impl, skip_local, fast, sweep_stride,
                                ml_mode, ml_kind, tel_mode, tnt_mode,
                                fib_impl, sess_impl, sess_hash, overlay)
        label = _step_label(impl, skip_local, fast, form, sweep_stride,
                            ring_slots, ml_mode, ml_kind, tel_mode,
                            tnt_mode, fib_impl, sess_impl, sess_hash,
                            overlay)
        if form == "plain":
            step = jax.jit(_counting(label, fn))
        elif form == "packed":
            step = jax.jit(
                _counting(label, _packed_call(fn, with_aux=True,
                                              tel=tel_mode)),
                donate_argnums=(1,))
        elif form == "ring":
            # the device-ring window program: the WHOLE carry is
            # donated — tables (argnum 0; at the 10M-flow config a
            # non-donated carry would copy ~hundreds of MB of session
            # columns per window, where donation aliases the unchanged
            # config arrays and updates the session columns in place),
            # the window-to-window cursor scalar (argnum 1), and the
            # rx window (argnum 2, a fresh upload each dispatch,
            # donated so the tx ring reuses its HBM). The caller MUST
            # own the tables buffers it passes — PersistentPump copies
            # the dataplane's live tables once at start precisely so
            # the first window's donation can't invalidate buffers the
            # collector/CLI still read.
            step = jax.jit(
                _counting(label, _ring_call(fn, ring_slots,
                                            tel=tel_mode)),
                donate_argnums=(0, 1, 2))
        else:
            step = jax.jit(
                _counting(label, _chained_call(fn, with_aux=True,
                                               tel=tel_mode)),
                donate_argnums=(1,))
        _JIT_STEPS[key] = step
    return step


def packed_input_zeros(n: int):
    """An all-invalid packed input batch (flags=0) — the pre-compile /
    warm-up argument for ``process_packed``."""
    return np.zeros((PACKED_IN_ROWS, n), np.int32)


def pack_packet_columns(fu, cols, n: int, off: int = 0) -> None:
    """Pack ring columns (native/ring.py PV_COLUMNS views) into a packed
    input batch. ``fu`` is the uint32 view of a [5, B] int32 batch;
    writes packets [off, off+n)."""
    def u(name):
        return cols[name][:n].view(np.uint32)

    fu[0, off:off + n] = u("src_ip")
    fu[1, off:off + n] = u("dst_ip")
    fu[2, off:off + n] = (u("sport") << 16) | (u("dport") & 0xFFFF)
    fu[3, off:off + n] = (
        ((u("pkt_len") & 0xFFFF) << 16) | ((u("proto") & 0xFF) << 8)
        | (u("ttl") & 0xFF)
    )
    fu[4, off:off + n] = (u("rx_if") << 8) | (u("flags") & 0xFF)


def local_table_pkts(flat, if_local_table: np.ndarray) -> int:
    """Valid packets of a host packed batch ([5, B] or a [K, 5, B]
    stack) whose rx interface points at a local ACL table (0 for a
    device array: counting it would fetch it)."""
    if not isinstance(flat, np.ndarray):
        return 0
    row = flat[..., 4, :].view(np.uint32)
    hit = np.take(if_local_table, row >> 8, mode="clip") >= 0
    return int(np.count_nonzero(hit & ((row & 1) == 1)))


def unpack_packet_input(flat) -> dict:
    """Host-side inverse of ``pack_packet_columns``: decode a [5, B]
    packed input batch back into named PacketVector column arrays (the
    pump's tracing path runs the unpacked step from these)."""
    fu = flat.view(np.uint32)
    return {
        "src_ip": fu[0],
        "dst_ip": fu[1],
        "proto": ((fu[3] >> 8) & 0xFF).astype(np.int32),
        "sport": (fu[2] >> 16).astype(np.int32),
        "dport": (fu[2] & 0xFFFF).astype(np.int32),
        "ttl": (fu[3] & 0xFF).astype(np.int32),
        "pkt_len": (fu[3] >> 16).astype(np.int32),
        "rx_if": (fu[4] >> 8).astype(np.int32),
        "flags": (fu[4] & 0xFF).astype(np.int32),
    }


def unpack_packet_result(out) -> dict:
    """Decode a fetched [5, B] packed result into named host arrays.
    ``out`` must be a writable int32 array (np.array of the device_get).
    tx_if 0xFFFF decodes to -1 (no egress interface)."""
    assert out.shape[0] == PACKED_OUT_ROWS_N, out.shape
    ou = out.view(np.uint32)
    row3 = ou[3]
    tx_if = (row3 & 0xFFFF).astype(np.int32)
    tx_if[tx_if == 0xFFFF] = -1
    return {
        "src_ip": ou[0],
        "dst_ip": ou[1],
        "sport": (ou[2] >> 16).astype(np.int32),
        "dport": (ou[2] & 0xFFFF).astype(np.int32),
        "ttl": ((row3 >> 16) & 0xFF).astype(np.int32),
        "disp": ((row3 >> 24) & 0xF).astype(np.int32),
        "drop_cause": (row3 >> 28).astype(np.int32),
        "tx_if": tx_if,
        "next_hop": ou[4],
    }


class Dataplane:
    def __init__(
        self, config: Optional[DataplaneConfig] = None, materialize: bool = True
    ):
        """``materialize=False`` skips the initial device upload — used by
        ClusterDataplane, which stages through per-node builders but
        publishes node-stacked tables itself (parallel/cluster.py)."""
        self.config = config or DataplaneConfig()
        self.builder = TableBuilder(self.config)
        self.tables: Optional[DataplaneTables] = (
            self.builder.to_device() if materialize else None
        )
        self.epoch = 0
        self._lock = threading.RLock()
        # Guards a whole stage-mutate-then-swap commit sequence. For a
        # standalone dataplane it's the same lock; a ClusterDataplane
        # repoints every node handle at ITS lock so one node's commit
        # can't publish another node's half-applied staging (cluster
        # swap reads all builders). Writers (renderer commit, CNI server,
        # node events, service configurator) hold this across builder
        # mutations + swap().
        self.commit_lock = self._lock
        # Step variants are built lazily through ONE factory
        # (graph.make_pipeline_step), jit-cached PROCESS-WIDE per
        # (classifier impl, skip-local, fast-tier, call form) — see
        # _get_step / _jitted_step. The two-tier (fast) variants put
        # BOTH kernels —
        # the classify-free fast path and the full chain — behind a
        # lax.cond in one jitted program, so an epoch swap caches both
        # compilations exactly like the plain step (jit keys on shapes,
        # which are epoch-invariant; only the selection gates flip).
        # Packed/chained forms donate the packed input (in and out are
        # both [5, B] int32, so XLA aliases the buffers) and ALL carry
        # the aux summary — the plain chain reports fastpath=0 but
        # still measures rx/sess_hits, so the hit-percentage regime
        # signal exists even with the fast path disengaged (exactly
        # when an operator is deciding whether to enable it).
        self._encap = None  # jitted vxlan_encap, built on first use
        # Classifier selection (re-evaluated at every swap, like the
        # fast-path gate): the ``classifier`` knob picks
        # dense | mxu | bv | auto; auto ladders BV above bv_min_rules
        # (the memory cap is honored at builder allocation —
        # ops/acl_bv.bv_enabled_for), MXU above mxu_threshold, dense
        # below. ``_use_mxu`` is kept as the legacy boolean view of
        # the selection (impl == "mxu").
        self.classifier = getattr(self.config, "classifier", "auto")
        self.mxu_threshold = 512
        self.bv_min_rules = int(
            getattr(self.config, "classifier_bv_min_rules", 1024)
        )
        self._classifier_impl = "dense"
        self._use_mxu = False
        # Policy-free local-classify skip: when NO interface points at
        # a local ACL table at swap time, the compiled step elides the
        # local stage entirely (ops/acl.acl_local_none) — gathering
        # full [P, R] rule rows against an all-(-1) if_local_table was
        # pure waste on nodes without isolated pods.
        self._skip_local = True
        # Established-flow fast path (two-tier dispatch). The enable +
        # min-rules threshold come from DataplaneConfig (YAML:
        # dataplane.fastpath / dataplane.fastpath_min_rules);
        # ``_use_fastpath`` is re-evaluated at every swap() against the
        # staged global rule count, like the classifier selection.
        self.fastpath_enabled = bool(getattr(self.config, "fastpath", True))
        self.fastpath_min_rules = int(
            getattr(self.config, "fastpath_min_rules", 0)
        )
        self._use_fastpath = False
        # Per-packet ML scoring stage (ISSUE 10; ops/mlscore.py): the
        # configured mode (off | score | enforce) engages only once a
        # model is actually staged (builder.set_ml_model) — re-gated
        # at every swap like the classifier/fastpath selections, so a
        # score/enforce config with no model compiles the stage OUT
        # and scoring starts at the first model-publishing swap.
        self.ml_stage = getattr(self.config, "ml_stage", "off")
        self._ml_mode = "off"
        self._ml_kind = "mlp"
        # Device-resident telemetry plane (ops/telemetry.py; ISSUE 11):
        # a pure config gate — unlike the classifier/ml selections it
        # never re-gates at swap (there is no staged state to consult;
        # the planes' shapes are config-static like sess_ways).
        self._tel_mode = getattr(self.config, "telemetry", "off")
        # Multi-tenant gateway mode (vpp_tpu/tenancy/; ISSUE 14): a
        # pure config gate like telemetry — the tenant planes' shapes
        # are config-static, and an unconfigured tenancy-on dataplane
        # behaves exactly like off (single default tenant, unsliced,
        # unlimited), so there is no staged state to re-gate on.
        self._tnt_mode = getattr(self.config, "tenancy", "off")
        # FIB lookup implementation (ISSUE 15; ops/fib.py dense,
        # ops/lpm.py per-length binary search): the classifier-ladder
        # twin — ``fib_impl: auto`` engages LPM once the staged route
        # count reaches fib_lpm_min_routes (and the staged table fits
        # its planes — builder.lpm_ok()), re-gated at every swap.
        self.fib_impl_knob = getattr(self.config, "fib_impl", "auto")
        self.fib_lpm_min_routes = int(
            getattr(self.config, "fib_lpm_min_routes", 256))
        self._fib_impl = "dense"
        # Session-probe implementation (ISSUE 16; ops/session.py
        # gather rung vs the fused pallas probe): eligibility is pure
        # backend + VMEM-budget — no staged state — but the selection
        # is re-derived with the rest so `show kernels` reads one
        # coherent snapshot.
        self.session_impl_knob = getattr(self.config, "session_impl",
                                         "auto")
        self._session_impl = "gather"
        # Session bucket hash family (tables.py sess_hash; ISSUE 18):
        # a pure config gate like telemetry — "sym" buckets flows
        # direction-invariantly so the fleet steering tier can map
        # packets to bucket ranges from outside the dataplane.
        self._sess_hash = getattr(self.config, "sess_hash", "fwd")
        # Device-resident VXLAN overlay stage pair (ISSUE 19): a pure
        # config gate like telemetry — the svc/overlay planes are
        # config-static shapes, and an overlay-on dataplane with no
        # VTEP/VNI staged only fail-closes overlay-ADDRESSED frames
        # (UDP:4789), so there is no staged state to re-gate on. ONE
        # extra step-form dimension, plain form only (_jitted_step).
        self._overlay = getattr(self.config, "overlay", "off")
        # optional Prometheus histogram (stats/collector.py): observes
        # the fib-group upload cost of every swap that actually
        # re-shipped FIB state (vpp_tpu_fib_churn_commit_seconds)
        self.fib_churn_hist = None
        self._refresh_selection()
        # cumulative host seconds inside process_packed[_chain]: turning
        # the host arguments into device arrays (t_dp_upload) and the
        # jitted step call until it returns (t_dp_call; dispatch is
        # asynchronous, so this is the host's side of the call, not the
        # device step). The pump folds the deltas of each dispatch into
        # its own stats (spans dp.upload / dp.step_call).
        self.host_timers = {"t_dp_upload": 0.0, "t_dp_call": 0.0}
        # packets process_packed[_chain] handed to the step whose rx
        # interface points at a local ACL table in the served epoch,
        # counted on the host from the packed rx_if row; folded into
        # the pump's stats the same way
        self.host_counters = {"local_table_pkts": 0}
        # (leaves, bytes) of the tables the last packed or chained step
        # variant returned per call — the leaves it writes
        # (_packed_call), not the 174 it reads; sizes, not counts
        self.step_out = (0, 0)
        self._out_step = None  # the step variant step_out describes
        # Session time base: wall-clock ticks (TICKS_PER_SEC), not frame
        # counts — aging semantics must not depend on offered load
        # (VERDICT r1 Weak #5; the reference ages on timers).
        self._t0 = _time.monotonic()
        self._now = 0
        # Amortized session aging (ops/session.py session_sweep): the
        # fused step sweeps this many buckets per table per step
        # (trace-time static — part of the jit-cache key).
        self._sweep_stride = int(
            getattr(self.config, "sess_sweep_stride", 256))
        # steps dispatched since the last expire_sessions() — the
        # lazy-maintenance signal (in-step sweep coverage)
        self._steps_since_expire = 0

        # interface registry
        self.pod_if: Dict[PodID, int] = {}
        self.if_pod: Dict[int, PodID] = {}
        self._free_ifs = list(range(self.config.max_ifaces - 1, 0, -1))
        # if 0 stays reserved as "unset"; uplink/host claimed explicitly
        self.uplink_if: Optional[int] = None
        self.host_if: Optional[int] = None

        # ACL table slot registry (renderer table id -> slot)
        self.table_slots: Dict[str, int] = {}
        self._free_slots = list(range(self.config.max_tables - 1, -1, -1))
        # optional PacketTracer (vpp_tpu.trace); when set, every
        # processed frame is offered to it (captures only while armed)
        self.tracer = None
        # optional TxnJournal (pipeline/txn.py): with enable_journal(),
        # every epoch swap records the builder ops staged since the
        # previous swap — the api-trace analog for the LIVE agent
        # (VERDICT r3 Missing #3)
        self.journal = None
        # observers notified when a pod interface slot is freed (the
        # statscollector zeroes its accumulators so a later pod reusing
        # the slot doesn't inherit counters)
        self.on_if_freed = []
        # optional Prometheus histograms (stats/collector.py
        # register_control_plane_metrics): txn_commit_hist observes
        # every swap's publish duration; propagation_hist observes the
        # config-propagation SLO (config event wall-clock → epoch-swap
        # complete) whenever a swap publishes under an active span trace
        self.txn_commit_hist = None
        self.propagation_hist = None

    # --- interfaces ---
    def add_uplink(self) -> int:
        with self._lock:
            if self.uplink_if is None:
                self.uplink_if = self._free_ifs.pop()
                self.builder.set_interface(
                    self.uplink_if, InterfaceType.UPLINK, apply_global=True
                )
            return self.uplink_if

    def add_host_interface(self) -> int:
        with self._lock:
            if self.host_if is None:
                self.host_if = self._free_ifs.pop()
                self.builder.set_interface(self.host_if, InterfaceType.HOST)
            return self.host_if

    def add_pod_interface(self, pod: PodID) -> int:
        with self._lock:
            if pod in self.pod_if:
                return self.pod_if[pod]
            if not self._free_ifs:
                raise RuntimeError("interface table full")
            idx = self._free_ifs.pop()
            self.pod_if[pod] = idx
            self.if_pod[idx] = pod
            self.builder.set_interface(idx, InterfaceType.POD)
            return idx

    def del_pod_interface(self, pod: PodID) -> bool:
        with self._lock:
            idx = self.pod_if.pop(pod, None)
            if idx is None:
                return False
            del self.if_pod[idx]
            self.builder.set_interface(idx, InterfaceType.NONE, local_table=-1)
            self._free_ifs.append(idx)
            observers = list(self.on_if_freed)
        for cb in observers:
            cb(idx)
        return True

    # --- ACL table slots (used by the TPU renderer) ---
    def alloc_table_slot(self, table_id: str) -> int:
        with self._lock:
            if table_id in self.table_slots:
                return self.table_slots[table_id]
            if not self._free_slots:
                raise RuntimeError("ACL table slots exhausted")
            slot = self._free_slots.pop()
            self.table_slots[table_id] = slot
            return slot

    def free_table_slot(self, table_id: str) -> None:
        with self._lock:
            slot = self.table_slots.pop(table_id, None)
            if slot is not None:
                self.builder.clear_local_table(slot)
                self._free_slots.append(slot)

    def assign_pod_table(self, pod: PodID, table_id: Optional[str]) -> None:
        """Point the pod's interface at a local ACL table (or none)."""
        with self._lock:
            idx = self.pod_if.get(pod)
            if idx is None:
                return
            slot = self.table_slots.get(table_id, -1) if table_id else -1
            self.builder.set_if_local_table(idx, slot)

    # --- epoch management ---
    def enable_journal(self, path: Optional[str]) -> None:
        """Turn on the config transaction trace: builder mutations are
        recorded and journaled (JSONL at ``path``; None = in-memory
        count only) per epoch swap. Replaying the journal onto a fresh
        builder reproduces the exact table history this dataplane
        enforced (reference: contiv-vswitch.conf `api-trace { on }`)."""
        from vpp_tpu.pipeline.txn import TxnJournal

        with self._lock:
            self.journal = TxnJournal(path)
            self.builder.start_recording()

    def swap(self) -> int:
        """Publish the staged configuration as a new table epoch. Live
        session state is carried over from the running epoch.

        On a cluster-node staging handle the swap delegates to the owning
        ClusterDataplane (set via ``_swap_delegate``), so renderers and
        the CNI server drive cluster nodes unchanged."""
        delegate = getattr(self, "_swap_delegate", None)
        span = spans.RECORDER.begin("swap", "epoch-swap")
        try:
            if delegate is not None:
                # cluster-node staging handle: the owning
                # ClusterDataplane publishes the multi-chip epoch; the
                # span + histograms still record THIS commit's cost and
                # propagation as the caller experienced it
                epoch = delegate()
                span.attrs["epoch"] = epoch
                span.name = f"epoch {epoch} (cluster)"
            else:
                with self._lock:
                    if self.tables is None:
                        raise RuntimeError(
                            "this Dataplane has no live tables and no "
                            "swap delegate (materialize=False without a "
                            "managing ClusterDataplane)"
                        )
                    self.tables = self.builder.to_device(
                        sessions=self.tables)
                    # re-gate the classifier selection, the policy-free
                    # local skip and the two-tier dispatch on the new
                    # epoch's staged state (the variants stay
                    # jit-cached — shapes are epoch-invariant, only the
                    # gates flip)
                    self._refresh_selection()
                    if (self.fib_churn_hist is not None
                            and self.builder.fib_last_shipped):
                        # route-churn commit cost (ISSUE 15): only
                        # swaps that actually re-shipped FIB state
                        self.fib_churn_hist.observe(
                            float(self.builder.fib_upload.get(
                                "ms", 0.0)) / 1e3)
                    self.epoch += 1
                    span.attrs["epoch"] = self.epoch
                    span.name = f"epoch {self.epoch}"
                    if self.journal is not None:
                        txn = self.builder.drain_recording()
                        if txn is not None:
                            self.journal.record(txn, self.epoch)
                    epoch = self.epoch
        finally:
            # the enclosing trace's root (KSR event, CNI add, ...) holds
            # the config event timestamp; capture it before this span
            # pops in case the swap IS the root (then there is no
            # propagation to measure — a bare swap isn't an NB event)
            root = spans.current_root()
            spans.RECORDER.end(span)
        if self.txn_commit_hist is not None and span.done:
            self.txn_commit_hist.observe(span.duration)
        if (self.propagation_hist is not None and root is not None
                and root is not span):
            self.propagation_hist.observe(
                _time.time() - root.t_wall, source=root.stage
            )
        return epoch

    def adopt_sessions(self, sessions) -> int:
        """Publish restored session state into the live tables (the
        crash-consistent snapshot restore path, pipeline/snapshot.py).

        ``sessions`` is a ``{field: host array}`` mapping of
        SESSION_FIELDS; the upload routes through
        ``TableBuilder.to_device(sessions=...)`` so it follows the same
        carry-over contract as an epoch swap (shape validation, config
        groups served from the device cache — nothing but the session
        columns ships). The epoch bumps so a persistent-mode pump
        restarts its resident ring against the restored state. Call at
        agent start, right after the base-config swap and before
        traffic — the builder must hold no unpublished staging (this
        path would publish it early)."""
        with self._lock:
            if self.tables is None:
                raise RuntimeError(
                    "this Dataplane is a staging handle managed by a "
                    "ClusterDataplane; session restore is not supported "
                    "on cluster node handles")
            self.tables = self.builder.to_device(sessions=sessions)
            self.epoch += 1
            return self.epoch

    # --- VXLAN edge (cluster-boundary peers; TPU↔TPU rides ICI instead) ---
    def set_vtep(self, vtep_ip: int) -> None:
        """Set this node's VXLAN tunnel endpoint address (the reference's
        per-node vxlanCIDR IP, plugins/contiv/ipam computeVxlanIPAddress).
        Also stages the device-resident copy (``ovl_vtep_ip``) the fused
        overlay stage pair reads (ISSUE 19) — published at the next
        swap(), like every staged mutation."""
        with self._lock:
            self._vtep = jnp.uint32(vtep_ip)
            self.builder.set_vtep_ip(vtep_ip)

    def encap_remote(self, result: StepResult) -> PacketVector:
        """Outer-header vector for REMOTE-disposed packets of a step —
        the vxlan-encap graph node for traffic leaving the cluster edge."""
        vtep = getattr(self, "_vtep", None)
        if vtep is None:
            raise RuntimeError("set_vtep() before encap_remote()")
        if self._encap is None:
            self._encap = jax.jit(vxlan_encap)
        # Encap only REMOTE traffic with a VTEP next_hop (fabric peers
        # and edge peers with an explicit tunnel endpoint): routes with
        # next_hop 0 — e.g. the SNAT'd default route — leave as plain IP
        # out the uplink; encapping them would emit VXLAN toward dst 0.
        mask = (result.disp == int(Disposition.REMOTE)) & (result.next_hop != 0)
        return self._encap(result.pkts, mask, vtep, result.next_hop)

    # --- time base (VPP session/NAT timers analog) ---
    TICKS_PER_SEC = 10

    def clock_ticks(self) -> int:
        """Monotonic wall-clock ticks since this dataplane started."""
        return int((_time.monotonic() - self._t0) * self.TICKS_PER_SEC)

    def advance_clock(self, seconds: float) -> None:
        """Shift the time base forward (tests simulate idle periods
        without sleeping)."""
        self._t0 -= seconds

    # --- session aging (host reclamation; lookups already ignore expired
    # entries and inserts evict them — the in-step sweep is the
    # steady-state reclaimer, this is the on-demand bulk pass) ---
    def expire_sessions(self, max_age: Optional[int] = None,
                        lazy: bool = False) -> int:
        """Invalidate reflective + NAT sessions idle for more than
        ``max_age`` ticks (default: the configured sess_max_age).
        Returns the number of sessions expired.

        ``lazy=True`` is the periodic-maintenance form: when the
        in-step amortized sweep (ops/session.py session_sweep) has
        covered the whole table since the last call — i.e. steps x
        stride >= buckets — the bulk device pass is SKIPPED, because
        steady-state aging already happened inside the fused program.
        Idle nodes (no steps) and tiny tables still reclaim here, so
        the occupancy gauges never go stale."""
        from vpp_tpu.ops.session import session_expire

        if max_age is None:
            max_age = self.config.sess_max_age
        with self._lock:
            if self.tables is None:
                return 0
            # the lazy skip is sound only for the CONFIGURED timeout:
            # the in-step sweep enforces tables.sess_max_age, so a
            # caller-supplied shorter max_age must still run the bulk
            # pass (it reclaims entries the sweep deliberately keeps)
            if lazy and max_age == self.config.sess_max_age:
                steps = self._steps_since_expire
                self._steps_since_expire = 0
                from vpp_tpu.ops.session import sweep_covered

                if sweep_covered(steps, self._sweep_stride, self.tables):
                    return 0
            self._now = max(self._now, self.clock_ticks())
            before = self.tables
            after = session_expire(before, self._now, max_age)
            # transfer-ok: device-reduced scalar (expired-slot count)
            expired = int(
                jnp.sum(before.sess_valid - after.sess_valid)
                + jnp.sum(before.natsess_valid - after.natsess_valid)
            )
            # publish ONLY when something expired: a no-op replacement
            # would still invalidate the `tables is self.tables` guard
            # of a concurrently dispatched step and silently discard
            # that batch's session inserts (the maintenance loop runs
            # every few seconds against live traffic)
            if expired:
                self.tables = after
        return expired

    # --- classifier / step selection ---
    @property
    def classifier_impl(self) -> str:
        """The global-classify implementation the LIVE epoch runs
        ("dense" | "mxu" | "bv") — surfaced by `show acl` and the
        ``vpp_tpu_acl_classifier`` info gauge."""
        return self._classifier_impl

    @property
    def fib_impl(self) -> str:
        """The ip4-lookup implementation the LIVE epoch runs ("dense" |
        "lpm" | "pallas") — surfaced by `show fib` and the
        ``vpp_tpu_fib_impl`` info gauge (ISSUE 15/16)."""
        return self._fib_impl

    @property
    def session_impl(self) -> str:
        """The session-probe implementation the LIVE epoch runs
        ("gather" | "pallas") — surfaced by `show kernels` and the
        ``vpp_tpu_kernel_impl`` info gauge (ISSUE 16)."""
        return self._session_impl

    def kernel_snapshot(self) -> dict:
        """Per-op kernel-rung resolution behind `show kernels` and the
        ``vpp_tpu_kernel_impl`` info-gauge family: which rung each hot
        op's ladder selected, the operator's knob, and WHY (the
        eligibility bit that decided). One coherent read under the
        lock — the StepStats ↔ Prometheus parity discipline."""
        from vpp_tpu.ops._pallas import pallas_available, use_pallas
        from vpp_tpu.ops.lpm import lpm_pallas_fits
        from vpp_tpu.ops.session import session_pallas_fits

        with self._lock:
            b = self.builder
            p_ok = use_pallas()

            def why(impl, knob, eligible, reason_ineligible):
                if impl == "pallas":
                    return "tpu backend + structure eligible"
                if knob == impl:
                    return "explicit knob"
                if not p_ok:
                    return "no tpu backend (pallas rung needs one)"
                if not eligible:
                    return reason_ineligible
                return "ladder heuristic"

            return {
                "backend": jax.default_backend(),
                "pallas_available": pallas_available(),
                "classifier": {
                    "impl": self._classifier_impl,
                    "knob": self.classifier,
                    "why": why(self._classifier_impl, self.classifier,
                               b.bv_ok(),
                               "bv structure ineligible"),
                },
                "fib": {
                    "impl": self._fib_impl,
                    "knob": self.fib_impl_knob,
                    "why": why(self._fib_impl, self.fib_impl_knob,
                               b.lpm_ok() and lpm_pallas_fits(
                                   self.config),
                               "lpm planes ineligible or past the "
                               "VMEM budget"),
                },
                "session": {
                    "impl": self._session_impl,
                    "knob": self.session_impl_knob,
                    "why": why(self._session_impl,
                               self.session_impl_knob,
                               session_pallas_fits(self.config),
                               "table exceeds VMEM budget"),
                },
                # table leaves/bytes the packed step returns per call
                # (0 before the first packed or chained dispatch)
                "step_out": {"leaves": self.step_out[0],
                             "bytes": self.step_out[1]},
            }

    def fib_snapshot(self) -> Optional[dict]:
        """Host scalars behind `show fib` / the ``vpp_tpu_fib_*``
        families: live route count, per-length histogram, ECMP group
        registry + the per-member forwarded-packet plane ([G, W] ints
        cross the transport, never route columns), plane bytes and the
        last churn upload. In persistent pump mode the ECMP plane
        rides the ring's private carry, so its view refreshes at
        sync_sessions/stop — the `show sessions` staleness contract."""
        from vpp_tpu.ops.lpm import lpm_plane_bytes

        with self._lock:
            t = self.tables
            b = self.builder
            # histogram straight off the per-slot arrays: correct for
            # dense-only configs too (the LPM staging counters only
            # move while planes are allocated)
            live = b.fib_plen[b.fib_plen >= 0]
            cnts = np.bincount(live, minlength=33) if len(live) else []
            by_len = {int(L): int(n) for L, n in enumerate(cnts) if n}
            # per-member rows aggregated ONCE here — `show fib` and
            # the vpp_tpu_fib_ecmp_packets family both consume these,
            # so the two views can never diverge
            groups = {}
            for g, e in b.nh_groups.items():
                groups[g] = [
                    {"nh": int(m[0]), "tx_if": int(m[1]),
                     "node": int(m[2]),
                     "ways": [w for w, a in enumerate(e["assign"])
                              if a == m],
                     "pkts": 0}
                    for m in e["members"]
                ]
            snap = {
                "impl": self._fib_impl,
                "knob": self.fib_impl_knob,
                "routes": int(len(live)),
                "by_length": by_len,
                "lpm_ok": b.lpm_ok(),
                "lpm_build_ms": float(b.lpm_build_ms),
                "ecmp_groups": groups,
                "plane_bytes": lpm_plane_bytes(self.config),
                "upload": dict(b.fib_upload),
            }
        if t is not None:
            ecmp_c = np.asarray(jax.device_get(t.fib_ecmp_c), np.int64)
            count_device_transfer("fib.snapshot", ecmp_c)
            snap["ecmp_c"] = ecmp_c
            for g, members in groups.items():
                for m in members:
                    if m["ways"]:
                        m["pkts"] = int(ecmp_c[g, m["ways"]].sum())
        return snap

    def _select_classifier(self) -> str:
        """Resolve the ``classifier`` knob against the staged builder
        state — eligibility bits (range rules for MXU, non-prefix
        masks or a busted memory cap for BV) feed the ONE shared
        ladder (partition.select_impl), which the cluster and
        multi-host planes apply to their own agreed bits so the mesh
        can never silently select a different rung."""
        from vpp_tpu.ops._pallas import use_pallas
        from vpp_tpu.parallel.partition import select_impl

        b = self.builder
        return select_impl(self.classifier, b.bv_ok(),
                           b.mxu_enabled and b.glb_mxu.ok,
                           b.glb_nrules, self.bv_min_rules,
                           self.mxu_threshold, pallas_ok=use_pallas(),
                           local_nrules=int(b.acl_nrules.max()))

    def _refresh_selection(self) -> None:
        """Re-gate every per-epoch compile-time choice against the
        staged builder: classifier impl, the policy-free local-classify
        skip, and the fast-path engagement. Called from __init__ and
        under the lock at every swap()."""
        b = self.builder
        self._classifier_impl = self._select_classifier()
        self._use_mxu = self._classifier_impl == "mxu"
        self._skip_local = bool((b.if_local_table < 0).all())
        # the published epoch's interface -> local table map, for the
        # local_table_pkts counter (None: no interface has a table)
        self._if_local_live = (None if self._skip_local
                               else b.if_local_table.copy())
        self._use_fastpath = (
            self.fastpath_enabled
            and b.glb_nrules >= self.fastpath_min_rules
        )
        # ML stage engages only with a model staged (kind != NONE);
        # the staged model's kind picks the compiled kernel variant
        ml_kind = int(getattr(b, "ml_kind", 0))
        self._ml_mode = self.ml_stage if ml_kind else "off"
        self._ml_kind = "forest" if ml_kind == 2 else "mlp"
        # FIB ladder (ISSUE 15): lpm when eligible and big enough —
        # the ONE shared rung mapping (partition.select_fib_impl), so
        # a mesh plane adopting the ladder can never diverge
        from vpp_tpu.ops._pallas import use_pallas
        from vpp_tpu.ops.lpm import lpm_pallas_fits
        from vpp_tpu.ops.session import session_pallas_fits
        from vpp_tpu.parallel.partition import (
            select_fib_impl,
            select_session_impl,
        )

        p_ok = use_pallas()
        self._fib_impl = select_fib_impl(
            self.fib_impl_knob, b.lpm_ok(), b.fib_route_count(),
            self.fib_lpm_min_routes,
            pallas_ok=p_ok and lpm_pallas_fits(self.config))
        self._session_impl = select_session_impl(
            self.session_impl_knob,
            p_ok and session_pallas_fits(self.config))

    def _get_step(self, fast: bool, form: str = "plain"):
        """The jit-cached step variant of the current selection.
        ``form``: "plain" (PacketVector in/out), "packed" ([5, B]
        boundary + aux) or "chain" (K packed frames under lax.scan).
        Call under ``_lock`` (reads the selection gates).

        The local-skip gate is an OPTIMIZATION, never a requirement:
        the non-skip variant is correct for every epoch (interfaces
        with if_local_table == -1 are permitted by the local stage
        anyway), so when that variant is already built we keep using
        it rather than paying a second full-chain compile for the
        skip variant — a process oscillating between policy-free and
        policied epochs compiles ONE program, whichever came first."""
        skip = self._skip_local
        stride = self._sweep_stride
        gates = (self._ml_mode, self._ml_kind, self._tel_mode,
                 self._tnt_mode, self._fib_impl, self._session_impl,
                 self._sess_hash, self._overlay)
        if (skip
                and (self._classifier_impl, skip, fast, form, stride,
                     0) + gates not in _JIT_STEPS
                and (self._classifier_impl, False, fast, form, stride,
                     0) + gates in _JIT_STEPS):
            skip = False
        return _jitted_step(self._classifier_impl, skip, fast, form,
                            stride, ml_mode=self._ml_mode,
                            ml_kind=self._ml_kind,
                            tel_mode=self._tel_mode,
                            tnt_mode=self._tnt_mode,
                            fib_impl=self._fib_impl,
                            sess_impl=self._session_impl,
                            sess_hash=self._sess_hash,
                            overlay=self._overlay)

    # --- traffic ---
    def _pick_step(self):
        """The unpacked step for the current regime: the two-tier auto
        dispatcher when the fast path is engaged, else the plain chain
        (classifier impl and local-skip per the epoch's selection
        either way). Call under ``_lock``."""
        return self._get_step(self._use_fastpath, "plain")

    def process(self, pkts: PacketVector, now: Optional[int] = None,
                ovl_inner: Optional[PacketVector] = None,
                ovl_vni=None) -> StepResult:
        """Run one packet vector through the fused step. With the
        overlay on (``config.overlay: vxlan``), ``ovl_inner``/
        ``ovl_vni`` are the host-IO-parsed inner-header sidecar for
        VXLAN-framed ingress ([P] inner PacketVector + [P] int32 VNI,
        -1 = no VXLAN framing on that lane); None synthesizes the
        all-unframed sidecar, under which any overlay-ADDRESSED frame
        fails closed (DROP_OVERLAY) — exactly what an unparseable
        VXLAN frame must do."""
        with self._lock:
            if self.tables is None:
                raise RuntimeError(
                    "this Dataplane is a staging handle managed by a "
                    "ClusterDataplane; process frames via cluster.step()"
                )
            tables = self.tables
            step = self._pick_step()
            self._steps_since_expire += 1
            if now is None:
                # wall-clock ticks, monotone non-decreasing (max keeps
                # explicitly-supplied test timestamps from going backward)
                self._now = max(self._now, self.clock_ticks())
                now = self._now
        if self._overlay != "off":
            if ovl_vni is None:
                ovl_vni = jnp.full(pkts.valid.shape, -1, jnp.int32)
            if ovl_inner is None:
                ovl_inner = pkts
            result = step(tables, pkts, jnp.int32(now), ovl_inner,
                          jnp.asarray(ovl_vni, jnp.int32))
        else:
            result = step(tables, pkts, jnp.int32(now))
        # Session-table mutations flow back into the live epoch (config
        # arrays are identical between result.tables and the staged ones
        # unless a swap happens, which re-grafts the session arrays).
        with self._lock:
            if tables is self.tables:
                self.tables = result.tables
            tracer = self.tracer
        if tracer is not None:
            tracer.record(result)
        return result

    def probe(self, pkts: PacketVector, now: Optional[int] = None) -> StepResult:
        """Side-effect-free step: classify a synthetic frame against the
        LIVE tables without committing anything back — no reflective
        session is installed, no tracer fires, no counters move. Debug
        probes (`test connectivity`) must never open a return-traffic
        hole or consume session slots."""
        with self._lock:
            if self.tables is None:
                raise RuntimeError(
                    "this Dataplane is a staging handle managed by a "
                    "ClusterDataplane; probe via its node pipelines"
                )
            tables = self.tables
            step = self._get_step(fast=False)
            if now is None:
                now = max(self._now, self.clock_ticks())
        if self._overlay != "off":
            return step(tables, pkts, jnp.int32(now), pkts,
                        jnp.full(pkts.valid.shape, -1, jnp.int32))
        return step(tables, pkts, jnp.int32(now))

    def process_packed(self, flat, now: Optional[int] = None,
                       commit: bool = True, with_aux: bool = False,
                       stamp_us: int = 0,
                       now_us: Optional[int] = None):
        """Single-transfer variant of process() for the pump's hot path:
        ``flat`` is a host [5, B] int32 bit-packed batch (see
        ``_packed_call`` for the row layout; build with
        ``pack_packet_columns`` / ``packed_input_zeros``); returns the
        DEVICE [5, B] int32 packed result without forcing a host sync —
        the caller device_gets it when ready. One upload, one fetch per
        batch, 20 bytes per packet each way.

        ``with_aux=True`` returns ``(out, aux)`` instead, where ``aux``
        is the DEVICE [PACKED_AUX_ROWS] int32 summary
        (``PACKED_AUX_SCHEMA``) from the same program. It is
        measured on BOTH tiers (fastpath is 0 on the full chain), so
        the session-hit regime signal exists even with the fast path
        disengaged.

        Only the table leaves the step writes cross back from the
        device (``_packed_call``: the session columns, sweep cursors
        and ECMP counters). The commit rebuilds the live tables from
        the tables object the step was called with and those leaves;
        every read-only plane stays the buffer that object holds, owned
        by whoever published it (``swap``, ``adopt_sessions``) and
        shared with every reader of ``dp.tables`` — none of it is
        donated. ``step_out`` says how many leaves and bytes come back
        per call.

        ``commit=False`` discards the resulting session-table state (a
        probe-like classify): REQUIRED for any caller other than the
        pump's single dispatch thread — two concurrent committers race
        the ``tables is self.tables`` swap guard and one side's
        reflective-session installs would be silently lost.

        With telemetry on (``config.telemetry`` != off), ``stamp_us``
        is the batch's rx-enqueue microsecond stamp (ops/telemetry.py
        tel_clock_us; 0 = unstamped, not observed) and ``now_us`` the
        dispatch clock (None = read it here) — the device histograms
        ``now_us − stamp_us`` for every valid packet inside the same
        program."""
        with self._lock:
            if self.tables is None:
                raise RuntimeError(
                    "this Dataplane is a staging handle managed by a "
                    "ClusterDataplane; process frames via cluster.step()"
                )
            tables = self.tables
            if_local = self._if_local_live
            step = self._get_step(self._use_fastpath, "packed")
            if commit:
                self._steps_since_expire += 1
            if now is None:
                self._now = max(self._now, self.clock_ticks())
                now = self._now
        tel = self._tel_mode != "off"
        if tel and now_us is None:
            from vpp_tpu.ops.telemetry import tel_clock_us

            now_us = tel_clock_us()
        timers = self.host_timers
        with Timed("dp.upload", timers, "t_dp_upload"):
            args = (jnp.asarray(flat), jnp.int32(now))
            if tel:
                args += (jnp.int32(stamp_us), jnp.int32(now_us))
        with Timed("dp.step_call", timers, "t_dp_call"):
            written, out, aux = step(tables, *args)
        self._note_step_out(step, written)
        if if_local is not None:
            self.host_counters["local_table_pkts"] += local_table_pkts(
                flat, if_local)
        if commit:
            new_tables = tables._replace(**written)
            with self._lock:
                if tables is self.tables:
                    self.tables = new_tables
        return (out, aux) if with_aux else out

    def process_packed_chain(self, flats, now: Optional[int] = None,
                             with_aux: bool = False,
                             stamps_us=None,
                             now_us: Optional[int] = None):
        """K packed batches in ONE device dispatch (``_chained_call``):
        ``flats`` is a host [K, 5, B] int32 stack; returns the DEVICE
        [K, 5, B] packed results. One dispatch + one fetch for K
        frames — the bounded-sync throughput lever when per-step
        dispatch dominates (remote transports, small frames).
        ``with_aux=True`` returns ``(outs, auxs)`` with the stacked
        [K, PACKED_AUX_ROWS] aux summaries (measured on both tiers).
        ``stamps_us`` ([K] int32 µs rx-enqueue stamps) feeds the
        device latency histogram when telemetry is on (None = all
        unstamped)."""
        with self._lock:
            if self.tables is None:
                raise RuntimeError(
                    "this Dataplane is a staging handle managed by a "
                    "ClusterDataplane; process frames via cluster.step()"
                )
            tables = self.tables
            if_local = self._if_local_live
            step = self._get_step(self._use_fastpath, "chain")
            # a K-chain sweeps once per scanned sub-batch
            self._steps_since_expire += max(1, len(flats))
            if now is None:
                self._now = max(self._now, self.clock_ticks())
                now = self._now
        tel = self._tel_mode != "off"
        if tel:
            from vpp_tpu.ops.telemetry import tel_clock_us

            if now_us is None:
                now_us = tel_clock_us()
            if stamps_us is None:
                stamps_us = np.zeros(len(flats), np.int32)
        timers = self.host_timers
        with Timed("dp.upload", timers, "t_dp_upload"):
            args = (jnp.asarray(flats), jnp.int32(now))
            if tel:
                args += (jnp.asarray(stamps_us, jnp.int32),
                         jnp.int32(now_us))
        with Timed("dp.step_call", timers, "t_dp_call"):
            written, outs, auxs = step(tables, *args)
        self._note_step_out(step, written)
        if if_local is not None:
            self.host_counters["local_table_pkts"] += local_table_pkts(
                flats, if_local)
        new_tables = tables._replace(**written)
        with self._lock:
            if tables is self.tables:
                self.tables = new_tables
        return (outs, auxs) if with_aux else outs

    def _note_step_out(self, step, written: dict) -> None:
        """Record the table leaves and bytes ``step`` returns per call
        (``step_out``). A variant's written set is fixed once traced,
        so it is summed once per variant switch."""
        if step is not self._out_step:
            self._out_step = step
            self.step_out = (len(written), sum(
                int(v.nbytes) for v in written.values()))

    # --- device telemetry (ops/telemetry.py; ISSUE 11) ---
    def telemetry_snapshot(self) -> Optional[dict]:
        """Host copy of the collect-facing telemetry planes: latency
        bins, the sketched-packet scalar and the top-K candidate rows.
        A few hundred BYTES cross the transport — the [d, w] sketch
        matrix stays device-resident (the PR 6 `show sessions` rule:
        collect fetches scalars, never tables). None when telemetry is
        off or no tables are live. Persistent-mode callers prefer the
        pump's rider snapshot (DataplanePump.tel_snapshot) — the ring
        threads its tables privately, so dp.tables lags until
        stop/sync."""
        if self._tel_mode == "off":
            return None
        with self._lock:
            t = self.tables
        if t is None:
            return None
        bins, sketched, key, src, dst, ports, cnt = jax.device_get((
            t.tel_lat_hist, t.tel_sketched, t.tel_top_key,
            t.tel_top_src, t.tel_top_dst, t.tel_top_ports,
            t.tel_top_cnt))
        count_device_transfer(
            "telemetry.snapshot",
            (bins, sketched, key, src, dst, ports, cnt))
        return {
            "mode": self._tel_mode,
            "bins": np.asarray(bins, np.int64),
            "sketched": int(sketched),
            "top_key": np.asarray(key, np.uint32),
            "top_src": np.asarray(src, np.uint32),
            "top_dst": np.asarray(dst, np.uint32),
            "top_ports": np.asarray(ports, np.uint32),
            "top_cnt": np.asarray(cnt, np.int64),
        }

    # --- multi-tenant gateway mode (vpp_tpu/tenancy/; ISSUE 14) ---
    def tenant_snapshot(self) -> Optional[dict]:
        """Host copy of the per-tenant planes `show tenants` and the
        ``vpp_tpu_tenant_*`` families read: token-bucket levels,
        rx/goodput/drop/quota-fail counters, and per-tenant live
        session occupancy (one on-device prefix sum —
        tenancy/derive.py tenant_occupancy; [T] ints cross the
        transport, never columns). None when tenancy is off or no
        tables are live. In persistent pump mode the planes ride the
        ring's private carry, so this view refreshes at
        sync_sessions/stop — the `show sessions` staleness contract.
        """
        if self._tnt_mode == "off":
            return None
        with self._lock:
            t = self.tables
            now = max(self._now, self.clock_ticks())
            registry = {tid: dict(e)
                        for tid, e in self.builder.tenants.items()}
        if t is None:
            return None
        from vpp_tpu.tenancy.derive import tenant_occupancy

        occ = tenant_occupancy(t.sess_valid, t.sess_time,
                               jnp.int32(now), t.sess_max_age,
                               t.tnt_sess_base, t.tnt_sess_mask + 1)
        tokens, rx, tx, rl, qf, occ_h, rate, burst, smask = \
            jax.device_get((t.tnt_tokens, t.tnt_rx_c, t.tnt_tx_c,
                            t.tnt_rl_c, t.tnt_qf_c, occ, t.tnt_rate,
                            t.tnt_burst, t.tnt_sess_mask))
        count_device_transfer(
            "tenant.snapshot",
            (tokens, rx, tx, rl, qf, occ_h, rate, burst, smask))
        return {
            "tenants": registry,
            "tokens": np.asarray(tokens, np.int64),
            "rx": np.asarray(rx, np.int64),
            "tx": np.asarray(tx, np.int64),
            "rl_drops": np.asarray(rl, np.int64),
            "quota_fails": np.asarray(qf, np.int64),
            "occupancy": np.asarray(occ_h, np.int64),
            "rate": np.asarray(rate, np.int64),
            "burst": np.asarray(burst, np.int64),
            "sess_quota_slots": (np.asarray(smask, np.int64) + 1)
            * int(getattr(self.config, "sess_ways", 4)),
        }
