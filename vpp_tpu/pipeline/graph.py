"""The fused packet pipeline: one jitted step over a packet vector.

Reference analog: the VPP graph-node chain installed by the agent
(SURVEY.md §3.5): ip4-input → acl-plugin-fa → nat44 → ip4-lookup →
[vxlan/remote] → interface-tx. VPP dispatches frames node-to-node through
a scheduler; under XLA the whole chain is traced once and fused, with
tables passed in functionally so a renderer commit is an epoch swap.

Counters follow VPP's per-node/per-interface model and feed the
statscollector (Prometheus) equivalent.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from vpp_tpu.ops.acl import acl_classify_global, acl_classify_local
from vpp_tpu.ops.fib import fib_lookup_dense
from vpp_tpu.ops.ip4 import ip4_input
from vpp_tpu.ops.nat44 import (
    nat44_dnat,
    nat44_dnat_match,
    nat44_record,
    nat44_reverse,
    nat44_snat,
    nat44_touch,
)
from vpp_tpu.ops.mlscore import ml_policy, ml_score
from vpp_tpu.ops.session import (
    session_batch_summary,
    session_hit_age,
    session_insert,
    session_lookup_reverse_idx,
    session_sweep,
    session_touch,
)
from vpp_tpu.pipeline.tables import DataplaneTables
from vpp_tpu.pipeline.vector import Disposition, PacketVector


class StepStats(NamedTuple):
    """Per-step counters (VPP `show errors` / interface counters analog)."""

    rx: jnp.ndarray            # int32 scalar: valid packets processed
    tx: jnp.ndarray            # int32 scalar: packets forwarded
    drop_ip4: jnp.ndarray      # int32 scalar: ip4-input drops (TTL/len)
    drop_acl: jnp.ndarray      # int32 scalar: policy denies
    drop_no_route: jnp.ndarray  # int32 scalar: FIB misses
    punt: jnp.ndarray          # int32 scalar: packets punted to host stack
    dnat: jnp.ndarray          # int32 scalar: DNAT translations applied
    snat: jnp.ndarray          # int32 scalar: SNAT translations applied
    nat_reversed: jnp.ndarray  # int32 scalar: reply-path un-NAT hits
    drop_nat: jnp.ndarray      # int32 scalar: NAT fail-closed drops
                               # (SNAT port collision / un-NATable proto
                               # on an SNAT egress route)
    sess_insert_fail: jnp.ndarray     # int32 scalar: reflective-session
                                      # probe-window congestion (no slot)
    natsess_insert_fail: jnp.ndarray  # int32 scalar: NAT-session insert
                                      # congestion
    sess_occupancy: jnp.ndarray       # int32 scalar: live reflective slots
    natsess_occupancy: jnp.ndarray    # int32 scalar: live NAT slots
    if_rx: jnp.ndarray         # int32 [I] per-interface rx packets
    if_tx: jnp.ndarray         # int32 [I] per-interface tx packets
    if_rx_bytes: jnp.ndarray   # int32 [I]
    if_tx_bytes: jnp.ndarray   # int32 [I]
    if_drops: jnp.ndarray      # int32 [I] drops attributed to the rx if
    sess_hits: jnp.ndarray     # int32 scalar: alive packets admitted via
                               # a live reflective session (the fast-path
                               # dispatch signal, two-tier pipeline)
    fastpath: jnp.ndarray      # int32 scalar: 1 when this step ran the
                               # classify-free established-flow kernel,
                               # 0 for the full chain
    # set-associative insert reclamation (ops/session.py): ways
    # reclaimed by an insert, split by reason — ``expired`` is the
    # benign idle-timeout reclaim, ``victim`` means a FULL bucket
    # evicted its oldest live session to admit a new flow (the true
    # table-pressure signal)
    sess_evict_expired: jnp.ndarray     # int32 scalar
    sess_evict_victim: jnp.ndarray      # int32 scalar
    natsess_evict_expired: jnp.ndarray  # int32 scalar
    natsess_evict_victim: jnp.ndarray   # int32 scalar
    # per-packet ML scoring stage (ops/mlscore.py; all 0 with the
    # stage compiled off): alive packets scored, packets whose score
    # crossed the model's flag threshold, and packets the ENFORCE
    # policy actually dropped (score mode never drops)
    ml_scored: jnp.ndarray              # int32 scalar
    ml_flagged: jnp.ndarray             # int32 scalar
    ml_drops: jnp.ndarray               # int32 scalar
    # device-resident telemetry plane (ops/telemetry.py; 0 below
    # telemetry "full"): alive packets folded into the count-min
    # heavy-hitter flow sketch this step
    tel_sketched: jnp.ndarray           # int32 scalar
    # multi-tenant gateway mode (vpp_tpu/tenancy/; both 0 with the
    # tenancy stage compiled off): packets dropped by a tenant's
    # token-bucket rate limit this step (attributed DROP_TENANT →
    # drops_total{reason="tenant_quota"}), and session/NAT inserts
    # that failed inside a tenant's capacity slice (the per-tenant
    # congestion signal — a full slice contends only with itself)
    tnt_limited: jnp.ndarray            # int32 scalar
    tnt_qfail: jnp.ndarray              # int32 scalar
    # device-resident VXLAN overlay stage pair (ISSUE 19; all 0 with
    # ``overlay: off`` — the stage compiles out): frames decapped at
    # ip4-input (inner vector re-admitted in place), frames encapped
    # at tx (outer header built on-device, resolved by the second FIB
    # walk), and overlay-ADDRESSED frames that failed validation
    # (unknown/absent VNI, invalid inner framing — fail closed,
    # attributed DROP_OVERLAY)
    ovl_decap: jnp.ndarray              # int32 scalar
    ovl_encap: jnp.ndarray              # int32 scalar
    drop_overlay: jnp.ndarray           # int32 scalar


# Per-packet drop attribution (error-drop counter analog). Values must
# stay < 16: the packed IO boundary carries the cause in a nibble
# (pipeline/dataplane.py _packed_call output row 3).
DROP_NONE = 0
DROP_IP4 = 1        # ip4-input: TTL/length/bad interface
DROP_ACL = 2        # policy deny
DROP_NO_ROUTE = 3   # FIB miss
DROP_FIB = 4        # matched a drop route
DROP_NAT = 5        # NAT fail-closed (port collision / un-NATable proto)
DROP_ML = 6         # ML-stage enforce verdict (drop / rate-limited)
DROP_TENANT = 7     # tenant token-bucket quota exceeded (ISSUE 14)
DROP_OVERLAY = 8    # overlay fail-closed: VXLAN-addressed frame with a
                    # bad/unknown VNI or invalid inner framing (ISSUE 19)

DROP_CAUSE_NAMES = {
    DROP_NONE: "none",
    DROP_IP4: "ip4-input",
    DROP_ACL: "acl-deny",
    DROP_NO_ROUTE: "no-route",
    DROP_FIB: "fib-drop",
    DROP_NAT: "nat-drop",
    DROP_ML: "ml-drop",
    DROP_TENANT: "tenant-quota",
    DROP_OVERLAY: "overlay-drop",
}


class StepResult(NamedTuple):
    pkts: PacketVector         # header fields after rewrites (TTL, NAT)
    disp: jnp.ndarray          # int32 [P] Disposition per packet
    tx_if: jnp.ndarray         # int32 [P] egress interface (-1 if dropped)
    node_id: jnp.ndarray       # int32 [P] destination node (-1 local)
    next_hop: jnp.ndarray      # uint32 [P] peer IP for remote disposition
    tables: DataplaneTables    # tables with updated session state
    stats: StepStats
    drop_cause: jnp.ndarray    # int32 [P] DROP_* attribution (0 = none)
    established: jnp.ndarray   # bool [P] admitted via reflective session
    dnat_applied: jnp.ndarray  # bool [P] DNAT rewrote the destination
    snat_applied: jnp.ndarray  # bool [P] SNAT rewrote the source
    ml_flagged: jnp.ndarray    # bool [P] ML score crossed the flag
                               # threshold (the mirror mask: the IO
                               # path can copy these out; all-False
                               # with the stage off)
    ml_scores: jnp.ndarray     # int32 [P] raw per-packet ML scores
                               # (the PacketTracer's ml-score node
                               # reads them; all-zero with the stage
                               # off — packed paths never fetch them)
    # overlay stage pair outputs (ISSUE 19) — None with ``overlay:
    # off`` (the gate is trace-time static, so both lax.cond tiers of
    # the auto dispatcher agree on the pytree structure). ``ovl_outer``
    # is the on-device-built outer header vector (valid exactly where
    # ``ovl_encap``); the host IO edge serializes (outer, inner, vni)
    # via ops/vxlan.encode_frame — no io_callback on the wire path.
    ovl_outer: Optional[PacketVector] = None
    ovl_encap: Optional[jnp.ndarray] = None   # bool [P] encapped at tx
    ovl_vni: Optional[jnp.ndarray] = None     # int32 [P] wire VNI
                                              # (-1 where not encapped)


# Every op of the fused step is traced under one of these
# ``jax.named_scope`` names (the shared tail's encap nests as
# ``tail/overlay``), so a profiler trace or the compiled HLO's
# ``op_name`` metadata splits the one fused program by graph stage:
# the `show run` per-node accounting of the reference, under XLA.
STAGE_SCOPES = ("overlay", "ip4-input", "tenant", "session", "nat", "ml",
                "classify", "fib", "tail")


def _stage(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``,
    a fresh scope per call (``named_scope``'s own decorator form keeps
    one instance, which two threads tracing at once would share)."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


@_stage("ip4-input")
def _ingress(tables: DataplaneTables, pkts: PacketVector):
    """Shared ingress prologue of every pipeline tier: ip4-input plus
    the unconfigured-interface drop (VPP analog: unknown sw_if_index →
    error-drop). One copy, so an ingress-semantics change lands on the
    full chain, the fast kernel and the dispatch predicate alike.
    Returns (pkts, drop_ip4, alive)."""
    pkts, drop_ip4 = ip4_input(pkts)
    bad_if = tables.if_type[pkts.rx_if] == 0
    drop_ip4 = drop_ip4 | (bad_if & pkts.valid)
    return pkts, drop_ip4, pkts.valid & ~drop_ip4


@_stage("tenant")
def _tenant_eval(tables: DataplaneTables, pkts: PacketVector,
                 alive: jnp.ndarray, now, tnt_mode: str,
                 ovl_tid=None, ovl_decapped=None):
    """The ONE copy of the tenant stage's stateful half (ISSUE 14),
    run EXACTLY ONCE per fused step (both pipeline tiers, and the
    two-tier dispatcher runs it ahead of the branch and hands the
    result to whichever tier wins — tokens are consumed once either
    way): derive each packet's tenant id on the ingress header
    (tenancy/derive.py — symmetric max of the src/dst prefix matches)
    and run the per-tenant token bucket. Returns ``(tid, dropped,
    tables')`` — ``tid`` is None with the stage compiled off (every
    consumer then takes its pre-tenancy path, and the zero ``dropped``
    constant folds away).

    With the overlay stage on (ISSUE 19), ``ovl_tid``/``ovl_decapped``
    carry the decap stage's VNI-named tenant: a decapped packet's
    tenant IS its VNI's tenant (the on-device VNI ↔ tenant pact,
    docs/OVERLAY.md) and the address derivation is overridden for
    exactly those lanes — underlay addresses say nothing about the
    inner flow's tenant."""
    # jax-ok: tnt_mode is a trace-time-static step-factory gate (a
    # Python string baked into the jit key), not a tracer branch
    if tnt_mode == "off":
        return None, jnp.zeros(alive.shape, bool), tables
    from vpp_tpu.tenancy.derive import tenant_ids, tenant_limit

    tid = tenant_ids(tables, pkts)
    # jax-ok: ovl_tid None-ness is trace-time static (the overlay gate
    # decides it at step-factory time), not a tracer branch
    if ovl_tid is not None:
        tid = jnp.where(ovl_decapped, ovl_tid, tid)
    tables, dropped = tenant_limit(tables, tid, alive, now)
    return tid, dropped, tables


@_stage("ml")
def _ml_eval(tables: DataplaneTables, pkts: PacketVector,
             alive: jnp.ndarray, established: jnp.ndarray,
             sess_age: jnp.ndarray, ml_mode: str, ml_kind: str,
             shard=None, tid=None):
    """The ONE copy of the ML-stage evaluation (ISSUE 10), shared by
    the full chain and the established-flow fast tier so the two can
    never silently diverge: scored on the post-NAT-reverse header plus
    the reflective-session hit state/age — values both tiers hold at
    their scoring point, bit-identically.

    Returns ``(scored, flagged, drop_wanted, scores)`` — three masks
    [P] plus the raw int32 score vector (the PacketTracer's ml-score
    node renders it; zeros with the stage off). ``ml_mode`` /
    ``ml_kind`` are trace-time-static step-factory gates: "off"
    returns all-False constants XLA folds away (the stage costs
    nothing when disabled); "score" never requests drops; only
    "enforce" passes the policy's drop verdict through — which the
    pipeline then applies AFTER the ACL verdict (deny beats ml-drop
    beats permit, pinned by tests/test_ml_stage.py)."""
    # jax-ok: ml_mode/ml_kind are trace-time-static step-factory gates
    # (Python strings baked into the jit key), not tracer branches
    if ml_mode == "off":
        false_p = jnp.zeros(alive.shape, bool)
        return false_p, false_p, false_p, jnp.zeros(alive.shape,
                                                    jnp.int32)
    scores = ml_score(tables, pkts, established, sess_age, kind=ml_kind,
                      shard=shard)
    flagged, drop_wanted = ml_policy(tables, pkts, alive, scores,
                                     tid=tid)
    # jax-ok: ml_mode is the same trace-time-static gate as above —
    # score mode statically discards the policy's drop verdict
    if ml_mode != "enforce":
        drop_wanted = jnp.zeros(alive.shape, bool)
    return alive, flagged, drop_wanted, scores


@_stage("tail")
def _finish_step(
    tables: DataplaneTables,
    pkts: PacketVector,
    now: jnp.ndarray,
    alive: jnp.ndarray,
    drop_ip4: jnp.ndarray,
    drop_acl: jnp.ndarray,
    permit: jnp.ndarray,
    fib,
    forwarded: jnp.ndarray,
    disp: jnp.ndarray,
    tx_if: jnp.ndarray,
    established: jnp.ndarray,
    nat_reversed: jnp.ndarray,
    dnat_applied: jnp.ndarray,
    snat_applied: jnp.ndarray,
    dropped_nat: jnp.ndarray,
    sess_fail: jnp.ndarray,
    natsess_fail: jnp.ndarray,
    fastpath: jnp.ndarray,
    sess_evict_expired: jnp.ndarray,
    sess_evict_victim: jnp.ndarray,
    natsess_evict_expired: jnp.ndarray,
    natsess_evict_victim: jnp.ndarray,
    ml_scored: jnp.ndarray,
    ml_flagged: jnp.ndarray,
    ml_dropped: jnp.ndarray,
    ml_scores: jnp.ndarray,
    sweep_stride: int = 0,
    tel_mode: str = "off",
    shard=None,
    tnt_mode: str = "off",
    tid=None,
    tnt_dropped=None,
    tnt_qfail=None,
    overlay: str = "off",
    fib_fn=fib_lookup_dense,
    ovl_dropped=None,
    ovl_decapped=None,
) -> StepResult:
    """Shared tail of both pipeline tiers: drop attribution, counters,
    StepStats and the StepResult assembly. The ONE copy of the
    accounting semantics — the fast kernel calls it with its statically
    empty NAT/insert masks (all-False vectors, which XLA folds), so an
    edit to drop_cause/occupancy/per-interface logic lands on both
    tiers by construction. Also the ONE place the amortized session
    sweep runs (``sweep_stride`` buckets per table per step —
    ops/session.py session_sweep), so aging rides EVERY tier of the
    fused program identically — and the ONE place the heavy-hitter
    flow sketch (ops/telemetry.py; ``tel_mode`` "full", trace-time
    static) folds the batch in, so both tiers feed the same sketch.
    With ``overlay: vxlan`` (ISSUE 19) it is also the ONE place the
    encap half of the overlay stage pair runs — both tiers build the
    outer header and resolve it through the second FIB walk here."""
    if ovl_dropped is None:
        ovl_dropped = jnp.zeros(alive.shape, bool)
    if ovl_decapped is None:
        ovl_decapped = jnp.zeros(alive.shape, bool)
    # --- overlay encap at tx (ISSUE 19): REMOTE-disposed packets with
    # a tunnel next_hop get an on-device outer header (entropy sport
    # from the inner 5-tuple — ops/vxlan.vxlan_encap) resolved by a
    # SECOND walk over the SAME fib planes: the inner walk's ECMP
    # group already spread tunnel endpoints on the flow hash
    # (next_hop IS the chosen VTEP), the outer walk routes TO that
    # endpoint. An unroutable endpoint folds into drop_no_route, fail
    # closed. The outer walk is deliberately NOT fed into the
    # per-member ECMP accounting below — the inner walk already
    # attributed this packet to its group member; counting the
    # outer-route group too would double-bill the plane.
    # jax-ok: overlay is a trace-time-static step-factory gate (a
    # Python string baked into the jit key), not a tracer branch
    if overlay != "off":
        from vpp_tpu.ops.vxlan import DEFAULT_VNI, vxlan_encap

        with jax.named_scope("overlay"):
            ovl_need = (forwarded & (disp == int(Disposition.REMOTE))
                        & (fib.next_hop != 0))
            ovl_outer = vxlan_encap(pkts, ovl_need, tables.ovl_vtep_ip,
                                    fib.next_hop)
            ofib = fib_fn(tables, ovl_outer)
            ofib_ok = ofib.matched & (ofib.disp != int(Disposition.DROP))
            ovl_miss = ovl_need & ~ofib_ok
            forwarded = forwarded & ~ovl_miss
            disp = jnp.where(ovl_miss, int(Disposition.DROP),
                             disp).astype(jnp.int32)
            ovl_encap = ovl_need & ofib_ok
            tx_if = jnp.where(ovl_encap, ofib.tx_if,
                              jnp.where(ovl_miss, -1, tx_if))
            ovl_outer = ovl_outer._replace(
                flags=jnp.where(ovl_encap, ovl_outer.flags, 0))
            # per-tenant VNI on the wire: the tenant's configured VNI
            # (tnt_vni — tenancy off keeps slot 0 at DEFAULT_VNI), with
            # DEFAULT_VNI covering tenants that configured none
            # jax-ok: tid None-ness is the trace-time-static tnt gate
            if tid is not None:
                vni_raw = tables.tnt_vni[tid]
            else:
                vni_raw = jnp.broadcast_to(tables.tnt_vni[0],
                                           alive.shape)
            vni = jnp.where(vni_raw >= 0, vni_raw, DEFAULT_VNI)
            ovl_vni_out = jnp.where(ovl_encap, vni, -1).astype(jnp.int32)
    else:
        ovl_miss = jnp.zeros(alive.shape, bool)
        ovl_encap = jnp.zeros(alive.shape, bool)
        ovl_outer = None
        ovl_vni_out = None
    tables = session_sweep(tables, now, sweep_stride)
    # per-member ECMP accounting (ISSUE 15; ops/fib.py resolve): one
    # flat scatter-add of forwarded group-routed packets into the
    # carried [G, W] plane — both tiers feed it here, the one place.
    # Non-ECMP packets (grp -1) target the out-of-range index and drop.
    n_grp, n_way = tables.fib_ecmp_c.shape
    gw = jnp.where(forwarded & (fib.grp >= 0),
                   fib.grp * n_way + fib.way, n_grp * n_way)
    tables = tables._replace(
        fib_ecmp_c=tables.fib_ecmp_c.reshape(-1).at[gw].add(
            1, mode="drop").reshape(n_grp, n_way))
    # jax-ok: tel_mode is a trace-time-static step-factory gate (a
    # Python string baked into the jit key), not a tracer branch
    if tel_mode == "full":
        from vpp_tpu.ops.telemetry import tel_flow_update

        tables, tel_sketched = tel_flow_update(tables, pkts, alive)
    else:
        tel_sketched = jnp.int32(0)
    # tenancy masks (ISSUE 14): ``alive`` at this point EXCLUDES
    # rate-limited packets (both tiers mask right after the tenant
    # stage); alive_all restores them for the rx/per-interface counts
    # — they were real received traffic, dropped with attribution
    if tnt_dropped is None:
        tnt_dropped = jnp.zeros(alive.shape, bool)
    if tnt_qfail is None:
        tnt_qfail = jnp.zeros(alive.shape, bool)
    # overlay fail-closed lanes left ``alive`` right after ip4-input
    # (the decap stage's bad mask) but were real received traffic —
    # alive_all restores them for rx/per-interface counts exactly
    # like the rate-limited lanes
    alive_all = alive | tnt_dropped | ovl_dropped
    # jax-ok: tnt_mode is a trace-time-static step-factory gate (a
    # Python string baked into the jit key), not a tracer branch
    if tnt_mode != "off":
        from vpp_tpu.tenancy.derive import tnt_account

        tables = tnt_account(tables, tid, alive_all, forwarded,
                             tnt_dropped, tnt_qfail)
    n_ifaces = tables.if_type.shape[0]

    def occupancy(valid, time):
        """Live slots (valid, not idle-expired). Sharded, the local
        sum covers this shard's bucket range; one psum makes the
        scalar the whole table's occupancy on every shard — StepStats
        outputs must be replicated along the rule axis."""
        occ = jnp.sum(((valid == 1)
                       & (now - time <= tables.sess_max_age)
                       ).astype(jnp.int32))
        if shard is not None:
            from jax import lax

            occ = lax.psum(occ, shard.axis)
        return occ

    # ml-drop wins attribution over the FIB outcomes (the packet never
    # reached forwarding), but LOSES to ACL deny: ml_dropped is
    # already masked to permitted traffic by the callers
    drop_no_route = (alive & permit & ~fib.matched & ~ml_dropped
                     | ovl_miss)
    fib_dropped = alive & permit & fib.matched & (
        fib.disp == int(Disposition.DROP)
    ) & ~ml_dropped
    dropped = (
        (pkts.valid & (drop_ip4 | drop_acl | drop_no_route))
        | fib_dropped
        | dropped_nat
        | ml_dropped
        | tnt_dropped
        | ovl_dropped
    )
    rx_if_safe = jnp.where(alive_all, pkts.rx_if, n_ifaces)
    tx_if_safe = jnp.where(forwarded, tx_if, n_ifaces)
    drop_if_safe = jnp.where(dropped, pkts.rx_if, n_ifaces)
    zero_i = jnp.zeros((n_ifaces,), jnp.int32)
    stats = StepStats(
        rx=jnp.sum(alive_all.astype(jnp.int32)),
        tx=jnp.sum(forwarded.astype(jnp.int32)),
        drop_ip4=jnp.sum(drop_ip4.astype(jnp.int32)),
        drop_acl=jnp.sum(drop_acl.astype(jnp.int32)),
        drop_no_route=jnp.sum(drop_no_route.astype(jnp.int32)),
        punt=jnp.sum(
            (forwarded & (disp == int(Disposition.HOST))).astype(jnp.int32)
        ),
        dnat=jnp.sum((dnat_applied & forwarded).astype(jnp.int32)),
        snat=jnp.sum((snat_applied & forwarded).astype(jnp.int32)),
        nat_reversed=jnp.sum((nat_reversed & forwarded).astype(jnp.int32)),
        drop_nat=jnp.sum(dropped_nat.astype(jnp.int32)),
        sess_insert_fail=jnp.sum(sess_fail.astype(jnp.int32)),
        natsess_insert_fail=jnp.sum(natsess_fail.astype(jnp.int32)),
        # live = valid and not idle-expired (what lookups actually see)
        sess_occupancy=occupancy(tables.sess_valid, tables.sess_time),
        natsess_occupancy=occupancy(tables.natsess_valid,
                                    tables.natsess_time),
        if_rx=zero_i.at[rx_if_safe].add(1, mode="drop"),
        if_tx=zero_i.at[tx_if_safe].add(1, mode="drop"),
        if_rx_bytes=zero_i.at[rx_if_safe].add(
            jnp.where(alive_all, pkts.pkt_len, 0), mode="drop"
        ),
        if_tx_bytes=zero_i.at[tx_if_safe].add(
            jnp.where(forwarded, pkts.pkt_len, 0), mode="drop"
        ),
        if_drops=zero_i.at[drop_if_safe].add(1, mode="drop"),
        sess_hits=jnp.sum(established.astype(jnp.int32)),
        fastpath=fastpath,
        sess_evict_expired=jnp.sum(sess_evict_expired.astype(jnp.int32)),
        sess_evict_victim=jnp.sum(sess_evict_victim.astype(jnp.int32)),
        natsess_evict_expired=jnp.sum(
            natsess_evict_expired.astype(jnp.int32)),
        natsess_evict_victim=jnp.sum(
            natsess_evict_victim.astype(jnp.int32)),
        ml_scored=jnp.sum(ml_scored.astype(jnp.int32)),
        ml_flagged=jnp.sum(ml_flagged.astype(jnp.int32)),
        ml_drops=jnp.sum(ml_dropped.astype(jnp.int32)),
        tel_sketched=tel_sketched,
        tnt_limited=jnp.sum(tnt_dropped.astype(jnp.int32)),
        tnt_qfail=jnp.sum(tnt_qfail.astype(jnp.int32)),
        ovl_decap=jnp.sum(ovl_decapped.astype(jnp.int32)),
        ovl_encap=jnp.sum(ovl_encap.astype(jnp.int32)),
        drop_overlay=jnp.sum(ovl_dropped.astype(jnp.int32)),
    )
    # attribution stays exclusive: tnt_dropped packets left ``alive``
    # right after the tenant stage, so every other cause mask (all
    # derived from alive/permit/forwarded) excludes them; ovl_dropped
    # lanes likewise left right after ip4-input (and exclude the
    # drop_ip4 lanes — the decap stage masks them out)
    drop_cause = (
        jnp.where(pkts.valid & drop_ip4, DROP_IP4, 0)
        + jnp.where(drop_acl, DROP_ACL, 0)
        + jnp.where(drop_no_route, DROP_NO_ROUTE, 0)
        + jnp.where(fib_dropped, DROP_FIB, 0)
        + jnp.where(dropped_nat, DROP_NAT, 0)
        + jnp.where(ml_dropped, DROP_ML, 0)
        + jnp.where(tnt_dropped, DROP_TENANT, 0)
        + jnp.where(ovl_dropped, DROP_OVERLAY, 0)
    ).astype(jnp.int32)
    return StepResult(
        pkts=pkts,
        disp=disp,
        tx_if=tx_if,
        node_id=jnp.where(forwarded, fib.node_id, -1),
        next_hop=jnp.where(forwarded, fib.next_hop, jnp.uint32(0)),
        tables=tables,
        stats=stats,
        drop_cause=drop_cause,
        established=established,
        dnat_applied=dnat_applied,
        snat_applied=snat_applied,
        ml_flagged=ml_flagged,
        ml_scores=ml_scores,
        ovl_outer=ovl_outer,
        ovl_encap=ovl_encap if overlay != "off" else None,
        ovl_vni=ovl_vni_out,
    )



# Buckets swept per table per fused step when the caller doesn't plumb
# the DataplaneConfig knob (the cluster step, module-level jits, tests
# calling pipeline_step directly).
SWEEP_STRIDE_DEFAULT = 256


def pipeline_step(
    tables: DataplaneTables,
    pkts: PacketVector,
    now: jnp.ndarray,
    acl_global_fn=acl_classify_global,
    acl_local_fn=acl_classify_local,
    sweep_stride: int = SWEEP_STRIDE_DEFAULT,
    ml_mode: str = "off",
    ml_kind: str = "mlp",
    tel_mode: str = "off",
    tnt_mode: str = "off",
    fib_fn=fib_lookup_dense,
    sess_impl: str = "gather",
    sess_hash: str = "fwd",
    shard=None,
    overlay: str = "off",
    ovl_inner=None,
    ovl_vni=None,
    _tnt_pre=None,
) -> StepResult:
    """Process one packet vector through the full forwarding chain.

    Pure function: (tables, frame, time) → (result, new session state).
    Jit once; call per frame. ``acl_global_fn`` lets the multi-chip
    cluster step substitute a rule-sharded global classify
    (vpp_tpu.parallel.cluster) without altering the chain;
    ``acl_local_fn`` swaps the per-interface classify the same way
    (the BV implementation, or the policy-free skip —
    ``make_pipeline_step`` composes both). ``sweep_stride`` buckets per
    session table are aged inside the step (trace-time static —
    ops/session.py session_sweep). ``ml_mode``/``ml_kind`` gate the
    per-packet ML scoring stage (trace-time static — ``_ml_eval``).
    ``shard`` (parallel/partition.py ShardCtx) marks the session/NAT
    bucket grids and ML weight planes as rule-axis shards: the session
    ops hash globally and recombine with psums, so the chain's
    per-packet results stay bit-exact vs standalone (docs/PARTITIONING.md).

    ``overlay: vxlan`` (ISSUE 19) engages the fused overlay stage
    pair: decap runs HERE, ahead of ip4-input (the outer header plus
    the host-parsed ``ovl_inner``/``ovl_vni`` sidecar — the inner
    vector is re-admitted in place, fail-closed lanes leave ``alive``
    attributed DROP_OVERLAY), and encap runs at tx inside the shared
    tail. Trace-time static like every other gate — ONE step-form
    dimension in the jit cache, zero io_callbacks.
    """
    # --- overlay decap at ip4-input (ISSUE 19) ---
    # jax-ok: overlay is a trace-time-static step-factory gate (a
    # Python string baked into the jit key), not a tracer branch
    if overlay != "off":
        from vpp_tpu.ops.vxlan import vxlan_decap_step

        with jax.named_scope("overlay"):
            pkts, ovl_bad, ovl_decapped, ovl_tid = vxlan_decap_step(
                tables, pkts, ovl_inner, ovl_vni)
    else:
        ovl_bad = ovl_decapped = ovl_tid = None

    # --- ip4-input (+ unconfigured-interface drop) ---
    pkts, drop_ip4, alive = _ingress(tables, pkts)
    # jax-ok: same trace-time-static overlay gate as above
    if overlay != "off":
        # fail-closed overlay lanes leave here; ip4-input keeps
        # attribution priority on lanes it already dropped (the outer
        # header must parse before the decap verdict means anything)
        ovl_dropped = ovl_bad & ~drop_ip4
        alive = alive & ~ovl_dropped
    else:
        ovl_dropped = None

    # --- tenant stage (ISSUE 14): derive + token-bucket ONCE per step.
    # ``_tnt_pre`` is the two-tier dispatcher's pre-consumed trio (it
    # runs _tenant_eval ahead of the lax.cond so neither branch
    # double-consumes tokens); rate-limited packets leave ``alive``
    # here — no session touch, no NAT state, no forwarding, attributed
    # DROP_TENANT in the shared tail.
    # jax-ok: _tnt_pre None-ness is trace-time static (the dispatcher
    # always passes it under tenancy), not a tracer branch
    if _tnt_pre is not None:
        tid, tnt_dropped, tables = _tnt_pre
    else:
        tid, tnt_dropped, tables = _tenant_eval(tables, pkts, alive,
                                                now, tnt_mode,
                                                ovl_tid=ovl_tid,
                                                ovl_decapped=ovl_decapped)
    alive = alive & ~tnt_dropped
    tnt = tnt_mode != "off"

    # --- reflective session bypass (return traffic of permitted flows) ---
    # Looked up on the raw (pre-NAT) header: forward sessions are installed
    # post-DNAT, so a backend's reply B→C reverses to the stored C→B key.
    # Expired entries (idle > sess_max_age ticks) don't match, and hits
    # refresh the timestamp — active flows never expire mid-flow.
    with jax.named_scope("session"):
        established, sess_hit_idx = session_lookup_reverse_idx(
            tables, pkts, now, shard=shard, tnt=tnt, impl=sess_impl,
            sym=sess_hash == "sym")
        established = established & alive
        # pre-touch session age: an ML feature (the touch below
        # refreshes the timestamp, so the age must be captured first —
        # the fast tier captures it at the same pre-touch point,
        # docs/ML_STAGE.md)
        sess_age = session_hit_age(tables, sess_hit_idx, established,
                                   now, shard=shard)
        tables = session_touch(tables, sess_hit_idx, established, now,
                               shard=shard)

    # --- NAT44: reverse-translate return traffic, then DNAT new flows ---
    with jax.named_scope("nat"):
        pkts, nat_reversed, nat_hit_idx = nat44_reverse(
            tables, pkts, alive, now, shard=shard, tnt=tnt)
        tables = nat44_touch(tables, nat_hit_idx, nat_reversed, now,
                             shard=shard)

    # --- per-packet ML scoring (ISSUE 10): on the post-reverse header,
    # the same values the fast tier scores — ONE shared evaluation
    ml_scored, ml_flagged, ml_drop_want, ml_scores = _ml_eval(
        tables, pkts, alive, established, sess_age, ml_mode, ml_kind,
        shard=shard, tid=tid)

    orig_dst, orig_dport = pkts.dst_ip, pkts.dport
    with jax.named_scope("nat"):
        pkts, dnat_applied, dnat_self_snat = nat44_dnat(
            tables, pkts, alive & ~nat_reversed
        )

    # --- ACL classify (local per-interface table + node-global table) ---
    with jax.named_scope("classify"):
        local_v = acl_local_fn(tables, pkts)
        glob_v = acl_global_fn(tables, pkts)
        permit = (local_v.permit & glob_v.permit) | established
        drop_acl = alive & ~permit

        # enforce-mode ML verdict, folded AFTER the ACL verdict: an
        # ACL-denied packet stays an ACL drop (deny beats ml-drop), an
        # ACL-permitted flagged packet drops here (ml-drop beats permit)
        ml_dropped = ml_drop_want & permit & alive

    # --- ip4-lookup (on possibly NAT-rewritten dst; dense or LPM per
    # the fib_impl ladder — both resolve through ops.fib) ---
    with jax.named_scope("fib"):
        fib = fib_fn(tables, pkts)
        forwarded = (alive & permit & ~ml_dropped & fib.matched
                     & (fib.disp != int(Disposition.DROP)))
        disp = jnp.where(forwarded, fib.disp,
                         int(Disposition.DROP)).astype(jnp.int32)
        tx_if = jnp.where(forwarded, fib.tx_if, -1)

    # --- SNAT for cluster-egress flows (routes marked snat) and for
    # self-snat DNAT mappings (nodeports: the backend's reply must return
    # through this node for un-DNAT even when the backend is remote).
    # New outbound flows only: reply traffic (un-NAT'd above, or admitted
    # via a reflective session) must keep its translated/original source.
    # Reference: configurator_impl.go:258-264 SNAT pool.
    with jax.named_scope("nat"):
        is_l4 = (pkts.proto == 6) | (pkts.proto == 17)
        # icmp: src-only translation
        nat_capable = is_l4 | (pkts.proto == 1)
        fresh = ~nat_reversed & ~established
        orig_src, orig_sport = pkts.src_ip, pkts.sport
        want_snat = (forwarded & fresh & nat_capable
                     & (fib.snat | dnat_self_snat))
        pkts, snat_applied = nat44_snat(tables, pkts, want_snat)
        # A protocol NAT can't translate, leaving via an SNAT route,
        # would leak the pod's private source address — fail closed.
        nat_unsupported = (
            forwarded & fresh & ~nat_capable & fib.snat
            & (tables.nat_snat_ip != 0)
        )

    # --- session install for newly permitted flows only (denied packets
    # must not consume session slots); keys are post-NAT so replies match ---
    with jax.named_scope("session"):
        want_sess = (forwarded & ~established & nat_capable
                     & ~nat_unsupported)
        tables, _, sess_fail, sess_ev_exp, sess_ev_vic = session_insert(
            tables, pkts, want_sess, now, shard=shard, tnt=tnt,
            sym=sess_hash == "sym")
    with jax.named_scope("nat"):
        nat_kind = (
            jnp.where(dnat_applied, 1, 0) + jnp.where(snat_applied, 2, 0)
        ).astype(jnp.int32)
        (tables, nat_conflict, natsess_fail, nat_ev_exp,
         nat_ev_vic) = nat44_record(
            tables, pkts, orig_dst, orig_dport, orig_src, orig_sport,
            nat_kind, (dnat_applied | snat_applied) & forwarded, now,
            shard=shard, tnt=tnt,
        )
        # Fail closed on reply-key collisions (two SNAT'd flows hashed
        # onto the same external port): misdelivering replies to the
        # wrong pod is worse than dropping the colliding flow — drops
        # are counted.
        dropped_nat = nat_conflict | nat_unsupported
        forwarded = forwarded & ~dropped_nat
        disp = jnp.where(dropped_nat, int(Disposition.DROP),
                         disp).astype(jnp.int32)
        tx_if = jnp.where(dropped_nat, -1, tx_if)

    # counters / attribution / result assembly: the shared tail
    return _finish_step(
        tables, pkts, now, alive, drop_ip4, drop_acl, permit, fib,
        forwarded, disp, tx_if, established, nat_reversed, dnat_applied,
        snat_applied, dropped_nat, sess_fail, natsess_fail,
        fastpath=jnp.int32(0),
        sess_evict_expired=sess_ev_exp, sess_evict_victim=sess_ev_vic,
        natsess_evict_expired=nat_ev_exp, natsess_evict_victim=nat_ev_vic,
        ml_scored=ml_scored, ml_flagged=ml_flagged, ml_dropped=ml_dropped,
        ml_scores=ml_scores, sweep_stride=sweep_stride, tel_mode=tel_mode,
        shard=shard, tnt_mode=tnt_mode, tid=tid, tnt_dropped=tnt_dropped,
        # only meaningful with the stage on (the per-tenant congestion
        # signal); the off-state constant keeps the counter at 0
        tnt_qfail=(sess_fail | natsess_fail) if tnt else None,
        overlay=overlay, fib_fn=fib_fn, ovl_dropped=ovl_dropped,
        ovl_decapped=ovl_decapped,
    )


# --- two-tier established-flow fast path ------------------------------
#
# BENCH_r05 put 15.4 ms of the 24.2 ms fused step in the global ACL
# classify, yet steady-state traffic is return flows the reflective
# session table already admits — the full chain computed `established`
# and then ran the classifier anyway just to OR the verdicts. The split
# below is the VPP acl-plugin flow-cache idea on a vector machine:
# per-PACKET branching is impossible under XLA (every lane executes
# every instruction), so the dispatch granularity is the BATCH — one
# `lax.cond` on "every valid packet hit a live session (and none
# touches DNAT state)" picks a classify-free kernel for the whole
# vector, and any partial-hit batch falls through to the full chain
# bit-for-bit unchanged.


def _pipeline_fast_finish(
    tables: DataplaneTables,
    pkts: PacketVector,
    now: jnp.ndarray,
    alive: jnp.ndarray,
    drop_ip4: jnp.ndarray,
    established: jnp.ndarray,
    sess_hit_idx: jnp.ndarray,
    nat_reversed: jnp.ndarray,
    nat_hit_idx: jnp.ndarray,
    sweep_stride: int = SWEEP_STRIDE_DEFAULT,
    ml_mode: str = "off",
    ml_kind: str = "mlp",
    tel_mode: str = "off",
    tnt_mode: str = "off",
    fib_fn=fib_lookup_dense,
    shard=None,
    tid=None,
    tnt_dropped=None,
    overlay: str = "off",
    ovl_dropped=None,
    ovl_decapped=None,
) -> StepResult:
    """Tail of the classify-free kernel, from post-reverse headers on.

    Valid ONLY under the dispatch invariant (every alive packet is
    established, none DNAT-matches): `permit` collapses to
    `established`, SNAT/session-insert/NAT-record are statically empty
    (they all require a fresh flow or a DNAT hit) and are elided rather
    than computed-and-discarded — that elision IS the speedup.

    The ML stage is NOT elided: the fast tier still scores (and in
    enforce mode still drops) every packet — anomaly traffic rides
    established flows too, and a fast tier that skipped the model
    would silently diverge from the full chain exactly on the
    steady-state traffic the model exists to police. ``_ml_eval`` is
    the ONE shared evaluation; the age feature is captured pre-touch
    here exactly as the full chain captures it.
    """
    # tenancy (ISSUE 14): ``alive``/``established`` arrive POST-limit
    # from the callers (the tenant stage ran before the lookups, the
    # full-chain order); tid/tnt_dropped ride through to the shared
    # tail for attribution + per-tenant accounting
    if tnt_dropped is None:
        tnt_dropped = jnp.zeros(alive.shape, bool)
    # pre-touch session age (the ML age feature — full-chain parity)
    with jax.named_scope("session"):
        sess_age = session_hit_age(tables, sess_hit_idx, established, now,
                                   shard=shard)
        tables = session_touch(tables, sess_hit_idx, established, now,
                               shard=shard)
    with jax.named_scope("nat"):
        tables = nat44_touch(tables, nat_hit_idx, nat_reversed, now,
                             shard=shard)

    # permit == (local & glob) | established on every alive packet by
    # the dispatch invariant, so the classify is skipped outright
    permit = established
    drop_acl = alive & ~permit

    ml_scored, ml_flagged, ml_drop_want, ml_scores = _ml_eval(
        tables, pkts, alive, established, sess_age, ml_mode, ml_kind,
        shard=shard, tid=tid)

    with jax.named_scope("fib"):
        ml_dropped = ml_drop_want & permit & alive
        fib = fib_fn(tables, pkts)
        forwarded = alive & permit & ~ml_dropped & fib.matched & (
            fib.disp != int(Disposition.DROP)
        )
        disp = jnp.where(forwarded, fib.disp,
                         int(Disposition.DROP)).astype(jnp.int32)
        tx_if = jnp.where(forwarded, fib.tx_if, -1)

    # the elided stages are statically empty under the invariant: hand
    # the shared tail all-False masks (XLA folds the dead reductions)
    false_p = jnp.zeros(alive.shape, bool)
    return _finish_step(
        tables, pkts, now, alive, drop_ip4, drop_acl, permit, fib,
        forwarded, disp, tx_if, established, nat_reversed,
        dnat_applied=false_p, snat_applied=false_p, dropped_nat=false_p,
        sess_fail=false_p, natsess_fail=false_p, fastpath=jnp.int32(1),
        sess_evict_expired=false_p, sess_evict_victim=false_p,
        natsess_evict_expired=false_p, natsess_evict_victim=false_p,
        ml_scored=ml_scored, ml_flagged=ml_flagged, ml_dropped=ml_dropped,
        ml_scores=ml_scores, sweep_stride=sweep_stride, tel_mode=tel_mode,
        shard=shard, tnt_mode=tnt_mode, tid=tid, tnt_dropped=tnt_dropped,
        # the fast tier inserts nothing, so slice quota failures are
        # statically empty here (the all-False constant XLA folds)
        tnt_qfail=None,
        overlay=overlay, fib_fn=fib_fn, ovl_dropped=ovl_dropped,
        ovl_decapped=ovl_decapped,
    )


def pipeline_step_fast(
    tables: DataplaneTables, pkts: PacketVector, now: jnp.ndarray,
    sweep_stride: int = SWEEP_STRIDE_DEFAULT,
    ml_mode: str = "off",
    ml_kind: str = "mlp",
    tel_mode: str = "off",
    tnt_mode: str = "off",
    fib_fn=fib_lookup_dense,
    sess_impl: str = "gather",
    sess_hash: str = "fwd",
    shard=None,
    overlay: str = "off",
    ovl_inner=None,
    ovl_vni=None,
) -> StepResult:
    """The classify-free established-flow kernel, standalone:
    [overlay decap] → ip4-input → session lookup/touch → NAT
    reverse/touch → [ML score] → FIB → tx [→ overlay encap].

    Bit-exact with ``pipeline_step`` ONLY when every valid packet hits
    a live reflective session and none DNAT-matches — the invariant
    ``pipeline_step_auto``'s dispatch predicate guarantees. Exposed on
    its own for the differential test and the bench's speedup capture;
    production traffic goes through the auto dispatcher.
    """
    # jax-ok: overlay is a trace-time-static step-factory gate (a
    # Python string baked into the jit key), not a tracer branch
    if overlay != "off":
        from vpp_tpu.ops.vxlan import vxlan_decap_step

        with jax.named_scope("overlay"):
            pkts, ovl_bad, ovl_decapped, ovl_tid = vxlan_decap_step(
                tables, pkts, ovl_inner, ovl_vni)
    else:
        ovl_bad = ovl_decapped = ovl_tid = None
    pkts, drop_ip4, alive = _ingress(tables, pkts)
    # jax-ok: same trace-time-static overlay gate as above
    if overlay != "off":
        ovl_dropped = ovl_bad & ~drop_ip4
        alive = alive & ~ovl_dropped
    else:
        ovl_dropped = None
    # tenant stage first — the full-chain order, so the two tiers stay
    # bit-exact under the dispatch invariant with tenancy on too
    tid, tnt_dropped, tables = _tenant_eval(tables, pkts, alive, now,
                                            tnt_mode, ovl_tid=ovl_tid,
                                            ovl_decapped=ovl_decapped)
    alive = alive & ~tnt_dropped
    tnt = tnt_mode != "off"
    with jax.named_scope("session"):
        established, sess_hit_idx = session_lookup_reverse_idx(
            tables, pkts, now, shard=shard, tnt=tnt, impl=sess_impl,
            sym=sess_hash == "sym")
        established = established & alive
    with jax.named_scope("nat"):
        pkts, nat_reversed, nat_hit_idx = nat44_reverse(
            tables, pkts, alive, now, shard=shard, tnt=tnt)
    return _pipeline_fast_finish(
        tables, pkts, now, alive, drop_ip4, established, sess_hit_idx,
        nat_reversed, nat_hit_idx, sweep_stride=sweep_stride,
        ml_mode=ml_mode, ml_kind=ml_kind, tel_mode=tel_mode,
        tnt_mode=tnt_mode, fib_fn=fib_fn, shard=shard, tid=tid,
        tnt_dropped=tnt_dropped, overlay=overlay,
        ovl_dropped=ovl_dropped, ovl_decapped=ovl_decapped,
    )


def pipeline_step_auto(
    tables: DataplaneTables,
    pkts: PacketVector,
    now: jnp.ndarray,
    acl_global_fn=acl_classify_global,
    acl_local_fn=acl_classify_local,
    sweep_stride: int = SWEEP_STRIDE_DEFAULT,
    ml_mode: str = "off",
    ml_kind: str = "mlp",
    tel_mode: str = "off",
    tnt_mode: str = "off",
    fib_fn=fib_lookup_dense,
    sess_impl: str = "gather",
    sess_hash: str = "fwd",
    shard=None,
    overlay: str = "off",
    ovl_inner=None,
    ovl_vni=None,
) -> StepResult:
    """Two-tier dispatch: the fast kernel when the whole batch rides
    established sessions, the full chain otherwise.

    With the overlay on (ISSUE 19) the decap stage runs ahead of the
    predicate — established INNER flows ride the fast tier even when
    they arrive encapped, which is exactly the east-west steady state
    the tier exists for. The full branch re-derives from the pre-decap
    vector (identical by construction, like the ingress masks).

    With tenancy on (ISSUE 14) the tenant stage runs HERE, ahead of
    the branch: token consumption is stateful and must happen exactly
    once per step, so the dispatcher consumes and hands the trio to
    whichever tier wins (the full branch takes it via ``_tnt_pre``
    instead of re-running ``_tenant_eval``). The dispatch predicate
    evaluates on the post-limit alive set — a rate-limited packet
    skips every downstream stage identically in both tiers.

    The predicate work (ip4-input, session summary, NAT reverse, DNAT
    probe) is computed once up front; the fast branch reuses it via
    closure, the full branch recomputes inside ``pipeline_step`` —
    paying a second session/NAT lookup only on the path that is about
    to pay the full classifier anyway. ``lax.cond`` executes exactly
    one branch per batch, so steady-state (all-established) traffic
    never touches the ACL tables.

    The predicate additionally requires that NO packet would DNAT-match
    after un-NAT: a reflective-session hit whose destination is also a
    service VIP still takes the full chain, because the full chain
    DNATs it and records NAT state the fast kernel elides.

    SPMD-uniformity under the mesh (``shard``): the sharded session
    summary already recombines per-shard hits with a psum, and the
    dispatch flag is additionally ALL-REDUCED (``pmin`` of each shard's
    flag) before the ``lax.cond`` — every shard provably takes the
    same branch, so the collectives inside both tiers line up. This is
    what lets the fast tier finally run under shard_map (the pre-ISSUE-
    12 cluster pump documented the predicate as not SPMD-uniform and
    pinned the mesh to the full chain).
    """
    from jax import lax

    orig_pkts = pkts
    # jax-ok: overlay is a trace-time-static step-factory gate (a
    # Python string baked into the jit key), not a tracer branch
    if overlay != "off":
        from vpp_tpu.ops.vxlan import vxlan_decap_step

        with jax.named_scope("overlay"):
            pkts, ovl_bad, ovl_decapped, ovl_tid = vxlan_decap_step(
                tables, pkts, ovl_inner, ovl_vni)
    else:
        ovl_bad = ovl_decapped = ovl_tid = None
    pkts1, drop_ip4, alive = _ingress(tables, pkts)
    # jax-ok: same trace-time-static overlay gate as above
    if overlay != "off":
        ovl_dropped = ovl_bad & ~drop_ip4
        alive = alive & ~ovl_dropped
    else:
        ovl_dropped = None
    # tenant stage ONCE, ahead of the branch (docstring); tbl carries
    # the consumed token buckets into whichever tier wins
    tid, tnt_dropped, tbl = _tenant_eval(tables, pkts1, alive, now,
                                         tnt_mode, ovl_tid=ovl_tid,
                                         ovl_decapped=ovl_decapped)
    alive = alive & ~tnt_dropped
    tnt = tnt_mode != "off"
    with jax.named_scope("session"):
        hits, sess_hit_idx, all_hit = session_batch_summary(
            tbl, pkts1, alive, now, shard=shard, tnt=tnt, impl=sess_impl,
            sym=sess_hash == "sym"
        )
    # NAT reverse runs before the DNAT probe: the un-NAT'd header is
    # what the full chain would hand nat44_dnat
    with jax.named_scope("nat"):
        rpkts, nat_reversed, nat_hit_idx = nat44_reverse(
            tbl, pkts1, alive, now, shard=shard, tnt=tnt
        )
        dnat_would = nat44_dnat_match(tbl, rpkts, alive & ~nat_reversed)
        ok = all_hit & ~jnp.any(dnat_would)
    if shard is not None:
        # the all-reduce that makes the dispatch provably uniform: the
        # inputs are already replicated (psum'd lookups), and the pmin
        # collapses any would-be divergence into "all take the slow
        # tier" instead of a cross-shard collective mismatch
        ok = lax.pmin(ok.astype(jnp.int32), shard.axis) > 0

    def fast(_):
        return _pipeline_fast_finish(
            tbl, rpkts, now, alive, drop_ip4, hits, sess_hit_idx,
            nat_reversed, nat_hit_idx, sweep_stride=sweep_stride,
            ml_mode=ml_mode, ml_kind=ml_kind, tel_mode=tel_mode,
            tnt_mode=tnt_mode, fib_fn=fib_fn, shard=shard, tid=tid,
            tnt_dropped=tnt_dropped, overlay=overlay,
            ovl_dropped=ovl_dropped, ovl_decapped=ovl_decapped,
        )

    def full(_):
        # the full chain re-derives its own ingress masks (and the
        # overlay decap) from orig_pkts (identical by construction)
        # but takes the ALREADY-CONSUMED tenant trio — tokens are
        # never spent twice
        return pipeline_step(tables, orig_pkts, now, acl_global_fn,
                             acl_local_fn, sweep_stride=sweep_stride,
                             ml_mode=ml_mode, ml_kind=ml_kind,
                             tel_mode=tel_mode, tnt_mode=tnt_mode,
                             fib_fn=fib_fn, sess_impl=sess_impl,
                             sess_hash=sess_hash, shard=shard,
                             overlay=overlay, ovl_inner=ovl_inner,
                             ovl_vni=ovl_vni,
                             _tnt_pre=((tid, tnt_dropped, tbl)
                                       if tnt else None))

    # Under shard_map the two tiers must agree on which mesh axes each
    # output varies over: the fast tier folds some stats and masks to
    # constants, which the full tier computes per node. Both branches
    # are cast to vary over the packet batch's axes.
    axes = frozenset().union(
        *(jax.typeof(x).vma for x in jax.tree.leaves(orig_pkts)))
    # jax-ok: the varying axes are part of the abstract type, static
    # at trace time
    if axes:
        fast = _varying_over(fast, axes)
        full = _varying_over(full, axes)
    return lax.cond(ok, fast, full, None)


def _varying_over(branch, axes):
    """``branch`` with every output leaf cast to vary over ``axes``
    (the ``lax.cond`` branch-type rule of shard_map's manual axes)."""
    from jax import lax

    def cast(x):
        missing = tuple(sorted(axes - jax.typeof(x).vma))
        return lax.pcast(x, missing, to="varying") if missing else x

    return lambda op: jax.tree.map(cast, branch(op))


def _classifier_fns(impl: str):
    """(global, local) classify functions of one implementation name.
    Only BV swaps the LOCAL classify too — the MXU kernel is a
    global-table reformulation (bit-plane matmul doesn't gather
    per-packet tables), so mxu keeps the dense local path."""
    if impl == "mxu":
        from vpp_tpu.ops.acl_mxu import acl_classify_global_mxu

        return acl_classify_global_mxu, acl_classify_local
    if impl == "bv":
        from vpp_tpu.ops.acl_bv import (
            acl_classify_global_bv,
            acl_classify_local_bv,
        )

        return acl_classify_global_bv, acl_classify_local_bv
    if impl == "pallas":
        # ISSUE 16: the fused BV word-AND + first-set kernel rung.
        # The functions dispatch internally (ops/_pallas.use_pallas):
        # off-TPU they ARE the bv rung, so a pallas-knobbed config
        # stays bit-exact on the CPU harness.
        from vpp_tpu.ops.acl_bv import (
            acl_classify_global_pallas,
            acl_classify_local_pallas,
        )

        return acl_classify_global_pallas, acl_classify_local_pallas
    if impl != "dense":
        raise ValueError(f"unknown classifier impl {impl!r}")
    return acl_classify_global, acl_classify_local


def _fib_fn(fib_impl: str):
    """The ip4-lookup implementation of one ladder rung (the
    _classifier_fns twin — ops/fib.py dense masked-compare,
    ops/lpm.py binary-search-over-prefix-lengths, or its fused pallas
    form; docs/ROUTING.md, docs/KERNELS.md)."""
    if fib_impl == "lpm":
        from vpp_tpu.ops.lpm import fib_lookup_lpm

        return fib_lookup_lpm
    if fib_impl == "pallas":
        from vpp_tpu.ops.lpm import fib_lookup_lpm_fused

        return fib_lookup_lpm_fused
    if fib_impl != "dense":
        raise ValueError(f"unknown fib impl {fib_impl!r}")
    return fib_lookup_dense


@functools.lru_cache(maxsize=None)
def make_pipeline_step(impl: str = "dense", skip_local: bool = False,
                       fast: bool = False,
                       sweep_stride: int = SWEEP_STRIDE_DEFAULT,
                       ml_mode: str = "off", ml_kind: str = "mlp",
                       tel_mode: str = "off", tnt_mode: str = "off",
                       fib_impl: str = "dense",
                       sess_impl: str = "gather",
                       sess_hash: str = "fwd",
                       overlay: str = "off"):
    """Compose one pipeline-step callable from the epoch's gates:
    classifier implementation (dense | mxu | bv), the policy-free
    local-classify skip, the two-tier fast-path dispatch, the session
    sweep stride, the ML-stage mode/kernel kind, and the telemetry
    mode (all trace-time static — part of the memo key, so two
    configs with different gates never share a program). The Dataplane
    builds (and jit-caches) its step variants exclusively through
    here, so every (impl, skip, tier, stride, ml, tel) combination
    shares ONE chain definition — a pipeline edit can't diverge a
    variant.

    Memoized: equal gates return the SAME function object, so jax's
    function-identity tracing/compilation caches are shared across
    every Dataplane (and test) in the process — exactly as the old
    module-level step functions were. A fresh closure per caller
    would recompile the whole chain per dataplane instance."""
    from vpp_tpu.ops.acl import acl_local_none

    if ml_mode not in ("off", "score", "enforce"):
        raise ValueError(f"unknown ml_mode {ml_mode!r}")
    if ml_kind not in ("mlp", "forest"):
        raise ValueError(f"unknown ml_kind {ml_kind!r}")
    if tel_mode not in ("off", "latency", "full"):
        raise ValueError(f"unknown tel_mode {tel_mode!r}")
    if tnt_mode not in ("off", "on"):
        raise ValueError(f"unknown tnt_mode {tnt_mode!r}")
    if sess_impl not in ("gather", "pallas"):
        raise ValueError(f"unknown sess_impl {sess_impl!r}")
    if sess_hash not in ("fwd", "sym"):
        raise ValueError(f"unknown sess_hash {sess_hash!r}")
    if overlay not in ("off", "vxlan"):
        raise ValueError(f"unknown overlay {overlay!r}")
    acl_global_fn, acl_local_fn = _classifier_fns(impl)
    fib_fn = _fib_fn(fib_impl)
    if skip_local:
        acl_local_fn = acl_local_none
    base = pipeline_step_auto if fast else pipeline_step

    # jax-ok: overlay is trace-time static — it picks the step's CALL
    # SIGNATURE (the overlay form takes the host-parsed inner/vni
    # sidecar as explicit jit arguments), not a tracer branch
    if overlay == "off":
        def step(tables: DataplaneTables, pkts: PacketVector,
                 now: jnp.ndarray) -> StepResult:
            return base(tables, pkts, now, acl_global_fn=acl_global_fn,
                        acl_local_fn=acl_local_fn,
                        sweep_stride=sweep_stride,
                        ml_mode=ml_mode, ml_kind=ml_kind,
                        tel_mode=tel_mode,
                        tnt_mode=tnt_mode, fib_fn=fib_fn,
                        sess_impl=sess_impl, sess_hash=sess_hash)
    else:
        def step(tables: DataplaneTables, pkts: PacketVector,
                 now: jnp.ndarray, ovl_inner: PacketVector,
                 ovl_vni: jnp.ndarray) -> StepResult:
            return base(tables, pkts, now, acl_global_fn=acl_global_fn,
                        acl_local_fn=acl_local_fn,
                        sweep_stride=sweep_stride,
                        ml_mode=ml_mode, ml_kind=ml_kind,
                        tel_mode=tel_mode,
                        tnt_mode=tnt_mode, fib_fn=fib_fn,
                        sess_impl=sess_impl, sess_hash=sess_hash,
                        overlay=overlay, ovl_inner=ovl_inner,
                        ovl_vni=ovl_vni)

    step.__name__ = "pipeline_step_{}{}{}{}{}{}{}{}{}{}".format(
        impl, "_nolocal" if skip_local else "", "_auto" if fast else "",
        "" if ml_mode == "off" else f"_ml{ml_mode}"
        + ("_forest" if ml_kind == "forest" else ""),
        "" if tel_mode == "off" else f"_tel{tel_mode}",
        "" if tnt_mode == "off" else "_tenancy",
        "" if fib_impl == "dense" else f"_fib{fib_impl}",
        "" if sess_impl == "gather" else f"_sess{sess_impl}",
        "" if sess_hash == "fwd" else f"_h{sess_hash}",
        "" if overlay == "off" else f"_o{overlay}",
    )
    return step


pipeline_step_jit = jax.jit(pipeline_step, donate_argnums=())
