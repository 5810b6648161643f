"""Multi-chip cluster step: N vswitch nodes on one device mesh.

The reference joins per-node vswitches with a VXLAN full-mesh (bridge
domain + BVI, plugins/contiv/node_events.go:184-250, host.go:211-331) and
shards pods across nodes via node-ID IPAM. Here each mesh position along
the ``node`` axis runs the full single-node pipeline over its own stacked
table shard, and inter-node traffic is exchanged in one ``all_to_all``
over ICI — the overlay *is* the interconnect, no encapsulation needed.
The node-global ACL table is additionally sharded along the ``rule`` axis
(tens of thousands of cluster-wide rules, the
tests/policy/perf/gen-policy.py regime), with cluster-wide first-match
recombined by a single ``pmin`` of encoded verdicts.

A cluster step therefore is: local pipeline pass (ip4 → sessions → NAT44
→ ACL → FIB) → pack packets with REMOTE disposition per destination node
→ ``all_to_all`` → delivery pipeline pass at the destination (rx on the
node's uplink, global ACL applies — same as VXLAN-decapped traffic
hitting the reference's uplink ACL). TTL is decremented once per pass,
matching the two vswitch hops a packet crosses in the reference.
"""

from __future__ import annotations

import functools
import threading
import time as _time
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vpp_tpu.ops.acl import (
    ENC_NO_MATCH,
    AclVerdict,
    acl_encode_shard,
    assemble_global_verdict,
)
from vpp_tpu.parallel.partition import (
    NODE_AXIS,
    RULE_AXIS,
    ShardCtx,
    agree_ml,
    bv_mesh_ok,
    select_fib_impl,
    select_impl,
    table_specs,
    validate_partitioning,
)
from vpp_tpu.pipeline.dataplane import Dataplane
from vpp_tpu.pipeline.graph import (
    SWEEP_STRIDE_DEFAULT,
    StepStats,
    _fib_fn,
    pipeline_step,
    pipeline_step_auto,
)
from vpp_tpu.pipeline.tables import (
    _UPLOAD_GROUPS,
    FIB_STATE_FIELDS,
    SESSION_FIELDS,
    TELEMETRY_FIELDS,
    TENANCY_STATE_FIELDS,
    DataplaneConfig,
    DataplaneTables,
    zero_fib_state,
    zero_sessions,
    zero_telemetry,
    zero_tenancy_state,
)
from vpp_tpu.pipeline.vector import (
    FLAG_VALID,
    Disposition,
    PacketVector,
    make_packet_vector,
)


@functools.lru_cache(maxsize=None)
def mesh_table_specs(bv_sharded: bool = True,
                     ml_sharded: bool = True) -> DataplaneTables:
    """The partition layer's spec tree, adjusted for THIS mesh's
    degraded axes: when the BV word axis can't shard (rule capacity not
    divisible by 32·shards — ``partition.bv_mesh_ok``) the glb_bv_*
    planes fall back to replicated (and the selection ladder never
    picks BV), and when the ML stage is off the placeholder-shaped
    glb_ml_* planes replicate (the stage is compiled out, the
    placeholders are never read). Both downgrades are observable
    (``show partitions`` prints the effective spec), never silent
    semantics changes — the session grids and dense/MXU rule rows have
    hard divisibility validation instead (``validate_partitioning``)."""
    specs = table_specs()._asdict()
    if not bv_sharded:
        for f in specs:
            if f.startswith("glb_bv_"):
                specs[f] = P(NODE_AXIS)
    if not ml_sharded:
        for f in specs:
            if f.startswith("glb_ml_"):
                specs[f] = P(NODE_AXIS)
    return DataplaneTables(**specs)


def mesh_table_shardings(mesh: Mesh, bv_sharded: bool = True,
                         ml_sharded: bool = True) -> DataplaneTables:
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        mesh_table_specs(bv_sharded, ml_sharded),
        is_leaf=lambda x: isinstance(x, P),
    )


class NodeTx(NamedTuple):
    """One node's egress view after a pass: header fields + where each
    packet went. ``node_id`` >= 0 marks packets handed to the fabric."""

    pkts: PacketVector
    disp: jnp.ndarray     # int32 Disposition
    tx_if: jnp.ndarray    # int32 egress interface (uplink for REMOTE, -1 dropped)
    node_id: jnp.ndarray  # int32 destination node, -1 local
    next_hop: jnp.ndarray  # uint32 VXLAN peer for EDGE traffic (0 = none)
    drop_cause: jnp.ndarray  # int32 DROP_* attribution (graph.py) — the
                             # host error path (ICMP generation) reads it


class ClusterStepResult(NamedTuple):
    local: NodeTx          # pass 1: traffic as seen at the ingress node [N, P]
    delivered: NodeTx      # pass 2: fabric traffic at its destination [N, N*B]
    tables: DataplaneTables  # node-stacked tables with updated sessions
    stats: StepStats       # per-node counters (both passes summed) [N, ...]
    fabric_overflow: jnp.ndarray  # int32 [N]: packets dropped because a
                                  # destination's slot budget was full
    fabric_sent: jnp.ndarray      # int32 [N]: packets actually handed to
                                  # the fabric (utilization numerator;
                                  # capacity = n_nodes * budget)
    fastpath_pass1: jnp.ndarray   # int32 [N]: 1 when the INGRESS pass
                                  # dispatched the classify-free fast
                                  # tier (stats.fastpath sums both
                                  # passes, and the empty-fabric pass 2
                                  # is vacuously fast — the pump's
                                  # "fast fabric step" telemetry needs
                                  # pass 1 alone; ISSUE 12)


def sharded_global_classify(tables: DataplaneTables, pkts: PacketVector) -> AclVerdict:
    """Global-ACL classify when the rule rows are sharded over RULE_AXIS.

    Each chip first-matches its shard, then one pmin of encoded verdicts
    (abs_idx<<1 | deny) yields the cluster-wide first match. Must run
    inside shard_map with the ``rule`` axis bound.
    """
    shard_rows = tables.glb_action.shape[0]
    base = lax.axis_index(RULE_AXIS).astype(jnp.int32) * shard_rows
    enc = acl_encode_shard(
        pkts,
        tables.glb_src_net, tables.glb_src_mask,
        tables.glb_dst_net, tables.glb_dst_mask,
        tables.glb_proto,
        tables.glb_sport_lo, tables.glb_sport_hi,
        tables.glb_dport_lo, tables.glb_dport_hi,
        tables.glb_action,
        base,
    )
    enc = lax.pmin(enc, RULE_AXIS)
    matched = enc != ENC_NO_MATCH
    return assemble_global_verdict(
        tables, pkts, matched, (enc & 1) == 0, enc >> 1
    )


def sharded_global_classify_mxu(
    tables: DataplaneTables, pkts: PacketVector
) -> AclVerdict:
    """Global-ACL classify on the MXU bit-plane kernel with the rule
    COLUMNS sharded over RULE_AXIS (sharding spec: parallel/mesh.py).

    Each chip matmuls the packet bit-planes against its coefficient
    column block and first-matches locally; the shard verdicts are
    encoded as (abs_rule_idx << 1 | deny) — the deny bit resolved from
    the column-aligned ``glb_mxu_act`` shard, since bit-plane columns
    and dense rule rows shard into different block boundaries when the
    column space is tile-padded (R' > R) — and one ``pmin`` over the
    rule axis yields the cluster-wide first match. Must run inside
    shard_map with the ``rule`` axis bound.

    This is the north-star kernel in the north-star regime: cluster-scale
    rule sets (the gen-policy.py 1000-CIDR x ports shape,
    /root/reference/tests/policy/perf/gen-policy.py:8-11) classified on
    the systolic array across every chip's shard at once (VERDICT r3
    Missing #2).
    """
    from vpp_tpu.ops.acl_mxu import ENC_MISS, mxu_classify_columns

    col = mxu_classify_columns(tables, pkts)
    shard_cols = tables.glb_mxu_coeff.shape[1]
    base = lax.axis_index(RULE_AXIS).astype(jnp.int32) * shard_cols
    hit = col != ENC_MISS
    safe = jnp.where(hit, col, 0)
    deny = tables.glb_mxu_act[safe] != 1
    enc = jnp.where(
        hit, ((base + col) << 1) | deny, jnp.int32(ENC_NO_MATCH)
    )
    enc = lax.pmin(enc, RULE_AXIS)
    matched = enc != ENC_NO_MATCH
    return assemble_global_verdict(
        tables, pkts, matched, (enc & 1) == 0, enc >> 1
    )


def sharded_global_classify_bv(
    tables: DataplaneTables, pkts: PacketVector
) -> AclVerdict:
    """Global-ACL classify on the BV interval-bitmap kernel with the
    rule-WORD axis sharded over RULE_AXIS (ISSUE 12 — the kernel the
    pre-partition mesh excluded wholesale).

    The boundary arrays and segment indices are replicated (a
    segment's bitmap row spans all rules, which is exactly why the
    ROW axis never sharded); what shards is the uint32 WORD axis the
    row packs the rules into: each chip gathers its word block, ANDs
    the five planes, and first-set-bits LOCALLY — yielding the lowest
    matching rule within its 32·W_shard-rule window — then one encoded
    ``pmin`` over the rule axis picks the cluster-wide first match
    (min by absolute rule index), exactly the dense/MXU recombination.
    The deny bit resolves from the shard's own ``glb_action`` row
    block: ``partition.bv_mesh_ok`` guarantees the word shard and the
    action-row shard cover the SAME absolute rule window
    (max_global_rules % 32·shards == 0). Must run inside shard_map
    with the ``rule`` axis bound.
    """
    from vpp_tpu.ops.acl_bv import bv_first_match

    shard_words = tables.glb_bv_src.shape[1]
    base = lax.axis_index(RULE_AXIS).astype(jnp.int32) * (shard_words * 32)
    matched, rule = bv_first_match(
        tables.glb_bv_bnd_src, tables.glb_bv_bnd_dst,
        tables.glb_bv_bnd_sport, tables.glb_bv_bnd_dport,
        tables.glb_bv_nbnd,
        tables.glb_bv_src, tables.glb_bv_dst,
        tables.glb_bv_sport, tables.glb_bv_dport, tables.glb_bv_proto,
        pkts,
    )
    # deny from the column-aligned local action rows (rule < 32·W_shard
    # == rows per action shard, by the bv_mesh_ok alignment guarantee)
    safe = jnp.clip(jnp.where(matched, rule, 0), 0,
                    tables.glb_action.shape[0] - 1)
    deny = tables.glb_action[safe] != 1
    enc = jnp.where(
        matched, ((base + rule) << 1) | deny, jnp.int32(ENC_NO_MATCH)
    )
    enc = lax.pmin(enc, RULE_AXIS)
    matched = enc != ENC_NO_MATCH
    return assemble_global_verdict(
        tables, pkts, matched, (enc & 1) == 0, enc >> 1
    )


# impl name -> the rule-sharded global classify of the cluster step
# (the mesh analog of graph._classifier_fns)
_SHARDED_GLOBAL_FNS = {
    "dense": sharded_global_classify,
    "mxu": sharded_global_classify_mxu,
    "bv": sharded_global_classify_bv,
}


def _pv_spec() -> PacketVector:
    return PacketVector(*([P(NODE_AXIS)] * len(PacketVector._fields)))


def make_cluster_step_wire(mesh: Mesh, budget: int = 0,
                           mxu: bool = False,
                           sweep_stride: int = SWEEP_STRIDE_DEFAULT,
                           **gates):
    """The cluster step for REAL wire traffic: headers AND payload
    bytes cross the fabric. Signature: (tables, pkts, payload, now,
    uplink_if) → (ClusterStepResult, delivered_payload), where
    ``payload`` is [N, P, snap] uint8 (each node's rx ring payload
    rows) and ``delivered_payload`` is [N, N·B, snap] — the packet
    BYTES of fabric-delivered traffic, aligned with
    ``result.delivered`` rows at the destination.

    This is the TPU-native answer to the question the VXLAN overlay
    answers in the reference: the full packet rides the interconnect.
    Headers travel as SoA columns, bodies as a uint8 block, both in
    the SAME all_to_all (one collective per direction per step); the
    destination's IO daemon rewrites headers into the delivered bytes
    and transmits (native/pkt_io.cpp pio_rewrite), exactly like
    locally-forwarded traffic. Payload bandwidth over ICI is
    B·snap/node/step — the deployment sizes ``snap`` to its MTU.
    """
    return make_cluster_step(mesh, budget=budget, mxu=mxu,
                             with_payload=True,
                             sweep_stride=sweep_stride, **gates)


@functools.lru_cache(maxsize=None)
def make_cluster_step(mesh: Mesh, budget: int = 0, mxu: bool = False,
                      with_payload: bool = False,
                      sweep_stride: int = SWEEP_STRIDE_DEFAULT,
                      impl: Optional[str] = None,
                      fast: bool = False,
                      ml_mode: str = "off", ml_kind: str = "mlp",
                      bv_sharded: bool = False,
                      ml_sharded: Optional[bool] = None,
                      fib: str = "dense"):
    """Build the jitted cluster step for ``mesh``.

    Signature: (tables, pkts, now, uplink_if) → ClusterStepResult, where
    ``tables`` is node-stacked (see ClusterDataplane.swap), ``pkts`` is
    [N, P] node-sharded, ``uplink_if`` is [N] (each node's uplink
    interface index, rx_if for fabric-delivered traffic).

    ``budget`` caps fabric slots per (src, dst) pair: remote packets are
    COMPACTED into ``budget`` slots per destination (position = running
    count), so the all_to_all payload is [N, budget] instead of a dense
    P-wide row per peer — O(N·B) not O(N·P) — and pass 2 runs over N·B
    packets. Overflow beyond the budget is dropped and counted
    (``fabric_overflow``), utilization is observable (``fabric_sent`` /
    N·B). 0 = P (dense layout, no compaction loss; fine at small N).
    VERDICT r1 Weak #6.

    ``impl`` picks the rule-sharded global classify ("dense" | "mxu" |
    "bv" — the partition layer's kernels; ``mxu=True`` is the legacy
    spelling of impl="mxu"); ``fast`` compiles the two-tier
    established-flow dispatch (SPMD-uniform predicate —
    pipeline_step_auto); ``ml_mode``/``ml_kind`` the per-packet ML
    stage on hidden/tree-sharded weight planes; ``bv_sharded`` whether
    the glb_bv_* planes ride word-sharded in_specs (partition.
    bv_mesh_ok — False keeps them replicated and impl must not be
    "bv"). All are trace-time static and part of the memo key: equal
    gates share ONE jitted program process-wide (the make_pipeline_step
    discipline — a fresh closure per ClusterDataplane instance would
    recompile the mesh program per test)."""
    n_nodes = mesh.shape[NODE_AXIS]
    rule_shards = mesh.shape[RULE_AXIS]
    if impl is None:
        impl = "mxu" if mxu else "dense"
    if impl == "bv" and not bv_sharded:
        raise ValueError(
            "impl='bv' requires word-sharded BV planes (bv_sharded)")
    global_fn = _SHARDED_GLOBAL_FNS[impl]
    # BV swaps the LOCAL classify too (graph._classifier_fns parity:
    # the local tables are replicated along the rule axis, so the
    # single-node BV local kernel runs unchanged inside shard_map)
    if impl == "bv":
        from vpp_tpu.ops.acl_bv import acl_classify_local_bv as local_fn
    else:
        from vpp_tpu.ops.acl import acl_classify_local as local_fn
    # ml_sharded is the PLACEMENT of the glb_ml_* planes (the cluster
    # shards them whenever its config enables the stage — even before
    # a model is staged and the selection still gates ml_mode off), so
    # the in_specs always match the arrays' actual sharding and no
    # step ever pays a silent reshard. Default follows ml_mode for
    # direct callers.
    if ml_sharded is None:
        ml_sharded = ml_mode != "off"
    shard = ShardCtx(RULE_AXIS, rule_shards)
    base_step = pipeline_step_auto if fast else pipeline_step
    # FIB rung (ISSUE 15 → the mesh flip): every fib_lpm_* plane is
    # registered REPLICATED along the rule axis in PARTITION_RULES and
    # the lookup is a pure gather, so the single-node LPM kernel runs
    # unchanged inside shard_map — same planes, same program on every
    # shard. The pallas rung stays standalone-only
    # (validate_partitioning rejects the explicit knob on a mesh).
    fib_fn = _fib_fn(fib)

    def node_step(t, p, now, uplink=None):
        return base_step(t, p, now, acl_global_fn=global_fn,
                         acl_local_fn=local_fn,
                         sweep_stride=sweep_stride,
                         ml_mode=ml_mode, ml_kind=ml_kind,
                         fib_fn=fib_fn, shard=shard)

    def body(tables, pkts, now, uplink_if, payload=None):
        t = jax.tree.map(lambda a: a[0], tables)
        p = jax.tree.map(lambda a: a[0], pkts)
        uplink = uplink_if[0]
        pay = payload[0] if payload is not None else None  # [P, S] u8
        n_pkts = p.src_ip.shape[0]
        B = budget if budget > 0 else n_pkts

        # Pass 1: the ingress node's full pipeline.
        res1 = node_step(t, p, now)

        # Fabric exchange: compact packets into per-destination budgeted
        # rows, swap rows across the node axis (each row rides a distinct
        # ICI lane — the reference's per-peer VXLAN tunnel, as one
        # collective).
        remote = res1.disp == int(Disposition.REMOTE)
        dests = jnp.arange(n_nodes, dtype=jnp.int32)
        dest_mask = remote[None, :] & (res1.node_id[None, :] == dests[:, None])
        # position of each packet within its destination row
        pos = jnp.cumsum(dest_mask.astype(jnp.int32), axis=1) - 1
        keep = dest_mask & (pos < B)
        overflow = jnp.sum((dest_mask & (pos >= B)).astype(jnp.int32))
        sent = jnp.sum(keep.astype(jnp.int32))
        # flat scatter target: dest*B + pos (out-of-range = dropped)
        idx = jnp.where(keep, dests[:, None] * B + pos, n_nodes * B)
        flat_idx = idx.reshape(-1)

        def pack(a):
            out = jnp.zeros((n_nodes * B,), a.dtype)
            src = jnp.broadcast_to(a[None, :], (n_nodes, n_pkts))
            out = out.at[flat_idx].set(src.reshape(-1), mode="drop")
            return out.reshape(n_nodes, B)

        rp = res1.pkts
        valid = jnp.zeros((n_nodes * B,), jnp.int32).at[flat_idx].set(
            FLAG_VALID, mode="drop"
        ).reshape(n_nodes, B)
        send = PacketVector(
            src_ip=pack(rp.src_ip), dst_ip=pack(rp.dst_ip),
            proto=pack(rp.proto), sport=pack(rp.sport), dport=pack(rp.dport),
            ttl=pack(rp.ttl), pkt_len=pack(rp.pkt_len), rx_if=pack(rp.rx_if),
            flags=valid,
        )
        recv = jax.tree.map(
            lambda a: lax.all_to_all(a, NODE_AXIS, 0, 0, tiled=True), send
        )
        flat = jax.tree.map(lambda a: a.reshape(-1), recv)
        deliv_pay = None
        if pay is not None:
            # packet BYTES take the same scatter + all_to_all as the
            # header columns: the full packet rides the interconnect
            snap_w = pay.shape[1]
            pay_out = jnp.zeros((n_nodes * B, snap_w), pay.dtype)
            pay_src = jnp.broadcast_to(
                pay[None], (n_nodes, n_pkts, snap_w)
            ).reshape(n_nodes * n_pkts, snap_w)
            pay_send = pay_out.at[flat_idx].set(
                pay_src, mode="drop"
            ).reshape(n_nodes, B, snap_w)
            deliv_pay = lax.all_to_all(
                pay_send, NODE_AXIS, 0, 0, tiled=True
            ).reshape(n_nodes * B, snap_w)
        # Fabric traffic enters through the node's uplink: the global ACL
        # applies, per-pod local tables do not (reference: VXLAN-decapped
        # traffic hits the uplink's ACL before ip4-lookup).
        flat = flat._replace(
            rx_if=jnp.broadcast_to(uplink, flat.rx_if.shape).astype(jnp.int32)
        )

        # Pass 2: delivery at the destination node.
        res2 = node_step(res1.tables, flat, now)

        stats = jax.tree.map(lambda a, b: a + b, res1.stats, res2.stats)
        out = ClusterStepResult(
            local=NodeTx(res1.pkts, res1.disp, res1.tx_if, res1.node_id,
                         res1.next_hop, res1.drop_cause),
            delivered=NodeTx(res2.pkts, res2.disp, res2.tx_if,
                             res2.node_id, res2.next_hop,
                             res2.drop_cause),
            tables=res2.tables,
            stats=stats,
            fabric_overflow=overflow,
            fabric_sent=sent,
            fastpath_pass1=res1.stats.fastpath,
        )
        if pay is not None:
            return jax.tree.map(lambda a: a[None], (out, deliv_pay))
        return jax.tree.map(lambda a: a[None], out)

    tx_spec = NodeTx(
        pkts=_pv_spec(), disp=P(NODE_AXIS), tx_if=P(NODE_AXIS),
        node_id=P(NODE_AXIS), next_hop=P(NODE_AXIS),
        drop_cause=P(NODE_AXIS),
    )
    t_specs = mesh_table_specs(bv_sharded, ml_sharded)
    out_specs = ClusterStepResult(
        local=tx_spec,
        delivered=tx_spec,
        tables=t_specs,
        stats=StepStats(*([P(NODE_AXIS)] * len(StepStats._fields))),
        fabric_overflow=P(NODE_AXIS),
        fabric_sent=P(NODE_AXIS),
        fastpath_pass1=P(NODE_AXIS),
    )
    if with_payload:
        def body_wire(tables, pkts, payload, now, uplink_if):
            return body(tables, pkts, now, uplink_if, payload=payload)

        in_specs = (t_specs, _pv_spec(), P(NODE_AXIS), P(),
                    P(NODE_AXIS))
        return jax.jit(jax.shard_map(
            body_wire, mesh=mesh, in_specs=in_specs,
            out_specs=(out_specs, P(NODE_AXIS)),
        ))
    in_specs = (t_specs, _pv_spec(), P(), P(NODE_AXIS))
    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


def cluster_step(mesh: Mesh):
    """Alias for make_cluster_step (public API name)."""
    return make_cluster_step(mesh)


class ClusterDataplane:
    """Host-side handle on an N-node cluster data plane over one mesh.

    Per-node configuration is staged through each node's single-node
    ``Dataplane`` handle (``.node(i)`` — same interface/table/FIB/NAT
    mutators the renderers drive); ``swap()`` stacks all builders and
    publishes one node-sharded table epoch, carrying live session state
    over exactly like the single-node epoch swap.
    """

    def __init__(self, mesh: Mesh, config: Optional[DataplaneConfig] = None):
        self.mesh = mesh
        # The node configs are NOT pinned dense anymore (ISSUE 12):
        # the partition-rule layer shards the BV word planes, the ML
        # hidden/tree planes and the session bucket grids along the
        # rule axis, so every single-chip classifier/fastpath/ML win
        # serves the mesh through the same selection ladder the
        # standalone Dataplane runs (docs/PARTITIONING.md).
        self.config = config or DataplaneConfig()
        self.n_nodes = mesh.shape[NODE_AXIS]
        rule_shards = mesh.shape[RULE_AXIS]
        self.rule_shards = rule_shards
        from vpp_tpu.ops.acl_mxu import mxu_rule_capacity

        for name, dim in (
            ("max_global_rules", self.config.max_global_rules),
            ("MXU rule capacity", mxu_rule_capacity(self.config.max_global_rules)),
        ):
            if dim % rule_shards:
                raise ValueError(
                    f"{name} {dim} not divisible by rule shards {rule_shards}"
                )
        # session/NAT bucket grids and (when the stage is on) the ML
        # hidden/tree axes must divide — fail FAST with a clear error
        validate_partitioning(self.config, rule_shards)
        # multi-tenant gateway mode (ISSUE 14) is not wired into the
        # cluster step yet: the mesh ops shard the tenant-sliced
        # BUCKET math bit-exactly (tests/test_tenancy.py 2-way
        # differential), but make_cluster_step compiles the in-step
        # token-bucket/accounting stage out. An isolation/enforcement
        # feature must never degrade silently (the explicit-bv-refusal
        # convention) — refuse loudly instead.
        if getattr(self.config, "tenancy", "off") != "off":
            raise ValueError(
                "dataplane.tenancy=on is not supported on the mesh "
                "yet: the cluster step would silently skip per-tenant "
                "rate limits and accounting — run tenancy on "
                "standalone dataplanes (docs/TENANCY.md)")
        # BV degrades instead: a rule capacity whose word axis can't
        # shard keeps the planes replicated and the ladder off BV —
        # unless the operator EXPLICITLY asked for bv, which deserves a
        # loud refusal, not a silent dense fallback
        self._bv_sharded = bv_mesh_ok(self.config, rule_shards)
        if (getattr(self.config, "classifier", "auto") == "bv"
                and rule_shards > 1 and not self._bv_sharded):
            raise ValueError(
                f"classifier=bv on a {rule_shards}-way rule-sharded mesh "
                f"requires max_global_rules ({self.config.max_global_rules}) "
                f"divisible by {32 * rule_shards} (32·shards) so the "
                "bitmap word shards align with the action-row shards")
        self._ml_sharded = getattr(self.config, "ml_stage", "off") != "off"
        self._lock = threading.RLock()
        self.nodes: List[Dataplane] = [
            Dataplane(self.config, materialize=False) for _ in range(self.n_nodes)
        ]
        for n in self.nodes:
            # Renderer/CNI commits on a node handle publish the whole
            # cluster epoch (the node's swap delegates here). All node
            # commits serialize on the CLUSTER lock — a single lock, so
            # concurrent per-node writers can't deadlock on each other
            # and a swap never reads a half-applied peer builder.
            n._swap_delegate = self.swap
            n.commit_lock = self._lock
        self.tables: Optional[DataplaneTables] = None
        self.epoch = 0
        # wall-clock session time base (matches Dataplane semantics)
        self._t0 = _time.monotonic()
        self._now = 0
        # cluster steps since the last expire_sessions (each step runs
        # the in-step session sweep twice — both pipeline passes)
        self._steps_since_expire = 0
        self._uplinks = None
        # the config's amortized-aging stride rides every cluster step
        # variant (trace-time static), same as the single-node path
        self._sweep_stride = int(
            getattr(self.config, "sess_sweep_stride",
                    SWEEP_STRIDE_DEFAULT))
        # Selection state, flipped at swap() exactly like the
        # single-node Dataplane._refresh_selection: the classifier
        # ladder (bv >= bv_min_rules > mxu >= mxu_threshold > dense,
        # honoring explicit knobs), the two-tier fastpath engagement
        # and the ML stage gates. One jitted program serves all nodes,
        # so every choice is cluster-wide; the jitted step variants
        # come from the MEMOIZED make_cluster_step factory, so equal
        # gates share one compile process-wide.
        self._impl = "dense"
        self._use_mxu = False          # legacy view (impl == "mxu")
        self._use_fast = False
        self._ml_mode = "off"
        self._ml_kind = "mlp"
        self._fib_impl = "dense"
        self.mxu_threshold = 512
        self.bv_min_rules = int(
            getattr(self.config, "classifier_bv_min_rules", 1024))
        self.fib_lpm_min_routes = int(
            getattr(self.config, "fib_lpm_min_routes", 256))
        # incremental per-shard upload groups (ISSUE 12 satellite): the
        # stacked+sharded device array of every clean upload group is
        # reused across swaps — only fields of groups some node's
        # builder actually dirtied (and, for glb_bv, only the planes
        # compile_bv actually REBUILT) re-ship. Mirrors
        # TableBuilder.to_device for the mesh.
        self._dev_cache = {}
        self.upload_stats = {"fields_shipped": 0, "fields_reused": 0}
        self._shardings = mesh_table_shardings(
            mesh, self._bv_sharded, self._ml_sharded)
        self._node_sharding = NamedSharding(mesh, P(NODE_AXIS))

    def node(self, i: int) -> Dataplane:
        return self.nodes[i]

    @property
    def classifier_impl(self) -> str:
        """The rule-sharded global classify the LIVE cluster epoch runs
        ("dense" | "mxu" | "bv") — `show partitions` / bench keys."""
        return self._impl

    @property
    def fastpath_selected(self) -> bool:
        return self._use_fast

    @property
    def fib_impl(self) -> str:
        """The FIB rung the LIVE cluster epoch runs ("dense" | "lpm")
        — the single-node ``Dataplane.fib_impl`` twin."""
        return self._fib_impl

    @property
    def ml_selected(self) -> str:
        return self._ml_mode

    def shard_sessions_resident(self) -> List[int]:
        """Live reflective sessions per rule shard (summed across
        nodes) — the ONE copy of the blocked-ownership layout math
        (shard s owns buckets [s·NB/S, (s+1)·NB/S) of every node);
        the collector gauge and ``show partitions`` both read this.
        Reduced ON device: only [shards] scalars cross the transport."""
        import jax.numpy as jnp

        with self._lock:
            tables = self.tables
        if tables is None:
            return [0] * self.rule_shards
        valid = tables.sess_valid  # [N, NB, W]
        per = valid.shape[1] // self.rule_shards
        # transfer-ok: device-reduced [rule_shards] counts — shards*8
        # bytes cross, the [N, NB, W] table never leaves the device
        resident = np.asarray(jnp.sum(
            valid.reshape(valid.shape[0], self.rule_shards, per,
                          valid.shape[2]),
            axis=(0, 2, 3)))
        return [int(v) for v in resident]

    def _refresh_selection(self) -> None:
        """Re-gate every cluster-wide compile-time choice against the
        staged node builders (the Dataplane._refresh_selection ladder,
        agreed across nodes because ONE jitted program serves them
        all). Called under the lock at every swap().

        * classifier: explicit knobs honored when compilable; ``auto``
          ladders BV >= bv_min_rules > MXU >= mxu_threshold > dense.
          BV additionally requires EVERY node's structure ok AND the
          mesh word-shard alignment (``_bv_sharded``).
        * fastpath: the knob and the min-rules gate against the
          LARGEST staged global table (the node that pays the most
          classify is the one the dispatch exists for).
        * ML: engages only when every node staged a model of the SAME
          kernel kind — the kind is trace-time static and
          cluster-wide; a partially-staged fleet keeps the stage off
          (models land per node through the "ml" upload group, so the
          next swap after the last node stages flips it on).
        """
        c = self.config
        mxu_ok = all(n.builder.mxu_enabled and n.builder.glb_mxu.ok
                     for n in self.nodes)
        bv_ok = self._bv_sharded and all(
            n.builder.bv_ok() for n in self.nodes)
        nmax = max(n.builder.glb_nrules for n in self.nodes)
        lmax = max(int(n.builder.acl_nrules.max()) for n in self.nodes)
        self._impl = select_impl(
            getattr(c, "classifier", "auto"), bv_ok, mxu_ok, nmax,
            self.bv_min_rules, self.mxu_threshold, local_nrules=lmax)
        self._use_mxu = self._impl == "mxu"
        self._use_fast = bool(getattr(c, "fastpath", True)) and \
            nmax >= int(getattr(c, "fastpath_min_rules", 0))
        self._ml_mode, self._ml_kind = agree_ml(
            getattr(c, "ml_stage", "off"),
            {int(getattr(n.builder, "ml_kind", 0))
             for n in self.nodes})
        # FIB ladder: lpm when EVERY node's staged table is eligible
        # and the largest node reaches the knee — the one shared rung
        # mapping (partition.select_fib_impl), applied to collective
        # bits exactly like the classifier. pallas_ok stays False on a
        # mesh (the fused rung doesn't shard — validate_partitioning).
        self._fib_impl = select_fib_impl(
            getattr(c, "fib_impl", "auto"),
            all(n.builder.lpm_ok() for n in self.nodes),
            max(n.builder.fib_route_count() for n in self.nodes),
            self.fib_lpm_min_routes, pallas_ok=False)

    def _get_step(self, with_payload: bool = False):
        """The jitted cluster step of the current selection (call
        under ``_lock``). The factory is memoized on (mesh, gates), so
        this is a dict hit after the first build of each variant."""
        return make_cluster_step(
            self.mesh, with_payload=with_payload,
            sweep_stride=self._sweep_stride,
            impl=self._impl, fast=self._use_fast,
            ml_mode=self._ml_mode, ml_kind=self._ml_kind,
            bv_sharded=self._bv_sharded, ml_sharded=self._ml_sharded,
            fib=self._fib_impl)

    def swap(self) -> int:
        """Stack every node's staged builder into one sharded table epoch.

        Each node's lock is held while its builder is read, so concurrent
        renderer mutations on other threads can't publish a torn epoch
        (the cluster analog of Dataplane.swap holding its lock)."""
        with self._lock:
            # Which fields this swap will actually re-ship (union of
            # every node's dirty upload groups + cache misses; within
            # glb_bv only the REBUILT dimension planes): computed
            # FIRST so the host copy below only touches those — with
            # the mesh no longer pinned dense the clean host arrays
            # include the ~100 MB/node BV structure, and memcpying it
            # on a session-only churn would negate the incremental
            # upload's host-side half.
            dirty_groups = set()
            bv_dirty_fields = set()
            fib_dirty_fields = set()
            for n in self.nodes:
                # settle lazy LPM staging BEFORE reading dirt: the
                # restage is what names the rebuilt length planes
                n.builder._restage_lpm()
                dirty_groups |= n.builder._dirty
                bv_dirty_fields |= n.builder._bv_dirty
                fib_dirty_fields |= n.builder._fib_dirty
            need = set()
            for group, fields in _UPLOAD_GROUPS.items():
                dirty = group in dirty_groups
                for k in fields:
                    if group == "glb_bv":
                        if (dirty and k in bv_dirty_fields) \
                                or k not in self._dev_cache:
                            need.add(k)
                    elif group == "fib":
                        # per-field granularity (the glb_bv pattern):
                        # a route flap on one node re-ships its touched
                        # length plane + the per-slot rows, never all
                        # 33 planes (ISSUE 15)
                        if (dirty and k in fib_dirty_fields) \
                                or k not in self._dev_cache:
                            need.add(k)
                    elif dirty or k not in self._dev_cache:
                        need.add(k)
            per_node = []
            guard = []
            for n in self.nodes:
                with n._lock:
                    arrs = n.builder.host_arrays()
                    per_node.append(
                        {k: np.copy(v) for k, v in arrs.items()
                         if k in need})
                    # guard inputs read (not copied) under the node
                    # lock; staging writers additionally hold the
                    # CLUSTER commit lock we already own, so these
                    # can't mutate before the device publish below
                    guard.append((arrs["fib_node_id"],
                                  arrs["fib_plen"]))
            # Misconfiguration guard: any node that fabric routes point at
            # must have an uplink, or its inbound traffic would arrive on
            # the reserved interface 0 and be silently dropped as bad-if.
            for i, (node_ids, plens) in enumerate(guard):
                targets = node_ids[plens >= 0]
                for t in np.unique(targets[targets >= 0]):
                    if self.nodes[int(t)].uplink_if is None:
                        raise ValueError(
                            f"node {i} routes to node {int(t)}, which has "
                            "no uplink interface (call add_uplink())"
                        )
            shardings = self._shardings._asdict()
            # Config fields upload INCREMENTALLY by group (the
            # TableBuilder.to_device discipline, lifted to the mesh):
            # a group no node's builder dirtied since the last swap
            # reuses its cached stacked+sharded device array — and
            # within glb_bv, only the dimension planes compile_bv
            # actually rebuilt re-ship, so a port-only policy churn
            # ships two word-sharded planes, not the whole structure.
            # SESSION state is carried over BY REFERENCE — the arrays
            # already live sharded on the mesh, and a device_put round
            # trip of a multi-hundred-MB table per epoch flip is
            # exactly the re-upload the set-associative rework
            # eliminates (docs/SESSIONS.md).
            dev = {}
            shipped = reused = 0
            for group, fields in _UPLOAD_GROUPS.items():
                for k in fields:
                    if k in need:
                        self._dev_cache[k] = jax.device_put(
                            np.stack([arrs[k] for arrs in per_node]),
                            shardings[k])
                        shipped += 1
                    else:
                        reused += 1
                    dev[k] = self._dev_cache[k]
            self.upload_stats["fields_shipped"] = shipped
            self.upload_stats["fields_reused"] = reused
            # builders' dirt cleared only now — everything above
            # succeeded, so the cache really holds the staged state
            # (cluster nodes never call to_device themselves; this
            # swap IS their upload path)
            for n in self.nodes:
                n.builder._dirty.clear()
                n.builder._bv_dirty.clear()
                n.builder._fib_dirty.clear()
            if self.tables is not None:
                sess = {f: getattr(self.tables, f) for f in SESSION_FIELDS}
                tel = {f: getattr(self.tables, f)
                       for f in TELEMETRY_FIELDS}
                tnt = {f: getattr(self.tables, f)
                       for f in TENANCY_STATE_FIELDS}
                fib_st = {f: getattr(self.tables, f)
                          for f in FIB_STATE_FIELDS}
            else:
                zs = zero_sessions(self.config, leading=(self.n_nodes,))
                sess = {
                    f: jax.device_put(v, shardings[f])
                    for f, v in zs.items()
                }
                # telemetry planes (ops/telemetry.py): node-stacked
                # placeholders, replicated-by-design along the rule
                # axis (partition.py) — the cluster step keeps the
                # telemetry knob off, so these are never read
                zt = zero_telemetry(self.config, leading=(self.n_nodes,))
                tel = {
                    f: jax.device_put(v, shardings[f])
                    for f, v in zt.items()
                }
                # tenancy state planes (vpp_tpu/tenancy/): cluster
                # node configs keep the tenancy knob off too —
                # placeholder shapes, replicated-by-design, never read
                ztn = zero_tenancy_state(self.config,
                                         leading=(self.n_nodes,))
                tnt = {
                    f: jax.device_put(v, shardings[f])
                    for f, v in ztn.items()
                }
                # per-member ECMP accounting plane (ISSUE 15):
                # node-stacked zeros, replicated along the rule axis
                zf = zero_fib_state(self.config,
                                    leading=(self.n_nodes,))
                fib_st = {
                    f: jax.device_put(v, shardings[f])
                    for f, v in zf.items()
                }
            self._refresh_selection()
            self.tables = DataplaneTables(**dev, **sess, **tel, **tnt,
                                          **fib_st)
            self._uplinks = jax.device_put(
                np.array(
                    [
                        n.uplink_if if n.uplink_if is not None else 0
                        for n in self.nodes
                    ],
                    np.int32,
                ),
                self._node_sharding,
            )
            self.epoch += 1
            # per-node api-trace: drained only AFTER the guard and the
            # device publish succeed — draining earlier would lose the
            # ops from the journal when the guard raises (the staged
            # builder state survives for the next swap; a drained
            # recording would not). Ops journal under the CLUSTER epoch
            # so a node's replayed history lines up with the epochs the
            # mesh actually published. Writers hold the cluster commit
            # lock across stage+swap, so nothing new staged between the
            # array copy above and this drain.
            for n in self.nodes:
                if n.journal is not None:
                    with n._lock:
                        txn = n.builder.drain_recording()
                    if txn is not None:
                        n.journal.record(txn, self.epoch)
            return self.epoch

    def make_frames(self, per_node_packets: Sequence[list], n: int = 256) -> PacketVector:
        """Stack per-node packet lists into one [N, P] sharded vector."""
        assert len(per_node_packets) == self.n_nodes
        vecs = [make_packet_vector(pkts, n=n) for pkts in per_node_packets]
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *vecs)
        return jax.device_put(stacked, self._node_sharding)

    def clock_ticks(self) -> int:
        """Monotonic wall-clock ticks since this cluster started
        (Dataplane.clock_ticks analog; TICKS_PER_SEC shared)."""
        return int(
            (_time.monotonic() - self._t0) * Dataplane.TICKS_PER_SEC
        )

    def advance_clock(self, seconds: float) -> None:
        """Shift the time base forward (tests simulate idle periods
        without sleeping — the Dataplane.advance_clock analog)."""
        self._t0 -= seconds

    def expire_sessions(self, max_age: Optional[int] = None,
                        lazy: bool = False) -> int:
        """Host-driven bulk aging of the node-stacked session tables
        (reflective + NAT), the Dataplane.expire_sessions analog: the
        in-kernel timeout already makes expired entries invisible and
        insert-time eviction reclaims their slots lazily — this frees
        slots in bulk so occupancy gauges reflect reality. Returns the
        number of sessions expired across all nodes.

        ``lazy=True`` (the maintenance-loop form) skips the bulk pass
        when the in-step amortized sweep has covered the whole table
        since the last call (each cluster step sweeps BOTH pipeline
        passes) — same contract as Dataplane.expire_sessions."""
        from vpp_tpu.ops.session import session_expire

        if max_age is None:
            max_age = self.config.sess_max_age
        with self._lock:
            if self.tables is None:
                return 0
            # lazy is sound only for the CONFIGURED timeout: the
            # in-step sweep enforces tables.sess_max_age, so a shorter
            # caller-supplied max_age must still run the bulk pass
            if lazy and max_age == self.config.sess_max_age:
                steps = self._steps_since_expire
                self._steps_since_expire = 0
                from vpp_tpu.ops.session import sweep_covered

                # node-stacked [N, n_buckets, W]; each cluster step
                # sweeps BOTH pipeline passes
                if sweep_covered(steps, self._sweep_stride, self.tables,
                                 bucket_axis=1, passes=2):
                    return 0
            self._now = max(self._now, self.clock_ticks())
            now = self._now
            before = self.tables
        # dispatch + the blocking count OUTSIDE the lock: this runs on
        # the maintenance cadence against live traffic, and holding the
        # lock across a device round trip would stall every concurrent
        # step dispatch (periodic p99 spikes)
        after = session_expire(before, now, max_age)
        # transfer-ok: device-reduced scalar (expired-slot count)
        expired = int(
            jnp.sum(before.sess_valid - after.sess_valid)
            + jnp.sum(before.natsess_valid - after.natsess_valid)
        )
        with self._lock:
            # publish ONLY when something expired (a no-op replacement
            # would still invalidate the `tables is self.tables` guard
            # of an in-flight step and discard its session inserts) and
            # only if no step published newer tables while we computed
            if expired and before is self.tables:
                self.tables = after
        return expired

    def step(self, pkts: PacketVector, now: Optional[int] = None) -> ClusterStepResult:
        with self._lock:
            if self.tables is None:
                self.swap()
            if now is None:
                self._now = max(self._now, self.clock_ticks())
                now = self._now
            tables, uplinks = self.tables, self._uplinks
            step = self._get_step()
            self._steps_since_expire += 1
        result = step(tables, pkts, jnp.int32(now), uplinks)
        with self._lock:
            if tables is self.tables:
                self.tables = result.tables
        return result

    def step_wire(self, pkts: PacketVector, payload,
                  now: Optional[int] = None):
        """Wire-traffic cluster step: ``payload`` is [N, P, snap] uint8
        (each node's rx ring payload rows); returns
        (ClusterStepResult, delivered_payload [N, N·B, snap]) — the
        fabric carries headers AND bytes (make_cluster_step_wire)."""
        with self._lock:
            if self.tables is None:
                self.swap()
            if now is None:
                self._now = max(self._now, self.clock_ticks())
                now = self._now
            step = self._get_step(with_payload=True)
            tables, uplinks = self.tables, self._uplinks
            self._steps_since_expire += 1
        result, deliv_pay = step(
            tables, pkts, jnp.asarray(payload), jnp.int32(now), uplinks
        )
        with self._lock:
            if tables is self.tables:
                self.tables = result.tables
        return result, deliv_pay

    def adopt_sessions(self, sessions) -> int:
        """Publish RESTORED session state (a ``{field: node-stacked
        host array}`` mapping of SESSION_FIELDS — the cluster
        snapshot-restore path, pipeline/snapshot.py) as a new epoch:
        the arrays upload onto their bucket-sharded mesh placement and
        established flows come back warm fleet-wide. Shapes must match
        the mesh geometry — the snapshot loader already refused a
        mismatch, so a bad shape here raises."""
        from vpp_tpu.pipeline.tables import session_shapes

        shapes = session_shapes(self.config)
        with self._lock:
            if self.tables is None:
                self.swap()
            missing = set(SESSION_FIELDS) - set(sessions)
            if missing:
                raise ValueError(
                    f"restored session state missing fields: "
                    f"{sorted(missing)}")
            dev = {}
            for f, dt in SESSION_FIELDS.items():
                want = (self.n_nodes,) + shapes[f]
                arr = np.asarray(sessions[f], dt)
                if arr.shape != want:
                    raise ValueError(
                        f"restored session field {f!r} shape "
                        f"{arr.shape} != mesh geometry {want}")
                dev[f] = jax.device_put(
                    arr, getattr(self._shardings, f))
            self.tables = self.tables._replace(**dev)
            self.epoch += 1
            return self.epoch
