"""Device-resident table state + host-side table compiler.

All data-plane configuration (ACL rule tables, FIB, NAT mappings, session
table, interface attributes) lives in one immutable pytree of device
arrays, ``DataplaneTables``. A renderer commit builds a *new* pytree on
the host (numpy) and swaps it in — the functional-JAX analog of VPP's
double-buffered table swap: the jitted pipeline step simply takes the
tables as an argument, so an epoch flip is one reference assignment and
in-flight vectors keep their epoch's tables.

Reference analogs: VPP ACL-plugin rule tables, ip4 FIB, NAT44 static
mappings (external C, configured via vendored vpp-agent models — see
SURVEY.md §2.3).
"""

from __future__ import annotations

import enum
import functools
import ipaddress
import logging
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from vpp_tpu.ir.rule import ANY_PORT, ContivRule
from vpp_tpu.pipeline.config import (  # noqa: F401 — re-exported
    DataplaneConfig,
    _is_pow2,
    validate_dataplane_config,
)
from vpp_tpu.pipeline.vector import Disposition

log = logging.getLogger("vpp_tpu.tables")


class InterfaceType(enum.IntEnum):
    NONE = 0
    POD = 1      # pod-facing interface (VPP analog: TAP/veth+af_packet)
    UPLINK = 2   # node uplink toward other nodes / cluster edge
    HOST = 3     # host-stack interface (VPP analog: tap0 to the host)


class DataplaneTables(NamedTuple):
    """The device table pytree. All arrays live in HBM; see module doc."""

    # --- ACL local tables, padded [T, R] ---
    acl_src_net: jnp.ndarray    # uint32, pre-masked network address
    acl_src_mask: jnp.ndarray   # uint32
    acl_dst_net: jnp.ndarray    # uint32
    acl_dst_mask: jnp.ndarray   # uint32
    acl_proto: jnp.ndarray      # int32 IANA proto, -1 = any, -2 = padding
    acl_sport_lo: jnp.ndarray   # int32 (padding rows: lo=1, hi=0)
    acl_sport_hi: jnp.ndarray   # int32
    acl_dport_lo: jnp.ndarray   # int32
    acl_dport_hi: jnp.ndarray   # int32
    acl_action: jnp.ndarray     # int32: 0 deny, 1 permit, -1 padding
    acl_nrules: jnp.ndarray     # int32 [T]
    # Interval-bitmap (BV) form of the local tables (ops/acl_bv.py);
    # minimal placeholder shapes when the classifier knob disables BV
    # (bv_capacity(enabled=False)) — shapes stay epoch-invariant.
    acl_bv_bnd_src: jnp.ndarray    # uint32 [T, I]
    acl_bv_bnd_dst: jnp.ndarray    # uint32 [T, I]
    acl_bv_bnd_sport: jnp.ndarray  # int32 [T, I]
    acl_bv_bnd_dport: jnp.ndarray  # int32 [T, I]
    acl_bv_nbnd: jnp.ndarray       # int32 [T, 4] live boundary counts
    acl_bv_src: jnp.ndarray        # uint32 [T, I, W] segment bitmaps
    acl_bv_dst: jnp.ndarray        # uint32 [T, I, W]
    acl_bv_sport: jnp.ndarray      # uint32 [T, I, W]
    acl_bv_dport: jnp.ndarray      # uint32 [T, I, W]
    acl_bv_proto: jnp.ndarray      # uint32 [T, PR, W] direct proto plane

    # --- global ACL table, padded [G] ---
    glb_src_net: jnp.ndarray
    glb_src_mask: jnp.ndarray
    glb_dst_net: jnp.ndarray
    glb_dst_mask: jnp.ndarray
    glb_proto: jnp.ndarray
    glb_sport_lo: jnp.ndarray
    glb_sport_hi: jnp.ndarray
    glb_dport_lo: jnp.ndarray
    glb_dport_hi: jnp.ndarray
    glb_action: jnp.ndarray
    glb_nrules: jnp.ndarray     # int32 scalar
    # Bit-plane form of the global table for the MXU classify kernel
    # (vpp_tpu.ops.acl_mxu); float32 {-1,0,1} coeffs, cast to bf16 at use.
    glb_mxu_coeff: jnp.ndarray  # float32 [PLANES, R']
    glb_mxu_k: jnp.ndarray      # float32 [R']
    glb_mxu_act: jnp.ndarray    # int32 [R'] action per bit-plane COLUMN
                                # (-1 padding) — column space can be wider
                                # than rule-row space (R' >= R), so the
                                # rule-sharded MXU classify must resolve
                                # the deny bit here, not via glb_action
    # Interval-bitmap (BV) form of the global table (ops/acl_bv.py);
    # its own upload group ("glb_bv"), re-uploaded per-dimension-plane
    # so a port-only policy churn doesn't re-ship the address bitmaps.
    # On the mesh the bitmap planes shard along the rule-WORD axis
    # (boundaries replicated — a segment's row spans ALL rules, but
    # packs them into words): vpp_tpu/parallel/partition.py,
    # docs/CLASSIFIER.md.
    glb_bv_bnd_src: jnp.ndarray    # uint32 [I]
    glb_bv_bnd_dst: jnp.ndarray    # uint32 [I]
    glb_bv_bnd_sport: jnp.ndarray  # int32 [I]
    glb_bv_bnd_dport: jnp.ndarray  # int32 [I]
    glb_bv_nbnd: jnp.ndarray       # int32 [4]
    glb_bv_src: jnp.ndarray        # uint32 [I, W]
    glb_bv_dst: jnp.ndarray        # uint32 [I, W]
    glb_bv_sport: jnp.ndarray      # uint32 [I, W]
    glb_bv_dport: jnp.ndarray      # uint32 [I, W]
    glb_bv_proto: jnp.ndarray      # uint32 [PR, W]

    # --- per-packet ML model (ops/mlscore.py; upload group "ml") ---
    # Shipped through set_ml_model exactly like ACL rules ship through
    # set_global_table: its OWN upload group, so policy churn never
    # re-ships the model and a model swap never re-ships the rules.
    # Minimal placeholder shapes when ml_stage is "off"
    # (ml_capacity(config)); biases are zero-point FOLDED (int8
    # features are centered x-128 — _fold_ml below).
    glb_ml_w1: jnp.ndarray       # int8 [F, H] layer-1 weights
    glb_ml_b1: jnp.ndarray       # int32 [H] layer-1 bias (folded)
    glb_ml_s1: jnp.ndarray       # int32 scalar: requant right shift
    glb_ml_w2: jnp.ndarray       # int8 [H] output weights
    glb_ml_b2: jnp.ndarray       # int32 scalar: output bias (folded)
    glb_ml_f_feat: jnp.ndarray   # int32 [T, D] forest feature index
    glb_ml_f_thresh: jnp.ndarray  # int32 [T, D] forest thresholds
    glb_ml_f_leaf: jnp.ndarray   # int32 [T, 2^D] forest leaf votes
    glb_ml_thresh: jnp.ndarray   # int32 scalar: score > t => flagged
    glb_ml_action: jnp.ndarray   # int32 scalar: ML_ACTION_* policy
    glb_ml_rl_shift: jnp.ndarray  # int32 scalar: ratelimit admit shift
    glb_ml_version: jnp.ndarray  # int32 scalar: staged model version

    # --- interfaces [I] ---
    if_type: jnp.ndarray        # int32 InterfaceType
    if_local_table: jnp.ndarray  # int32 local ACL table slot, -1 = none
    if_apply_global: jnp.ndarray  # int32 bool: global table applies here

    # --- FIB [F] ---
    fib_prefix: jnp.ndarray     # uint32 pre-masked
    fib_mask: jnp.ndarray       # uint32
    fib_plen: jnp.ndarray       # int32, -1 = empty slot
    fib_tx_if: jnp.ndarray      # int32
    fib_disp: jnp.ndarray       # int32 Disposition
    fib_next_hop: jnp.ndarray   # uint32 (peer/VXLAN dst IP, else 0)
    fib_node_id: jnp.ndarray    # int32 remote node index (ICI), -1 local
    fib_snat: jnp.ndarray       # int32 bool: cluster-egress route — SNAT
                                # applies (reference: configurator_impl.go
                                # :258-264 SNAT pool for external traffic)
    fib_grp: jnp.ndarray        # int32 [F] ECMP next-hop group of the
                                # route, -1 = unicast (the scalar
                                # next_hop/tx_if/node_id columns above)

    # --- LPM per-length prefix planes (ops/lpm.py; ISSUE 15) --------
    # One [2, N_L] uint32 plane per prefix length: row 0 the sorted
    # masked prefixes (pad 0xFFFFFFFF), row 1 the owning FIB slot.
    # SEPARATE fields deliberately — a BGP flap re-ships only the
    # touched length's plane; the others keep device-array identity
    # (the glb_bv per-dimension-plane discipline). Capacities are
    # config-static (fib_lpm_plen_caps; 0 = zero-width plane, skipped
    # at trace time). Replicated along the mesh rule axis
    # (parallel/partition.py).
    fib_lpm_p0: jnp.ndarray
    fib_lpm_p1: jnp.ndarray
    fib_lpm_p2: jnp.ndarray
    fib_lpm_p3: jnp.ndarray
    fib_lpm_p4: jnp.ndarray
    fib_lpm_p5: jnp.ndarray
    fib_lpm_p6: jnp.ndarray
    fib_lpm_p7: jnp.ndarray
    fib_lpm_p8: jnp.ndarray
    fib_lpm_p9: jnp.ndarray
    fib_lpm_p10: jnp.ndarray
    fib_lpm_p11: jnp.ndarray
    fib_lpm_p12: jnp.ndarray
    fib_lpm_p13: jnp.ndarray
    fib_lpm_p14: jnp.ndarray
    fib_lpm_p15: jnp.ndarray
    fib_lpm_p16: jnp.ndarray
    fib_lpm_p17: jnp.ndarray
    fib_lpm_p18: jnp.ndarray
    fib_lpm_p19: jnp.ndarray
    fib_lpm_p20: jnp.ndarray
    fib_lpm_p21: jnp.ndarray
    fib_lpm_p22: jnp.ndarray
    fib_lpm_p23: jnp.ndarray
    fib_lpm_p24: jnp.ndarray
    fib_lpm_p25: jnp.ndarray
    fib_lpm_p26: jnp.ndarray
    fib_lpm_p27: jnp.ndarray
    fib_lpm_p28: jnp.ndarray
    fib_lpm_p29: jnp.ndarray
    fib_lpm_p30: jnp.ndarray
    fib_lpm_p31: jnp.ndarray
    fib_lpm_p32: jnp.ndarray
    fib_lpm_cnt: jnp.ndarray    # int32 [33] live (deduped) entries per
                                # length plane, clipped to each cap
    fib_lpm_hint: jnp.ndarray   # int32 [H] concatenated per-length
                                # stride hint tables (ops/lpm.py
                                # lpm_hint_layout — offsets are
                                # config-static, derived from the caps)

    # --- ECMP next-hop group tables (ops/fib.py; ISSUE 15) ----------
    # [G, W] member tables, member picked by the session flow hash
    # (way = mix & (W-1)); fib_grp_n counts DISTINCT members (0 =
    # unconfigured group — routes referencing it fail closed).
    fib_grp_nh: jnp.ndarray     # uint32 [G, W] member next-hop IP
    fib_grp_tx_if: jnp.ndarray  # int32 [G, W]
    fib_grp_node: jnp.ndarray   # int32 [G, W]
    fib_grp_n: jnp.ndarray      # int32 [G] distinct member count
    # per-member forwarded-packet accounting (graph._finish_step
    # scatter-add; the vpp_tpu_fib_ecmp_packets family) — STATE,
    # carried by reference across swaps like the telemetry planes
    fib_ecmp_c: jnp.ndarray     # int32 [G, W]

    # --- reflective sessions (W-way set-associative hash) [NB, W] ---
    # The way count W is carried IN THE SHAPE (ops/session.py): one
    # bucket-row gather fetches a flow's whole associativity set.
    sess_src: jnp.ndarray       # uint32 [NB, W]
    sess_dst: jnp.ndarray       # uint32 [NB, W]
    sess_ports: jnp.ndarray     # uint32 [NB, W] (sport<<16 | dport)
    sess_proto: jnp.ndarray     # int32 [NB, W]
    sess_valid: jnp.ndarray     # int32 bool [NB, W]
    sess_time: jnp.ndarray      # int32 [NB, W] last-hit tick (aging)
    sess_max_age: jnp.ndarray   # int32 scalar: idle timeout in ticks

    # --- NAT44 DNAT mappings [M] + backends [B] ---
    nat_ext_ip: jnp.ndarray     # uint32 service VIP / node IP
    nat_ext_port: jnp.ndarray   # int32
    nat_proto: jnp.ndarray      # int32
    nat_boff: jnp.ndarray       # int32 offset into backend arrays
    nat_bcnt: jnp.ndarray       # int32 backend count (0 = empty slot)
    nat_total_w: jnp.ndarray    # int32 total backend weight
    nat_self_snat: jnp.ndarray  # int32 bool [M]: DNAT'd flows of this
                                # mapping are also SNAT'd (nodeport case:
                                # the reply must return via this node)
    natb_ip: jnp.ndarray        # uint32 [B]
    natb_port: jnp.ndarray      # int32 [B]
    natb_cumw: jnp.ndarray      # int32 [B] cumulative weight within mapping
    nat_snat_ip: jnp.ndarray    # uint32 scalar: SNAT address (node IP)

    # --- NAT44 session table (reverse translation state) [NNB, W] ---
    # key: the flow as the *reply* will present it,
    # (reply_src_ip, reply_dst_ip, reply_sport<<16|reply_dport, proto)
    natsess_a: jnp.ndarray          # uint32
    natsess_b: jnp.ndarray          # uint32
    natsess_ports: jnp.ndarray      # uint32
    natsess_proto: jnp.ndarray      # int32
    natsess_valid: jnp.ndarray      # int32
    natsess_time: jnp.ndarray       # int32
    natsess_orig_ip: jnp.ndarray    # uint32 original dst (service VIP)
    natsess_orig_port: jnp.ndarray  # int32 original dst port
    natsess_src_ip: jnp.ndarray     # uint32 original src (pre-SNAT pod IP)
    natsess_sport: jnp.ndarray      # int32 original src port
    natsess_kind: jnp.ndarray       # int32 bitmask: 1=DNAT'd, 2=SNAT'd

    # --- amortized aging cursors (ops/session.py session_sweep) ---
    # next bucket each in-step sweep starts from; int32 scalars that
    # ride the session-state carry-over so a swap never resets aging
    sess_sweep_cursor: jnp.ndarray
    natsess_sweep_cursor: jnp.ndarray

    # --- device-resident telemetry plane (ops/telemetry.py; ISSUE 11) --
    # Carried across epoch swaps by reference like the session state
    # (TELEMETRY_FIELDS below); minimal placeholder shapes when the
    # ``telemetry`` knob is off (tel_capacity — the ml/BV gating
    # pattern, the placeholders are never read by an off-state step).
    tel_lat_hist: jnp.ndarray   # int32 [NB] log2 µs wire-latency bins
    tel_sketch: jnp.ndarray    # int32 [d, w] count-min flow sketch
    tel_sketched: jnp.ndarray  # int32 scalar: packets folded in
    tel_top_key: jnp.ndarray   # uint32 [K] top-K candidate flow hash
    tel_top_src: jnp.ndarray   # uint32 [K] candidate src ip
    tel_top_dst: jnp.ndarray   # uint32 [K] candidate dst ip
    tel_top_ports: jnp.ndarray  # uint32 [K] sport<<16 | dport
    tel_top_cnt: jnp.ndarray   # int32 [K] estimated packet count

    # --- multi-tenant gateway mode (ISSUE 14; vpp_tpu/tenancy/) -----
    # Config half ("tenant" upload group — ships independently of
    # rules/model, so tenant churn re-ships a few hundred bytes and
    # rule/model churn re-ships zero tenant state). Placeholder [1]
    # shapes when the ``tenancy`` knob is off (tnt_capacity).
    tnt_pfx_net: jnp.ndarray    # uint32 [S] pre-masked prefix network
    tnt_pfx_mask: jnp.ndarray   # uint32 [S]
    tnt_pfx_id: jnp.ndarray     # int32 [S] tenant id, -1 = empty slot
    tnt_rate: jnp.ndarray       # int32 [T] bucket tokens/tick (0 = no
                                # limit; bounded 2^16 — int32 refill)
    tnt_burst: jnp.ndarray      # int32 [T] bucket capacity
    tnt_sess_base: jnp.ndarray  # int32 [T] first session bucket of the
                                # tenant's slice (GLOBAL bucket units)
    tnt_sess_mask: jnp.ndarray  # int32 [T] slice bucket mask (nbk-1;
                                # unsliced tenants carry the full-table
                                # mask — base 0)
    tnt_nat_base: jnp.ndarray   # int32 [T] NAT-session slice base
    tnt_nat_mask: jnp.ndarray   # int32 [T] NAT-session slice mask
    # per-tenant ML policy vectors (tenancy/sched.py ML_MODE_CODES:
    # 0 inherit | 1 off | 2 score | 3 enforce; thresh INT32_MIN =
    # inherit the model's global flag threshold). Deliberately in the
    # "tenant" group, NOT "ml": flipping a tenant's threshold/mode
    # never re-ships the weight planes (ISSUE 14 satellite).
    glb_ml_tnt_mode: jnp.ndarray    # int32 [T]
    glb_ml_tnt_thresh: jnp.ndarray  # int32 [T]
    # Direct VNI → tenant map (ISSUE 19 satellite: the overlay decap
    # stage derives the tenant from the VALIDATED VNI on-device, so
    # tunneled traffic no longer depends on inner-address prefixes).
    # tnt_vni[t] is tenant t's VNI (-1 = none); a decapped VNI that
    # maps to no tenant FAILS CLOSED (DROP_OVERLAY). Tenancy-off
    # placeholder [1] carries DEFAULT_VNI so the single-tenant overlay
    # admits VNI 10 and nothing else.
    tnt_vni: jnp.ndarray        # int32 [T]
    # State half (TENANCY_STATE_FIELDS — carried by reference across
    # swaps like the sweep cursors; the persistent ring threads them
    # window-to-window): token-bucket level + last-refill tick, and
    # the per-tenant accounting planes `show tenants` /
    # vpp_tpu_tenant_* read as host scalars.
    tnt_tokens: jnp.ndarray     # int32 [T] current bucket level
    tnt_tok_time: jnp.ndarray   # int32 [T] last refill tick
    tnt_rx_c: jnp.ndarray       # int32 [T] packets received
    tnt_tx_c: jnp.ndarray       # int32 [T] packets forwarded (goodput)
    tnt_rl_c: jnp.ndarray       # int32 [T] rate-limit (tenant_quota)
                                # drops
    tnt_qf_c: jnp.ndarray       # int32 [T] session-slice insert
                                # failures attributed to the tenant

    # --- VXLAN overlay config (ops/vxlan.py; ISSUE 19) --------------
    # The node's local VTEP address; rides the tiny "config" upload
    # group (one scalar — a VTEP move ships bytes, not planes). 0 =
    # unset: decap then admits any VTEP-addressed UDP/4789 frame (the
    # single-node test harness), encap still stamps it as outer src.
    ovl_vtep_ip: jnp.ndarray    # uint32 scalar

    # --- service NAT44 LB planes (ops/nat44.py svc path; ISSUE 19) --
    # VIP rows sorted by (ip, port, proto) — the --tables invariant —
    # with padding rows inert via svc_bk_n == 0 (a row with no staged
    # backend set must NEVER serve: the half-applied-churn guard).
    # Backend columns are WAY tables, member picked by the session
    # flow hash (way = mix & (B-1)) with sticky weighted fill
    # (set_service — the set_nh_group discipline), so backend churn
    # only remaps the ways it must. Their OWN "svc" upload group: a
    # rolling backend replacement ships a few-KB scatter blob and
    # zero ACL/ML/FIB bytes (_upload_svc).
    svc_vip_ip: jnp.ndarray     # uint32 [V] service VIP
    svc_vip_port: jnp.ndarray   # int32 [V] service port (exact match)
    svc_vip_proto: jnp.ndarray  # int32 [V] IANA proto
    svc_vip_snat: jnp.ndarray   # int32 bool [V]: nodeport-style —
                                # DNAT'd flows also SNAT (reply must
                                # return via this node)
    svc_bk_n: jnp.ndarray       # int32 [V] distinct backends (0 =
                                # empty/padding row, never serves)
    svc_bk_ip: jnp.ndarray      # uint32 [V, B] per-way backend IP
    svc_bk_port: jnp.ndarray    # int32 [V, B] per-way backend port


def _mask_of(plen: int, bits: int = 32) -> int:
    return ((1 << bits) - 1) ^ ((1 << (bits - plen)) - 1) if plen else 0


# Session-state fields of DataplaneTables (reflective ACL + NAT session
# tables + sweep cursors) with their dtypes — the single source for
# zero-initialization and for epoch-swap carry-over. The shape KIND of
# each field lives in _SESSION_SHAPE: "sess"/"natsess" are [NB, W]
# bucket grids, "scalar" is the per-table sweep cursor.
SESSION_FIELDS: Dict[str, type] = {
    "sess_src": np.uint32, "sess_dst": np.uint32, "sess_ports": np.uint32,
    "sess_proto": np.int32, "sess_valid": np.int32, "sess_time": np.int32,
    "natsess_a": np.uint32, "natsess_b": np.uint32, "natsess_ports": np.uint32,
    "natsess_proto": np.int32, "natsess_valid": np.int32,
    "natsess_time": np.int32, "natsess_orig_ip": np.uint32,
    "natsess_orig_port": np.int32, "natsess_src_ip": np.uint32,
    "natsess_sport": np.int32, "natsess_kind": np.int32,
    "sess_sweep_cursor": np.int32, "natsess_sweep_cursor": np.int32,
}

_SESSION_SHAPE: Dict[str, str] = {
    k: ("scalar" if k.endswith("_sweep_cursor")
        else "natsess" if k.startswith("natsess_") else "sess")
    for k in SESSION_FIELDS
}


def natsess_slots_of(config: DataplaneConfig) -> int:
    """Effective NAT-session slot count (the knob's 0 default means
    'same as sess_slots')."""
    n = int(getattr(config, "natsess_slots", 0) or 0)
    return n if n else config.sess_slots


def session_shapes(config: DataplaneConfig) -> Dict[str, Tuple[int, ...]]:
    """Per-field session-state shapes (no leading axes): the bucket
    grid [slots/ways, ways] per table, () for the sweep cursors."""
    w = int(getattr(config, "sess_ways", 4))
    shapes = {
        "sess": (config.sess_slots // w, w),
        "natsess": (natsess_slots_of(config) // w, w),
        "scalar": (),
    }
    return {k: shapes[_SESSION_SHAPE[k]] for k in SESSION_FIELDS}


def zero_sessions(config: DataplaneConfig, leading: Tuple[int, ...] = ()) -> Dict[str, np.ndarray]:
    """Fresh (empty) session-state arrays, optionally with leading axes
    (the cluster data plane stacks per-node session tables)."""
    shapes = session_shapes(config)
    return {k: np.zeros(leading + shapes[k], dt)
            for k, dt in SESSION_FIELDS.items()}


def zero_sessions_device(config: DataplaneConfig) -> Dict[str, jnp.ndarray]:
    """Device-resident fresh session state: ``jnp.zeros`` fills on the
    accelerator instead of shipping host zero buffers — at the 10M-slot
    regime the session columns are hundreds of MB, and uploading zeros
    is pure waste."""
    shapes = session_shapes(config)
    return {k: jnp.zeros(shapes[k], dt)
            for k, dt in SESSION_FIELDS.items()}


# Telemetry-plane fields of DataplaneTables (ops/telemetry.py; ISSUE
# 11) with their dtypes — the single source for zero-fill, the
# epoch-swap carry-over (to_device) and the persistent-pump stop-merge.
# Deliberately NOT part of SESSION_FIELDS: the crash-consistent
# snapshot format (pipeline/snapshot.py) enumerates SESSION_FIELDS, and
# telemetry is measurement state that restarts cold by design.
TELEMETRY_FIELDS: Dict[str, type] = {
    "tel_lat_hist": np.int32,
    "tel_sketch": np.int32,
    "tel_sketched": np.int32,
    "tel_top_key": np.uint32,
    "tel_top_src": np.uint32,
    "tel_top_dst": np.uint32,
    "tel_top_ports": np.uint32,
    "tel_top_cnt": np.int32,
}

_TELEMETRY_SHAPE: Dict[str, str] = {
    "tel_lat_hist": "lat", "tel_sketch": "sketch",
    "tel_sketched": "scalar", "tel_top_key": "topk",
    "tel_top_src": "topk", "tel_top_dst": "topk",
    "tel_top_ports": "topk", "tel_top_cnt": "topk",
}


def tel_capacity(config: DataplaneConfig) -> Tuple[int, int, int, int]:
    """(lat_buckets, sketch_rows, sketch_cols, topk) of the telemetry
    planes. "off" carries minimal placeholders (never read — the step
    factory compiles the stage out); "latency" keeps the sketch/top-K
    planes at placeholder size too."""
    mode = getattr(config, "telemetry", "off")
    if mode == "off":
        return 1, 1, 1, 1
    nb = int(getattr(config, "telemetry_lat_buckets", 24))
    if mode == "latency":
        return nb, 1, 1, 1
    return (nb, int(getattr(config, "telemetry_sketch_rows", 2)),
            int(getattr(config, "telemetry_sketch_cols", 1024)),
            int(getattr(config, "telemetry_topk", 8)))


def telemetry_shapes(config: DataplaneConfig) -> Dict[str, Tuple[int, ...]]:
    """Per-field telemetry-plane shapes (no leading axes)."""
    nb, d, w, k = tel_capacity(config)
    shapes = {"lat": (nb,), "sketch": (d, w), "topk": (k,),
              "scalar": ()}
    return {f: shapes[_TELEMETRY_SHAPE[f]] for f in TELEMETRY_FIELDS}


def zero_telemetry(config: DataplaneConfig,
                   leading: Tuple[int, ...] = ()) -> Dict[str, np.ndarray]:
    """Fresh (empty) telemetry planes, optionally node-stacked (the
    cluster data plane's leading axis, mirroring zero_sessions)."""
    shapes = telemetry_shapes(config)
    return {f: np.zeros(leading + shapes[f], dt)
            for f, dt in TELEMETRY_FIELDS.items()}


def zero_telemetry_device(config: DataplaneConfig) -> Dict[str, jnp.ndarray]:
    """Device-resident fresh telemetry planes (zero_sessions_device
    twin — the planes are small, but the fill still belongs on device)."""
    shapes = telemetry_shapes(config)
    return {f: jnp.zeros(shapes[f], dt)
            for f, dt in TELEMETRY_FIELDS.items()}


# --- multi-tenant gateway mode (ISSUE 14; vpp_tpu/tenancy/) ----------

# glb_ml_tnt_thresh sentinel: "inherit the model's global flag
# threshold" (a real threshold of -2^31 would flag every packet — not
# a usable configuration, so the sentinel costs nothing).
ML_TNT_THRESH_INHERIT = -(1 << 31)

# Tenancy STATE fields of DataplaneTables (token buckets + accounting
# planes) — carried by reference across epoch swaps and grafted back
# from the persistent ring at stop/sync, exactly like TELEMETRY_FIELDS.
# Deliberately NOT in SESSION_FIELDS: the crash-consistent snapshot
# format enumerates SESSION_FIELDS, and bucket levels/counters are
# measurement state that restarts cold by design.
TENANCY_STATE_FIELDS: Dict[str, type] = {
    "tnt_tokens": np.int32,
    "tnt_tok_time": np.int32,
    "tnt_rx_c": np.int32,
    "tnt_tx_c": np.int32,
    "tnt_rl_c": np.int32,
    "tnt_qf_c": np.int32,
}


def tnt_capacity(config: DataplaneConfig) -> Tuple[int, int]:
    """(tenants T, prefix slots S) of the tenant planes. "off" carries
    minimal placeholders (never read — the step factory compiles the
    tenant stage out, the ml/telemetry gating pattern)."""
    if getattr(config, "tenancy", "off") == "off":
        return 1, 1
    return (int(getattr(config, "tenancy_tenants", 8)),
            int(getattr(config, "tenancy_prefixes", 64)))


def tenancy_state_shapes(config: DataplaneConfig) -> Dict[str, Tuple[int, ...]]:
    t, _s = tnt_capacity(config)
    return {f: (t,) for f in TENANCY_STATE_FIELDS}


def zero_tenancy_state(config: DataplaneConfig,
                       leading: Tuple[int, ...] = ()) -> Dict[str, np.ndarray]:
    shapes = tenancy_state_shapes(config)
    return {f: np.zeros(leading + shapes[f], dt)
            for f, dt in TENANCY_STATE_FIELDS.items()}


def zero_tenancy_state_device(config: DataplaneConfig) -> Dict[str, jnp.ndarray]:
    shapes = tenancy_state_shapes(config)
    return {f: jnp.zeros(shapes[f], dt)
            for f, dt in TENANCY_STATE_FIELDS.items()}


# FIB STATE fields of DataplaneTables (the per-member ECMP accounting
# plane — ISSUE 15), carried by reference across epoch swaps exactly
# like TELEMETRY_FIELDS, cold on snapshot restore by design (the
# crash-consistent snapshot format enumerates SESSION_FIELDS only).
FIB_STATE_FIELDS: Dict[str, type] = {
    "fib_ecmp_c": np.int32,
}


def fib_state_shapes(config: DataplaneConfig) -> Dict[str, Tuple[int, ...]]:
    from vpp_tpu.ops.lpm import ecmp_capacity

    g, w = ecmp_capacity(config)
    return {f: (g, w) for f in FIB_STATE_FIELDS}


def zero_fib_state(config: DataplaneConfig,
                   leading: Tuple[int, ...] = ()) -> Dict[str, np.ndarray]:
    shapes = fib_state_shapes(config)
    return {f: np.zeros(leading + shapes[f], dt)
            for f, dt in FIB_STATE_FIELDS.items()}


def zero_fib_state_device(config: DataplaneConfig) -> Dict[str, jnp.ndarray]:
    shapes = fib_state_shapes(config)
    return {f: jnp.zeros(shapes[f], dt)
            for f, dt in FIB_STATE_FIELDS.items()}


def svc_capacity(config: DataplaneConfig) -> Tuple[int, int]:
    """(VIP rows V, backend ways B) of the service LB planes (ISSUE
    19). svc_vips 0 carries a [1, B] placeholder whose single row has
    bk_n 0 — it can never match, so the always-compiled svc consult is
    one inert gather (no step-form dimension for the svc path)."""
    b = int(getattr(config, "svc_backend_ways", 8))
    v = int(getattr(config, "svc_vips", 0))
    return (v if v > 0 else 1), b


def ml_capacity(config: DataplaneConfig) -> Tuple[int, int, int, int]:
    """(features, hidden, trees, depth) capacity of the staged ML
    model arrays. With ml_stage "off" the fields carry minimal
    placeholder shapes (the BV allocation-gating pattern) — the stage
    is compiled out, so the placeholders are never read."""
    from vpp_tpu.ops.mlscore import ML_FEATURES

    if getattr(config, "ml_stage", "off") == "off":
        return ML_FEATURES, 1, 1, 1
    return (ML_FEATURES, int(getattr(config, "ml_hidden", 16)),
            int(getattr(config, "ml_trees", 4)),
            int(getattr(config, "ml_depth", 3)))


def empty_ml(config: DataplaneConfig) -> Dict[str, np.ndarray]:
    """Zero (no-model) ML staging arrays at the config's capacity.
    glb_ml_thresh defaults to INT32_MAX so even a kernel compiled with
    the stage on flags nothing until a model is staged (belt to the
    kind==NONE re-gate's braces)."""
    f, h, t, d = ml_capacity(config)
    return {
        "glb_ml_w1": np.zeros((f, h), np.int8),
        "glb_ml_b1": np.zeros(h, np.int32),
        "glb_ml_s1": np.int32(0),
        "glb_ml_w2": np.zeros(h, np.int8),
        "glb_ml_b2": np.int32(0),
        "glb_ml_f_feat": np.zeros((t, d), np.int32),
        "glb_ml_f_thresh": np.zeros((t, d), np.int32),
        "glb_ml_f_leaf": np.zeros((t, 1 << d), np.int32),
        "glb_ml_thresh": np.int32(0x7FFFFFFF),
        "glb_ml_action": np.int32(0),
        "glb_ml_rl_shift": np.int32(0),
        "glb_ml_version": np.int32(0),
    }


def _fold_ml(model, config: DataplaneConfig) -> Tuple[Dict[str, np.ndarray], int]:
    """Validate one MlModel against the config capacity and produce
    the padded, zero-point-FOLDED staging arrays (+ the staged kind).

    Validates COMPLETELY before returning — the builder only assigns
    the result, so a refused model can never leave staging
    half-mutated (the loader's keep-serving-the-previous-epoch
    contract). The fold: device features are int8 ``x - 128``, so each
    integer bias absorbs ``+128 * column_sum(W)``; exact in integers,
    pinned bit-exact against the unfolded oracle by
    tests/test_ml_stage.py."""
    from vpp_tpu.ml.model import MlModel, MlModelError
    from vpp_tpu.ops.mlscore import (
        ML_ACTION_NAMES,
        ML_KIND_FOREST,
        ML_KIND_MLP,
    )

    if isinstance(model, dict):
        model = MlModel.from_dict(model)
    model.validate()
    f, h, t, d = ml_capacity(config)
    if model.n_features > f:
        raise MlModelError(
            f"model has {model.n_features} features, pipeline computes "
            f"{f}")
    out = empty_ml(config)
    action_code = {name: code for code, name
                   in ML_ACTION_NAMES.items()}[model.action]
    if model.kind == "mlp":
        mh = model.hidden
        if mh > h:
            raise MlModelError(
                f"model hidden {mh} exceeds dataplane.ml_hidden {h}")
        w1 = np.zeros((f, h), np.int8)
        w1[: model.n_features, :mh] = model.w1
        b1 = np.zeros(h, np.int32)
        # the zero-point fold, layer 1: +128 per centered input column
        b1[:mh] = model.b1.astype(np.int64) + 128 * model.w1.astype(
            np.int64).sum(axis=0)
        # padding columns keep bias 0 => relu(0) = 0 => q1 = 0; their
        # centered form contributes -128 * w2_pad = 0 (w2 padding is 0)
        w2 = np.zeros(h, np.int8)
        w2[:mh] = model.w2
        # layer-2 fold: q1c = q1 - 128 over ALL h columns (padding
        # included — q1 of a padding column is 0, centered -128, times
        # its zero weight = 0, so folding over mh columns is exact)
        b2 = int(model.b2) + 128 * int(
            model.w2.astype(np.int64).sum())
        out.update(
            glb_ml_w1=w1, glb_ml_b1=b1, glb_ml_s1=np.int32(model.s1),
            glb_ml_w2=w2, glb_ml_b2=np.int32(b2))
        kind = ML_KIND_MLP
    else:
        mt, md = model.trees, model.depth
        if mt > t or md > d:
            raise MlModelError(
                f"forest {mt}x{md} exceeds dataplane.ml_trees/ml_depth "
                f"{t}x{d}")
        f_feat = np.zeros((t, d), np.int32)
        f_thresh = np.full((t, d), 255, np.int32)  # pad bits never set
        f_leaf = np.zeros((t, 1 << d), np.int32)
        f_feat[:mt, :md] = model.f_feat
        f_thresh[:mt, :md] = model.f_thresh
        # pad levels always test feature 0 > 255 => bit 0, so a padded
        # tree's leaf index only spans the model's 2^md prefix
        f_leaf[:mt, : 1 << md] = model.f_leaf
        out.update(
            glb_ml_f_feat=f_feat, glb_ml_f_thresh=f_thresh,
            glb_ml_f_leaf=f_leaf, glb_ml_b2=np.int32(model.b2))
        kind = ML_KIND_FOREST
    out.update(
        glb_ml_thresh=np.int32(model.flag_thresh),
        glb_ml_action=np.int32(action_code),
        glb_ml_rl_shift=np.int32(model.rl_shift),
        glb_ml_version=np.int32(model.version),
    )
    return out, kind


def pack_rules(rules: Sequence[ContivRule], max_rules: int) -> Dict[str, np.ndarray]:
    """Compile an ordered ContivRule list into padded match arrays.

    Rules must already be in evaluation order (most specific first — the
    ContivRuleTable invariant); first match wins in the kernel. Padding
    rows can never match (impossible port range, proto -2).

    Single Python pass gathering scalars + vectorized array fill: the
    original per-row array-store loop was the dominant host cost of a
    10k-rule commit (~17 ms), ahead of the bit-plane compile.
    """
    n = len(rules)
    if n > max_rules:
        raise ValueError(f"{n} rules exceed table capacity {max_rules}")
    out = _empty_packed(max_rules)
    if not n:
        return out
    rows = np.empty((n, 10), np.int64)
    for i, r in enumerate(rules):
        rows[i] = _rule_row(r)
    _fill_packed(out, rows, n)
    return out


def _empty_packed(max_rules: int) -> Dict[str, np.ndarray]:
    """All-padding match arrays (rows that can never match)."""
    return {
        "src_net": np.zeros(max_rules, np.uint32),
        "src_mask": np.zeros(max_rules, np.uint32),
        "dst_net": np.zeros(max_rules, np.uint32),
        "dst_mask": np.zeros(max_rules, np.uint32),
        "proto": np.full(max_rules, -2, np.int32),
        "sport_lo": np.ones(max_rules, np.int32),
        "sport_hi": np.zeros(max_rules, np.int32),
        "dport_lo": np.ones(max_rules, np.int32),
        "dport_hi": np.zeros(max_rules, np.int32),
        "action": np.full(max_rules, -1, np.int32),
    }


def _fill_packed(out: Dict[str, np.ndarray], rows: np.ndarray,
                 n: int) -> None:
    # out's insertion order IS the row-tuple order — one source of truth
    for j, (name, arr) in enumerate(out.items()):
        arr[:n] = rows[:, j].astype(arr.dtype)


def _rule_row(r: ContivRule) -> tuple:
    """One rule's 10-value match row (pack_rules layout)."""
    # IPv6 is a DESIGNED limitation of this v4 data plane (README
    # "Scope"): non-IPv4 frames never enter the classifier — the IO
    # front-end punts them to the host path — so a v6 rule can never
    # influence a verdict here. Skip it (row stays never-match)
    # instead of failing the whole table commit; enforcement for v6
    # belongs to the host stack that terminates that traffic.
    if (r.src_network is not None and r.src_network.version != 4) or (
        r.dest_network is not None and r.dest_network.version != 4
    ):
        log.warning("skipping IPv6 rule in v4 table: %s", r)
        return (0, 0, 0, 0, -2, 1, 0, 1, 0, -1)  # never-match row
    if r.src_network is not None:
        sm = _mask_of(r.src_network.prefixlen)
        sn = int(r.src_network.network_address) & sm
    else:
        sm = sn = 0
    if r.dest_network is not None:
        dm = _mask_of(r.dest_network.prefixlen)
        dn = int(r.dest_network.network_address) & dm
    else:
        dm = dn = 0
    sp, dp = r.src_port, r.dest_port
    return (
        sn, sm, dn, dm, r.protocol.ip_proto,
        0 if sp == ANY_PORT else sp, 65535 if sp == ANY_PORT else sp,
        0 if dp == ANY_PORT else dp, 65535 if dp == ANY_PORT else dp,
        int(r.action),
    )


def pack_rules_incremental(
    rules: Sequence[ContivRule],
    max_rules: int,
    prev_rules: Optional[list],
    prev_rows: Optional[np.ndarray],
) -> Tuple[Dict[str, np.ndarray], np.ndarray, Optional[np.ndarray]]:
    """pack_rules with an identity diff against the previous commit.

    Policy churn hands the builder a full rule list per commit, but
    unchanged entries are the SAME frozen ContivRule objects (the
    renderer cache reuses them) — so ``new[i] is old[i]`` finds the
    rows whose match columns must be recomputed, and everything else
    copies from ``prev_rows``. Rules that shift position (an
    insert/remove earlier in the list) fail the identity check at
    their new index and are simply recomputed — correctness never
    depends on the caller's reuse discipline, only the speedup does.

    Returns ``(packed, rows, changed)``: ``rows`` is the cache for the
    next call; ``changed`` is the sorted index array of rows that
    differ from the previous commit INCLUDING previously-live rows now
    past the end of the table (their bit-plane columns must revert to
    padding), or None when there was no usable previous state (full
    recompile)."""
    n = len(rules)
    if n > max_rules:
        raise ValueError(f"{n} rules exceed table capacity {max_rules}")
    rows = np.empty((n, 10), np.int64)
    if prev_rules is None or prev_rows is None:
        changed = None  # cold start: everything recompiles
        for i, r in enumerate(rules):
            rows[i] = _rule_row(r)
    else:
        m = len(prev_rules)
        changed_idx = []
        for i, r in enumerate(rules):
            if i < m and r is prev_rules[i]:
                rows[i] = prev_rows[i]
            else:
                rows[i] = _rule_row(r)
                changed_idx.append(i)
        # rows that existed last commit but are past the new end: their
        # packed slots revert to padding below, and their bit-plane
        # columns must be recompiled to never-match
        changed_idx.extend(range(n, m))
        changed = np.asarray(changed_idx, np.int64)
    packed = _empty_packed(max_rules)
    if n:
        _fill_packed(packed, rows, n)
    return packed, rows, changed


# Global-table fields in ROW space [R] (diffed/updated together; the
# bit-plane fields live in COLUMN space [R'] and diff separately).
_GLB_ROW_FIELDS: Tuple[str, ...] = (
    "glb_src_net", "glb_src_mask", "glb_dst_net", "glb_dst_mask",
    "glb_proto", "glb_sport_lo", "glb_sport_hi", "glb_dport_lo",
    "glb_dport_hi", "glb_action",
)


@functools.lru_cache(maxsize=16)
def _glb_update_fn(w_r: int, w_c: int, planes: int):
    """Jitted incremental global-table update for (row-block w_r,
    column-block w_c): ONE packed int32 blob upload carries every
    changed block, and one compiled program scatters the blocks into
    the cached device arrays with dynamic_update_slice (traced start
    offsets — no recompile per position). Blob layout:
    [10 x w_r rows | w_c k | w_c act | planes x w_c coeff]."""
    import jax

    def update(rows, k, act, coeff, blob, lo_r, lo_c):
        from jax import lax

        out_rows = []
        for i, dev in enumerate(rows):
            piece = lax.bitcast_convert_type(
                blob[i * w_r:(i + 1) * w_r], dev.dtype
            )
            out_rows.append(lax.dynamic_update_slice(dev, piece, (lo_r,)))
        base = 10 * w_r
        k_piece = lax.bitcast_convert_type(
            blob[base:base + w_c], jnp.float32
        )
        new_k = lax.dynamic_update_slice(k, k_piece, (lo_c,))
        act_piece = blob[base + w_c:base + 2 * w_c]
        new_act = lax.dynamic_update_slice(act, act_piece, (lo_c,))
        coeff_piece = lax.bitcast_convert_type(
            blob[base + 2 * w_c:base + 2 * w_c + planes * w_c],
            jnp.float32,
        ).reshape(planes, w_c)
        new_coeff = lax.dynamic_update_slice(
            coeff, coeff_piece, (0, lo_c)
        )
        return out_rows, new_k, new_act, new_coeff

    return jax.jit(update)


def _block_of(changed: np.ndarray, total: int) -> Optional[Tuple[int, int]]:
    """(lo, width) of the smallest padded block covering every changed
    index, widths on a x4 ladder; None when nothing changed."""
    idx = np.nonzero(changed)[0]
    if len(idx) == 0:
        return None
    lo, hi = int(idx[0]), int(idx[-1]) + 1
    span = hi - lo
    w = 256
    while w < span:
        w *= 4
    if w >= total:
        return 0, total
    lo = min(lo, total - w)
    return lo, w


# Upload groups: which DataplaneTables fields each builder mutation
# invalidates. to_device() re-uploads only dirty groups; the rest reuse
# the previous epoch's device arrays (the big win: a CNI add doesn't
# re-ship the multi-MB 10k-rule bit-plane matrix).
_UPLOAD_GROUPS: Dict[str, Tuple[str, ...]] = {
    "acl": ("acl_src_net", "acl_src_mask", "acl_dst_net", "acl_dst_mask",
            "acl_proto", "acl_sport_lo", "acl_sport_hi", "acl_dport_lo",
            "acl_dport_hi", "acl_action", "acl_nrules",
            "acl_bv_bnd_src", "acl_bv_bnd_dst", "acl_bv_bnd_sport",
            "acl_bv_bnd_dport", "acl_bv_nbnd", "acl_bv_src",
            "acl_bv_dst", "acl_bv_sport", "acl_bv_dport",
            "acl_bv_proto"),
    "glb": ("glb_src_net", "glb_src_mask", "glb_dst_net", "glb_dst_mask",
            "glb_proto", "glb_sport_lo", "glb_sport_hi", "glb_dport_lo",
            "glb_dport_hi", "glb_action", "glb_nrules", "glb_mxu_coeff",
            "glb_mxu_k", "glb_mxu_act"),
    # the BV structure uploads per-dimension-plane (see to_device): a
    # separate group so the "glb" incremental row/column blob path can
    # never leave stale BV planes on the device
    "glb_bv": ("glb_bv_bnd_src", "glb_bv_bnd_dst", "glb_bv_bnd_sport",
               "glb_bv_bnd_dport", "glb_bv_nbnd", "glb_bv_src",
               "glb_bv_dst", "glb_bv_sport", "glb_bv_dport",
               "glb_bv_proto"),
    # the ML model blob (set_ml_model): its OWN group so an epoch swap
    # re-ships it ONLY when the model actually changed — ACL/FIB/NAT
    # churn reuses the cached device arrays (zero re-ship, pinned by
    # tests/test_ml_stage.py), and a model swap ships ~a few hundred
    # int8 weights without touching the multi-MB rule planes
    "ml": ("glb_ml_w1", "glb_ml_b1", "glb_ml_s1", "glb_ml_w2",
           "glb_ml_b2", "glb_ml_f_feat", "glb_ml_f_thresh",
           "glb_ml_f_leaf", "glb_ml_thresh", "glb_ml_action",
           "glb_ml_rl_shift", "glb_ml_version"),
    "if": ("if_type", "if_local_table", "if_apply_global"),
    # the FIB group uploads with per-field granularity (see to_device):
    # per-slot row arrays go through the incremental scatter-blob path
    # (_fib_incremental — a route flap ships a few-KB blob, not 9 x 4 MB
    # columns at the 1M-route regime), and the per-length LPM planes +
    # ECMP tables re-ship only when _fib_dirty names them (a flap =
    # ONE touched length plane + the count vector)
    "fib": ("fib_prefix", "fib_mask", "fib_plen", "fib_tx_if", "fib_disp",
            "fib_next_hop", "fib_node_id", "fib_snat", "fib_grp",
            "fib_lpm_p0", "fib_lpm_p1", "fib_lpm_p2", "fib_lpm_p3",
            "fib_lpm_p4", "fib_lpm_p5", "fib_lpm_p6", "fib_lpm_p7",
            "fib_lpm_p8", "fib_lpm_p9", "fib_lpm_p10", "fib_lpm_p11",
            "fib_lpm_p12", "fib_lpm_p13", "fib_lpm_p14", "fib_lpm_p15",
            "fib_lpm_p16", "fib_lpm_p17", "fib_lpm_p18", "fib_lpm_p19",
            "fib_lpm_p20", "fib_lpm_p21", "fib_lpm_p22", "fib_lpm_p23",
            "fib_lpm_p24", "fib_lpm_p25", "fib_lpm_p26", "fib_lpm_p27",
            "fib_lpm_p28", "fib_lpm_p29", "fib_lpm_p30", "fib_lpm_p31",
            "fib_lpm_p32", "fib_lpm_cnt", "fib_lpm_hint",
            "fib_grp_nh", "fib_grp_tx_if", "fib_grp_node", "fib_grp_n"),
    "nat": ("nat_ext_ip", "nat_ext_port", "nat_proto", "nat_boff",
            "nat_bcnt", "nat_total_w", "nat_self_snat", "natb_ip",
            "natb_port", "natb_cumw", "nat_snat_ip"),
    "config": ("sess_max_age", "ovl_vtep_ip"),
    # tenancy config half (ISSUE 14): its OWN group, so tenant churn
    # (a new prefix, a rate change, a per-tenant ML threshold flip)
    # ships a few hundred bytes and never re-ships rules or weights —
    # and vice versa. The tnt_* STATE planes are not here: they ride
    # the carry-over like the sweep cursors.
    "tenant": ("tnt_pfx_net", "tnt_pfx_mask", "tnt_pfx_id",
               "tnt_rate", "tnt_burst",
               "tnt_sess_base", "tnt_sess_mask",
               "tnt_nat_base", "tnt_nat_mask",
               "glb_ml_tnt_mode", "glb_ml_tnt_thresh", "tnt_vni"),
    # service NAT44 LB planes (ISSUE 19): their OWN group so a rolling
    # backend replacement ships ONLY svc bytes — every other group
    # keeps its cached device-array identity (zero reship).
    # Additionally rides the incremental
    # scatter-blob path (_upload_svc): changed VIP rows confine to a
    # block and ship as one few-KB blob.
    "svc": ("svc_vip_ip", "svc_vip_port", "svc_vip_proto",
            "svc_vip_snat", "svc_bk_n", "svc_bk_ip", "svc_bk_port"),
}

# Per-slot FIB row arrays (the dense kernel's columns + the shared
# resolver's route data): diffed together against _fib_prev and
# scatter-updated on device as ONE packed blob when a commit's changes
# confine to a block (_fib_incremental — the _glb_incremental scheme
# without the bit-plane column space).
_FIB_SLOT_FIELDS: Tuple[str, ...] = (
    "fib_prefix", "fib_mask", "fib_plen", "fib_tx_if", "fib_disp",
    "fib_next_hop", "fib_node_id", "fib_snat", "fib_grp",
)


@functools.lru_cache(maxsize=8)
def _fib_update_fn(w: int):
    """Jitted incremental per-slot FIB update for row-block width
    ``w``: one packed int32 blob carries every per-slot array's
    changed block, one compiled program scatters the blocks into the
    cached device arrays with dynamic_update_slice (traced start
    offset — no recompile per position). Blob layout: [9 x w rows]."""
    import jax

    def update(rows, blob, lo):
        from jax import lax

        out = []
        for i, dev in enumerate(rows):
            piece = lax.bitcast_convert_type(
                blob[i * w:(i + 1) * w], dev.dtype
            )
            out.append(lax.dynamic_update_slice(dev, piece, (lo,)))
        return out

    return jax.jit(update)


# Service-LB planes in VIP-row space (ISSUE 19): diffed together
# against _svc_prev and scatter-updated on device as ONE packed blob
# when a churn's changes confine to a row block (the _fib_incremental
# scheme; the [V, B] way tables flatten into the blob row-major).
_SVC_1D_FIELDS: Tuple[str, ...] = (
    "svc_vip_ip", "svc_vip_port", "svc_vip_proto", "svc_vip_snat",
    "svc_bk_n",
)
_SVC_2D_FIELDS: Tuple[str, ...] = ("svc_bk_ip", "svc_bk_port")


@functools.lru_cache(maxsize=8)
def _svc_update_fn(w: int, ways: int):
    """Jitted incremental service-plane update for VIP-row-block width
    ``w``: one packed int32 blob carries every svc array's changed row
    block, one compiled program scatters the blocks into the cached
    device arrays (traced start offset — no recompile per position).
    Blob layout: [5 x w rows | 2 x w x B way rows]."""
    import jax

    def update(rows, grids, blob, lo):
        from jax import lax

        out_rows = []
        for i, dev in enumerate(rows):
            piece = lax.bitcast_convert_type(
                blob[i * w:(i + 1) * w], dev.dtype
            )
            out_rows.append(lax.dynamic_update_slice(dev, piece, (lo,)))
        base = len(rows) * w
        out_grids = []
        for i, dev in enumerate(grids):
            piece = lax.bitcast_convert_type(
                blob[base + i * w * ways:base + (i + 1) * w * ways],
                dev.dtype,
            ).reshape(w, ways)
            out_grids.append(
                lax.dynamic_update_slice(dev, piece, (lo, 0)))
        return out_rows, out_grids

    return jax.jit(update)


# BV dimension -> its global-table device fields (granular upload:
# only the planes compile_bv actually rebuilt re-ship; the nbnd count
# vector rides along whenever anything changed).
_GLB_BV_DIM_FIELDS: Dict[str, Tuple[str, ...]] = {
    "src": ("glb_bv_bnd_src", "glb_bv_src"),
    "dst": ("glb_bv_bnd_dst", "glb_bv_dst"),
    "sport": ("glb_bv_bnd_sport", "glb_bv_sport"),
    "dport": ("glb_bv_bnd_dport", "glb_bv_dport"),
    "proto": ("glb_bv_proto",),
}


class TableBuilder:
    """Mutable host-side (numpy) staging area for the device tables.

    The TPU renderer and the node controller mutate this builder, then call
    ``to_device()`` to produce the immutable DataplaneTables pytree for the
    next epoch. Session state is *not* rebuilt: ``to_device`` can graft the
    live session arrays from a previous epoch so established flows survive
    table swaps.
    """

    def __init__(self, config: DataplaneConfig = DataplaneConfig()):
        validate_dataplane_config(config)
        self.config = config
        self.mxu_enabled = True  # opt-out knob for the bit-plane compile
        # api-trace analog (pipeline/txn.py): with recording started,
        # every mutator appends its declarative op here and the owning
        # Dataplane journals the batch at swap() — production writers
        # (renderers, CNI, service, node events) get recorded without
        # changing, exactly like VPP tracing at the binary-API boundary
        # (reference contiv-vswitch.conf:13-15 `api-trace { on }`).
        self._rec = None
        # optional writer-supplied label for the NEXT journaled txn
        self.txn_label = ""
        c = config
        z = np.zeros
        self.acl = {
            k: np.tile(v, (c.max_tables, 1))
            for k, v in pack_rules([], c.max_rules).items()
        }
        self.acl_nrules = z(c.max_tables, np.int32)
        self.glb = pack_rules([], c.max_global_rules)
        self.glb_nrules = 0
        from vpp_tpu.ops.acl_mxu import empty_bitplanes

        self.glb_mxu = empty_bitplanes(c.max_global_rules)
        # BV interval-bitmap staging (ops/acl_bv.py). Allocation is
        # knob-gated: dense/mxu configs (and auto configs whose
        # worst-case structure busts classifier_bv_mem_mb) carry only
        # minimal placeholder shapes — the BV kernels are then never
        # selected, so the placeholders are never read.
        from vpp_tpu.ops.acl_bv import bv_capacity, bv_enabled_for, empty_bv

        knob = getattr(c, "classifier", "auto")
        if knob not in ("dense", "mxu", "bv", "pallas", "auto"):
            # loud, at config time: a typo'd knob silently falling
            # through to the auto ladder would run a different
            # classifier than the operator believes is deployed
            raise ValueError(
                f"unknown dataplane.classifier {knob!r} "
                f"(expected dense | mxu | bv | pallas | auto)")
        self.bv_enabled = bv_enabled_for(c)
        self.glb_bv = empty_bv(c.max_global_rules, self.bv_enabled)
        self._bv_cols = None        # per-dim column cache (incremental)
        self._bv_dirty = set(_UPLOAD_GROUPS["glb_bv"])
        self.bv_rebuilt: Tuple[str, ...] = ()  # last commit's planes
        self.bv_build_ms = 0.0      # last commit's BV host build cost
        local_bv = empty_bv(c.max_rules, self.bv_enabled)
        lib, lw, lpr = bv_capacity(c.max_rules, self.bv_enabled)
        self.acl_bv = {
            "bnd_src": np.tile(local_bv.bnd_src, (c.max_tables, 1)),
            "bnd_dst": np.tile(local_bv.bnd_dst, (c.max_tables, 1)),
            "bnd_sport": np.tile(local_bv.bnd_sport, (c.max_tables, 1)),
            "bnd_dport": np.tile(local_bv.bnd_dport, (c.max_tables, 1)),
            "nbnd": np.tile(local_bv.nbnd, (c.max_tables, 1)),
            "src": np.zeros((c.max_tables, lib, lw), np.uint32),
            "dst": np.zeros((c.max_tables, lib, lw), np.uint32),
            "sport": np.zeros((c.max_tables, lib, lw), np.uint32),
            "dport": np.zeros((c.max_tables, lib, lw), np.uint32),
            "proto": np.zeros((c.max_tables, lpr, lw), np.uint32),
        }
        self.acl_bv_ok = np.ones(c.max_tables, bool)
        # per-packet ML model staging (ops/mlscore.py; docs/ML_STAGE.md):
        # zero/no-model arrays at the config capacity until
        # set_ml_model stages an artifact. ml_kind is the staged
        # model's kernel variant (ML_KIND_*; 0 = none — the Dataplane
        # re-gates the compiled stage off at swap while it is 0).
        self.ml = empty_ml(c)
        self.ml_kind = 0
        # multi-tenant gateway staging (ISSUE 14; vpp_tpu/tenancy/):
        # a normalized tenant-entry registry (set_tenant) compiled
        # into the "tenant" upload-group arrays by _restage_tenants.
        # The VNI → tenant map and the WFQ weights live in the
        # registry only — they are HOST-side knobs (the IO pump's
        # TenantClassifier), not device state.
        self.tenants: Dict[int, dict] = {}
        self.tnt: Dict[str, np.ndarray] = {}
        self._restage_tenants()
        self.if_type = z(c.max_ifaces, np.int32)
        self.if_local_table = np.full(c.max_ifaces, -1, np.int32)
        self.if_apply_global = z(c.max_ifaces, np.int32)
        self.fib_prefix = z(c.fib_slots, np.uint32)
        self.fib_mask = z(c.fib_slots, np.uint32)
        self.fib_plen = np.full(c.fib_slots, -1, np.int32)
        self.fib_tx_if = z(c.fib_slots, np.int32)
        self.fib_disp = np.full(c.fib_slots, int(Disposition.DROP), np.int32)
        self.fib_next_hop = z(c.fib_slots, np.uint32)
        self.fib_node_id = np.full(c.fib_slots, -1, np.int32)
        self.fib_snat = z(c.fib_slots, np.int32)
        self.fib_grp = np.full(c.fib_slots, -1, np.int32)
        # LPM per-length prefix planes (ops/lpm.py; ISSUE 15).
        # Allocation is knob-gated like BV: dense configs (and auto
        # configs whose worst-case planes bust fib_lpm_mem_mb) carry
        # zero-width placeholders — the LPM kernel is then never
        # selected. Staging is LAZY: mutators only mark the touched
        # LENGTH dirty; _restage_lpm() recompiles dirty planes at
        # host_arrays()/lpm_ok() time (one vectorized pass per dirty
        # length — a 1M-route bulk load pays 33 passes total, not one
        # per route).
        from vpp_tpu.ops.lpm import (
            LPM_LENGTHS,
            LPM_PAD,
            ecmp_capacity,
            lpm_enabled_for,
            lpm_field,
            lpm_hint_layout,
            lpm_len_caps,
        )

        self.lpm_enabled = lpm_enabled_for(c)
        self.lpm_caps = lpm_len_caps(c)
        self._lpm_layout, hint_rows = lpm_hint_layout(self.lpm_caps)
        self.lpm_hint = z(hint_rows, np.int32)
        self.lpm_planes = {}
        for length in range(LPM_LENGTHS):
            plane = np.zeros((2, self.lpm_caps[length]), np.uint32)
            plane[0, :] = LPM_PAD
            self.lpm_planes[lpm_field(length)] = plane
        self.lpm_cnt = z(LPM_LENGTHS, np.int32)
        # full per-length route counts (deduped, NOT clipped to caps —
        # the lpm_ok() overflow signal and the `show fib` histogram)
        self.lpm_counts = z(LPM_LENGTHS, np.int64)
        self._lpm_dirty_lens = set(range(LPM_LENGTHS))
        self.lpm_build_ms = 0.0   # host cost of the LAST plane restage
        # ECMP next-hop groups: registry {gid: {"members": [(nh,
        # tx_if, node), ...], "assign": [member per way]}} compiled
        # into the [G, W] member tables with STICKY way assignment
        # (set_nh_group) — member churn only reassigns the ways it
        # must, so flows hashed to surviving ways keep their member.
        gcap, ways = ecmp_capacity(c)
        self.nh_groups: Dict[int, dict] = {}
        self.fib_grp_nh = z((gcap, ways), np.uint32)
        self.fib_grp_tx_if = np.full((gcap, ways), -1, np.int32)
        self.fib_grp_node = np.full((gcap, ways), -1, np.int32)
        self.fib_grp_n = z(gcap, np.int32)
        # per-field dirty set of the "fib" upload group (the _bv_dirty
        # pattern): to_device re-ships only named fields; per-slot row
        # arrays additionally try the incremental scatter-blob path
        self._fib_dirty = set(_UPLOAD_GROUPS["fib"])
        # per-slot arrays as of the last full device upload (the
        # incremental diff base; None = next commit uploads full)
        self._fib_prev: Optional[Dict[str, np.ndarray]] = None
        # last fib-group upload, for `show fib` / fib_bench: fields
        # re-shipped, bytes, host ms ("blob" = the per-slot scatter)
        self.fib_upload: Dict[str, object] = {}
        self.fib_last_shipped = False
        self.nat_ext_ip = z(c.nat_mappings, np.uint32)
        self.nat_ext_port = z(c.nat_mappings, np.int32)
        self.nat_proto = z(c.nat_mappings, np.int32)
        self.nat_boff = z(c.nat_mappings, np.int32)
        self.nat_bcnt = z(c.nat_mappings, np.int32)
        self.nat_total_w = z(c.nat_mappings, np.int32)
        self.nat_self_snat = z(c.nat_mappings, np.int32)
        self.natb_ip = z(c.nat_backends, np.uint32)
        self.natb_port = z(c.nat_backends, np.int32)
        self.natb_cumw = z(c.nat_backends, np.int32)
        self.nat_snat_ip = np.uint32(0)
        # VXLAN overlay config (ISSUE 19): the node's local VTEP
        # address, staged into the tiny "config" group.
        self.ovl_vtep_ip = np.uint32(0)
        # Service NAT44 LB staging (ISSUE 19): a normalized service
        # registry (set_service) compiled into the "svc" upload-group
        # arrays by _restage_svc — the tenant-registry pattern. Each
        # entry keeps its sticky way ASSIGNMENT, keyed by the service
        # key, so VIP-row moves from churn elsewhere never reshuffle a
        # surviving service's backend picks.
        self.services: Dict[Tuple[int, int, int], dict] = {}
        self.svc: Dict[str, np.ndarray] = {}
        self._restage_svc()
        # svc incremental-upload state (the _fib_prev discipline):
        # diff base of the last full device upload (None = next commit
        # uploads full) + last-upload record for `show services` /
        # overlay_bench's svc_churn_bytes.
        self._svc_prev: Optional[Dict[str, np.ndarray]] = None
        self.svc_upload: Dict[str, object] = {}
        self.svc_last_shipped = False
        # Upload groups touched since the last to_device(): every field
        # of a clean group reuses the previous epoch's DEVICE array, so
        # a CNI add (fib+if dirty) doesn't re-upload the 10k-rule
        # bit-plane matrix — each host→device transfer is a full RPC
        # round trip on a remote transport (VERDICT r2 Weak #4).
        self._dirty = set(_UPLOAD_GROUPS)
        self._dev_cache: Dict[str, object] = {}
        # host arrays as of the last SUCCESSFUL device upload of the
        # "glb" group: the diff base for incremental column/row-block
        # commits (row arrays copied — see _set_glb_prev).
        self._glb_prev: Optional[Dict[str, np.ndarray]] = None
        # incremental global-table HOST compile (VERDICT r4 Next #3):
        # the renderer hands a full rule list per commit but reuses
        # unchanged frozen ContivRule objects, so an identity diff
        # (pack_rules_incremental) finds the churned rows and only
        # their match rows + bit-plane columns are recomputed.
        # Invalidated (None) whenever glb state changes by any path
        # other than set_global_table (snapshot restore).
        self._glb_rules_ref: Optional[list] = None
        self._glb_rows: Optional[np.ndarray] = None
        self._glb_bad: Optional[np.ndarray] = None

    def _mark(self, group: str) -> None:
        self._dirty.add(group)

    # --- op recording (config transaction trace) ---
    def start_recording(self) -> None:
        from vpp_tpu.pipeline.txn import ConfigTxn

        if self._rec is None:
            self._rec = ConfigTxn()

    def drain_recording(self):
        """Ops recorded since the last drain as one ConfigTxn (None when
        recording is off or nothing was staged). Consumes the pending
        ``txn_label``. Called by swap() under the commit lock."""
        from vpp_tpu.pipeline.txn import ConfigTxn

        if self._rec is None or not self._rec.ops:
            self.txn_label = ""
            return None
        txn = self._rec
        txn.label = self.txn_label
        self.txn_label = ""
        self._rec = ConfigTxn()
        return txn

    def bv_ok(self) -> bool:
        """Whether the BV classifier can serve THIS staged config:
        structure allocated, and every table (global + all local
        slots) expressible as interval bitmaps (no non-prefix masks)."""
        return (self.bv_enabled and self.glb_bv.ok
                and bool(self.acl_bv_ok.all()))

    # --- ACL ---
    def set_local_table(self, slot: int, rules: Sequence[ContivRule]) -> None:
        packed = pack_rules(rules, self.config.max_rules)
        for k, v in packed.items():
            self.acl[k][slot] = v
        self.acl_nrules[slot] = len(rules)
        if self.bv_enabled:
            # per-slot full rebuild: local tables are <= max_rules
            # (128) rows, so the plane compile is microseconds — the
            # dimension-incremental path only pays off at global scale
            from vpp_tpu.ops.acl_bv import compile_bv

            bv, _, _ = compile_bv(packed, self.config.max_rules)
            for dim in ("src", "dst", "sport", "dport"):
                self.acl_bv[f"bnd_{dim}"][slot] = getattr(bv, f"bnd_{dim}")
                self.acl_bv[dim][slot] = getattr(bv, f"bm_{dim}")
            self.acl_bv["nbnd"][slot] = bv.nbnd
            self.acl_bv["proto"][slot] = bv.bm_proto
            self.acl_bv_ok[slot] = bv.ok
        if self._rec is not None:
            self._rec.set_local_table(slot, rules)
        self._mark("acl")

    def clear_local_table(self, slot: int) -> None:
        self.set_local_table(slot, [])

    def set_global_table(self, rules: Sequence[ContivRule]) -> None:
        from vpp_tpu.ops.acl_mxu import (
            compile_bitplanes_full,
            compile_bitplanes_update,
            empty_bitplanes,
        )

        cap = self.config.max_global_rules
        packed, rows, changed = pack_rules_incremental(
            rules, cap, self._glb_rules_ref, self._glb_rows)
        self.glb = packed
        self.glb_nrules = len(rules)
        if self._rec is not None:
            self._rec.set_global_table(rules)
        # mxu_enabled=False skips the O(PLANES·R) host-side bit-plane
        # compile for callers that will never take the MXU path. (The
        # zero coeff matrix is still part of the pytree — shapes must
        # stay epoch-invariant for jit — so the device upload itself is
        # not avoided, only the host work.)
        #
        # The identity caches are persisted only AFTER a successful
        # compile: caching them first would let a compile exception
        # (e.g. MemoryError on the coeff matrix) poison the diff base —
        # a retried commit with the same rule objects would see
        # changed=[] and carry the STALE bit-planes forward silently.
        try:
            if not self.mxu_enabled:
                self.glb_mxu = empty_bitplanes(cap)
                bad = None  # forces a full compile if re-enabled
            elif changed is None or self._glb_bad is None:
                self.glb_mxu, bad = compile_bitplanes_full(self.glb, cap)
            else:
                # policy churn: only the changed rule columns recompile
                self.glb_mxu, bad = compile_bitplanes_update(
                    self.glb, cap, self.glb_mxu, self._glb_bad, changed)
            if self.bv_enabled:
                # dimension-incremental BV compile (ops/acl_bv.py):
                # composes with the identity-diff pack above — only
                # dimension planes whose per-rule intervals actually
                # moved rebuild; a port-only churn keeps the (large)
                # address bitmaps untouched on host AND device
                from vpp_tpu.ops.acl_bv import compile_bv

                # upload-ok: compile_bv reuses the prev planes for
                # every dimension it did not rebuild, so when
                # `rebuilt` is empty the device copies are still
                # content-identical and skipping the glb_bv mark is
                # the zero-reship design, not a staleness gap; any
                # rebuilt dimension marks the group two lines down
                self.glb_bv, self._bv_cols, rebuilt = compile_bv(
                    self.glb, cap, prev=self.glb_bv,
                    prev_cols=self._bv_cols)
                self.bv_rebuilt = rebuilt
                self.bv_build_ms = self.glb_bv.build_ms
                if rebuilt:
                    self._bv_dirty.add("glb_bv_nbnd")
                    for dim in rebuilt:
                        self._bv_dirty.update(_GLB_BV_DIM_FIELDS[dim])
                    self._mark("glb_bv")
        except Exception:
            self._glb_rules_ref = None
            self._glb_rows = None
            self._glb_bad = None
            self._bv_cols = None
            self._bv_dirty = set(_UPLOAD_GROUPS["glb_bv"])
            raise
        self._glb_rules_ref = list(rules)
        self._glb_rows = rows
        self._glb_bad = bad
        self._mark("glb")

    # --- per-packet ML model (ops/mlscore.py) ---
    def set_ml_model(self, model) -> None:
        """Stage one quantized model (an MlModel or its dict form —
        vpp_tpu/ml/model.py) for the next epoch. Validation + padding
        + the zero-point fold all happen in ``_fold_ml`` BEFORE any
        staging state mutates, so a refused artifact (bad shape, bad
        version, capacity overflow) leaves the previous model serving
        — the loader's clean-refusal contract (vpp_tpu/ml/loader.py).
        Marks only the "ml" upload group: rule churn and model churn
        re-ship independently."""
        staged, kind = _fold_ml(model, self.config)
        self.ml = staged
        self.ml_kind = kind
        if self._rec is not None:
            self._rec.set_ml_model(model)
        self._mark("ml")

    def clear_ml_model(self) -> None:
        """Back to the no-model state (the stage re-gates off at the
        next swap)."""
        self.ml = empty_ml(self.config)
        self.ml_kind = 0
        if self._rec is not None:
            self._rec.clear_ml_model()
        self._mark("ml")

    # --- multi-tenant gateway (ISSUE 14; vpp_tpu/tenancy/) ---
    def _restage_tenants(self) -> None:
        """Compile the tenant registry into the "tenant" upload-group
        arrays. Session/NAT bucket slices are allocated contiguously
        in ascending tenant-id order from the TOP of the table
        downward (GLOBAL bucket units — the mesh's bucket-axis shards
        split any global index, so slices compose with the partition
        layer unchanged); unsliced tenants (including the implicit
        default tenant 0) share the residual BOTTOM region, masked to
        the largest power of two that fits — disjoint from every
        slice, so unsliced traffic can never hash into (let alone
        evict from) a sliced tenant's range. With nothing sliced the
        residual is the whole table: bit-identical to the unsliced
        ``_hash``. Deterministic: the same registry always compiles
        byte-identical arrays."""
        c = self.config
        T, S = tnt_capacity(c)
        ways = int(getattr(c, "sess_ways", 4))
        sess_nb = c.sess_slots // ways
        nat_nb = natsess_slots_of(c) // ways
        net = np.zeros(S, np.uint32)
        mask = np.zeros(S, np.uint32)
        pid = np.full(S, -1, np.int32)
        rate = np.zeros(T, np.int32)
        burst = np.zeros(T, np.int32)
        sb = np.zeros(T, np.int32)
        sm = np.zeros(T, np.int32)
        nb_ = np.zeros(T, np.int32)
        nm = np.zeros(T, np.int32)
        mlm = np.zeros(T, np.int32)
        mlt = np.full(T, ML_TNT_THRESH_INHERIT, np.int32)
        # VNI → tenant plane (ISSUE 19): tenant t's registered VNI or
        # -1. Tenancy-off placeholder admits DEFAULT_VNI as tenant 0 so
        # the single-tenant overlay works out of the box; every other
        # VNI fails closed at decap.
        from vpp_tpu.ops.vxlan import DEFAULT_VNI  # local: keeps the
        # tables module importable without pulling the overlay ops in
        # at module load (the sched-import discipline)

        vni = np.full(T, -1, np.int32)
        if getattr(c, "tenancy", "off") == "off":
            vni[0] = DEFAULT_VNI
        slot = 0
        cursor = {"sess": sess_nb, "nat": nat_nb}
        sliced_tids = {"sess": set(), "nat": set()}
        from vpp_tpu.tenancy.sched import ML_MODE_CODES  # jax-free

        for tid in sorted(self.tenants):
            e = self.tenants[tid]
            for p in e["prefixes"]:
                if slot >= S:
                    raise ValueError(
                        f"tenant prefix map full ({S} slots — raise "
                        f"dataplane.tenancy_prefixes)")
                pnet = ipaddress.ip_network(p, strict=False)
                m = _mask_of(pnet.prefixlen)
                net[slot] = int(pnet.network_address) & m
                mask[slot] = m
                pid[slot] = tid
                slot += 1
            rate[tid] = e["rate"]
            burst[tid] = e["burst"]
            for kind, basearr, maskarr in (
                    ("sess", sb, sm), ("nat", nb_, nm)):
                nbk = e[f"{kind}_buckets"]
                if nbk:
                    cursor[kind] -= nbk
                    basearr[tid] = cursor[kind]
                    maskarr[tid] = nbk - 1
                    sliced_tids[kind].add(tid)
            mlm[tid] = ML_MODE_CODES[e.get("ml_mode", "inherit")]
            if e.get("ml_thresh") is not None:
                mlt[tid] = int(e["ml_thresh"])
            if e.get("vni") is not None:
                vni[tid] = int(e["vni"])
        # unsliced tenants (every tid not sliced above, tenant 0
        # included unless it registered a slice): base 0, masked to
        # the largest power of two inside the residual [0, cursor) so
        # they can never land in a slice. validate_tenancy_config
        # guarantees cursor > 0 whenever an unsliced tenant exists.
        for kind, maskarr in (("sess", sm), ("nat", nm)):
            free = cursor[kind]
            um = (1 << (free.bit_length() - 1)) - 1 if free > 0 else 0
            for tid in range(T):
                if tid not in sliced_tids[kind]:
                    maskarr[tid] = um
        self.tnt = {
            "tnt_pfx_net": net, "tnt_pfx_mask": mask, "tnt_pfx_id": pid,
            "tnt_rate": rate, "tnt_burst": burst,
            "tnt_sess_base": sb, "tnt_sess_mask": sm,
            "tnt_nat_base": nb_, "tnt_nat_mask": nm,
            "glb_ml_tnt_mode": mlm, "glb_ml_tnt_thresh": mlt,
            "tnt_vni": vni,
        }

    def set_tenant(self, tid: int, **kw) -> None:
        """Register (or replace) one tenant: prefixes, VNI, token
        bucket (``rate`` tokens/tick, ``burst`` capacity), session/NAT
        capacity slices (``sess_buckets``/``nat_buckets`` — power-of-2
        bucket counts; 0 = unsliced), the pump's WFQ ``weight``, and
        the per-tenant ML override (``ml_mode``/``ml_thresh``).
        Validated as a whole (vpp_tpu/tenancy/sched.py) so an
        oversubscribed slice or a bad prefix is refused BEFORE any
        staging mutates."""
        if getattr(self.config, "tenancy", "off") == "off":
            raise ValueError(
                "dataplane.tenancy is off — set_tenant requires "
                "tenancy: on (the tnt_* planes carry placeholder "
                "shapes otherwise)")
        from vpp_tpu.tenancy.sched import validate_tenancy_config

        merged = {t: dict(e) for t, e in self.tenants.items()}
        merged[int(tid)] = {"id": int(tid), **kw}
        entries = validate_tenancy_config(self.config,
                                          list(merged.values()))
        self.tenants = {e["id"]: e for e in entries}
        self._restage_tenants()
        if self._rec is not None:
            self._rec.set_tenant(int(tid), **kw)
        self._mark("tenant")

    def clear_tenants(self) -> None:
        """Back to the single default tenant (everything tenant 0,
        unsliced, unlimited)."""
        self.tenants = {}
        self._restage_tenants()
        if self._rec is not None:
            self._rec.clear_tenants()
        self._mark("tenant")

    def set_tenant_ml(self, tid: int, ml_mode: str = "inherit",
                      ml_thresh: Optional[int] = None) -> None:
        """Flip ONE tenant's ML mode/threshold without touching its
        other staging — marks only the "tenant" group, so the model's
        weight planes re-ship NOTHING (the ISSUE 14 satellite: tenants
        run different off|score|enforce modes against one staged
        model)."""
        if int(tid) not in self.tenants:
            raise ValueError(
                f"tenant {tid} not registered (set_tenant first)")
        e = dict(self.tenants[int(tid)])
        e["ml_mode"] = ml_mode
        e["ml_thresh"] = ml_thresh
        from vpp_tpu.tenancy.sched import validate_tenancy_config

        merged = {t: dict(x) for t, x in self.tenants.items()}
        merged[int(tid)] = e
        entries = validate_tenancy_config(self.config,
                                          list(merged.values()))
        self.tenants = {x["id"]: x for x in entries}
        self._restage_tenants()
        if self._rec is not None:
            self._rec.set_tenant_ml(int(tid), ml_mode, ml_thresh)
        self._mark("tenant")

    # --- interfaces ---
    def set_interface(
        self,
        if_index: int,
        if_type: InterfaceType,
        local_table: int = -1,
        apply_global: bool = False,
    ) -> None:
        self.if_type[if_index] = int(if_type)
        self.if_local_table[if_index] = local_table
        self.if_apply_global[if_index] = int(apply_global)
        if self._rec is not None:
            self._rec.set_interface(if_index, int(if_type), local_table,
                                    bool(apply_global))
        self._mark("if")

    def set_if_local_table(self, if_index: int, slot: int) -> None:
        """Point one interface at a local ACL table slot (-1 = none).
        The single mutation point for if_local_table outside
        set_interface — external writers must come through here so the
        'if' upload group gets marked dirty."""
        self.if_local_table[if_index] = slot
        if self._rec is not None:
            self._rec.set_if_local_table(if_index, slot)
        self._mark("if")

    # --- FIB ---
    def _mark_fib_slots(self, *plens: int) -> None:
        """One route mutation: the per-slot row arrays changed (they
        ship via the incremental blob or, fallback, in full) and the
        named prefix LENGTHS need their LPM plane restaged."""
        self._fib_dirty.update(_FIB_SLOT_FIELDS)
        if self.lpm_enabled:
            for plen in plens:
                if 0 <= plen <= 32:
                    self._lpm_dirty_lens.add(int(plen))
        self._mark("fib")

    def add_route(
        self,
        prefix: str,
        tx_if: int,
        disposition: Disposition,
        next_hop: int = 0,
        node_id: int = -1,
        slot: Optional[int] = None,
        snat: bool = False,
        group: Optional[int] = None,
    ) -> int:
        """Install one route. ``group`` names an ECMP next-hop group
        (set_nh_group) the route resolves through instead of the
        scalar next_hop/tx_if/node_id columns — which are still staged
        as given (the trace/debug fallback and the group's fail-closed
        documentation of intent)."""
        net = ipaddress.ip_network(prefix)
        if group is not None:
            gcap = self.fib_grp_nh.shape[0]
            if int(getattr(self.config, "fib_ecmp_groups", 0)) <= 0:
                raise ValueError(
                    "route names an ECMP group but "
                    "dataplane.fib_ecmp_groups is 0")
            if not (0 <= int(group) < gcap):
                raise ValueError(
                    f"ECMP group {group} out of range 0..{gcap - 1}")
        if slot is None:
            free = np.nonzero(self.fib_plen < 0)[0]
            if len(free) == 0:
                raise ValueError("FIB full")
            slot = int(free[0])
        old_plen = int(self.fib_plen[slot])
        mask = _mask_of(net.prefixlen)
        self.fib_prefix[slot] = int(net.network_address) & mask
        self.fib_mask[slot] = mask
        self.fib_plen[slot] = net.prefixlen
        self.fib_tx_if[slot] = tx_if
        self.fib_disp[slot] = int(disposition)
        self.fib_next_hop[slot] = next_hop
        self.fib_node_id[slot] = node_id
        self.fib_snat[slot] = int(snat)
        self.fib_grp[slot] = -1 if group is None else int(group)
        if self._rec is not None:
            self._rec.add_route(prefix, tx_if, int(disposition),
                                int(next_hop), int(node_id), bool(snat),
                                slot=slot, group=group)
        self._mark_fib_slots(old_plen, net.prefixlen)
        return slot

    def add_routes_np(self, nets: np.ndarray, plens: np.ndarray,
                      tx_if: np.ndarray, disp: np.ndarray,
                      next_hop=0, node_id=-1, snat=0, group=-1,
                      base_slot: int = 0) -> int:
        """Bulk route loader (the BGP full-feed path; ISSUE 15):
        vectorized writes of N routes into slots [base_slot,
        base_slot + N). Scalars broadcast; ``nets`` must already be
        masked networks. NOT journaled — a 1M-entry feed is adjacency
        state, not NB config (replay rebuilds it from the feed, the
        way VPP reloads its RIB). Returns the count staged."""
        n = len(nets)
        if base_slot + n > self.config.fib_slots:
            raise ValueError(
                f"{n} routes at base {base_slot} exceed fib_slots "
                f"{self.config.fib_slots}")
        grp = np.asarray(group, np.int32)
        if (grp >= 0).any():
            # the add_route group validation, vectorized: an
            # out-of-range id would be CLIPPED on-device onto a real
            # group and silently forward via its members
            gcap = self.fib_grp_nh.shape[0]
            if int(getattr(self.config, "fib_ecmp_groups", 0)) <= 0:
                raise ValueError(
                    "routes name ECMP groups but "
                    "dataplane.fib_ecmp_groups is 0")
            if int(grp.max()) >= gcap or int(grp.min()) < -1:
                raise ValueError(
                    f"ECMP group ids must be -1 (none) or in "
                    f"0..{gcap - 1}")
        plens = np.asarray(plens, np.int32)
        sl = slice(base_slot, base_slot + n)
        masks = np.array([_mask_of(int(p)) for p in range(33)],
                         np.uint32)[plens]
        old = self.fib_plen[sl]
        self.fib_prefix[sl] = np.asarray(nets, np.uint32) & masks
        self.fib_mask[sl] = masks
        self.fib_plen[sl] = plens
        self.fib_tx_if[sl] = np.asarray(tx_if, np.int32)
        self.fib_disp[sl] = np.asarray(disp, np.int32)
        self.fib_next_hop[sl] = np.asarray(next_hop, np.uint32)
        self.fib_node_id[sl] = np.asarray(node_id, np.int32)
        self.fib_snat[sl] = np.asarray(snat, np.int32)
        self.fib_grp[sl] = np.asarray(group, np.int32)
        touched = set(np.unique(plens).tolist())
        touched |= set(np.unique(old[old >= 0]).tolist())
        self._mark_fib_slots(*touched)
        return n

    def del_route(self, prefix: str) -> bool:
        net = ipaddress.ip_network(prefix)
        mask = _mask_of(net.prefixlen)
        want = int(net.network_address) & mask
        hit = np.nonzero(
            (self.fib_plen == net.prefixlen) & (self.fib_prefix == want)
        )[0]
        if len(hit) == 0:
            return False
        self.fib_plen[hit[0]] = -1
        if self._rec is not None:
            self._rec.del_route(prefix)
        self._mark_fib_slots(net.prefixlen)
        return True

    # --- ECMP next-hop groups (ops/fib.py resolve_fib_slot) ---
    def set_nh_group(self, gid: int, members) -> None:
        """Stage one ECMP group: ``members`` is a sequence of
        ``(next_hop_ip, tx_if, node_id)`` tuples. Way assignment is
        STICKY: surviving members keep the ways they already own (up
        to their rebalanced share), so member churn only remaps the
        flows it must — the stickiness contract tests pin
        (docs/ROUTING.md)."""
        c = self.config
        if int(getattr(c, "fib_ecmp_groups", 0)) <= 0:
            raise ValueError(
                "dataplane.fib_ecmp_groups is 0 — ECMP group tables "
                "carry placeholder shapes (raise the knob)")
        gcap, ways = self.fib_grp_nh.shape
        if not (0 <= int(gid) < gcap):
            raise ValueError(f"ECMP group {gid} out of range "
                             f"0..{gcap - 1}")
        gid = int(gid)
        mset = []
        for m in members:
            nh, tx, node = int(m[0]), int(m[1]), int(m[2])
            if (nh, tx, node) not in mset:
                mset.append((nh, tx, node))
        if not mset:
            raise ValueError(
                "ECMP group needs at least one member "
                "(del_nh_group removes a group)")
        if len(mset) > ways:
            raise ValueError(
                f"{len(mset)} distinct members exceed fib_ecmp_ways "
                f"{ways}")
        prev = self.nh_groups.get(gid)
        prev_assign = list(prev["assign"]) if prev else [None] * ways
        n = len(mset)
        target = [ways // n + (1 if i < ways % n else 0)
                  for i in range(n)]
        counts = [0] * n
        assign_i = [None] * ways
        # pass 1: surviving members keep their ways up to their share
        for w in range(ways):
            m = prev_assign[w]
            if m in mset:
                i = mset.index(m)
                if counts[i] < target[i]:
                    assign_i[w] = i
                    counts[i] += 1
        # pass 2: freed/new ways go to the most under-share member
        # (deterministic: ties by member order)
        for w in range(ways):
            if assign_i[w] is None:
                i = min(range(n), key=lambda j: (counts[j] - target[j], j))
                assign_i[w] = i
                counts[i] += 1
        assign = [mset[i] for i in assign_i]
        self.nh_groups[gid] = {"members": mset, "assign": assign}
        self.fib_grp_nh[gid] = np.array([m[0] for m in assign], np.uint32)
        self.fib_grp_tx_if[gid] = np.array([m[1] for m in assign], np.int32)
        self.fib_grp_node[gid] = np.array([m[2] for m in assign], np.int32)
        self.fib_grp_n[gid] = n
        if self._rec is not None:
            self._rec.set_nh_group(gid, [list(m) for m in mset])
        self._fib_dirty.update(("fib_grp_nh", "fib_grp_tx_if",
                                "fib_grp_node", "fib_grp_n"))
        self._mark("fib")

    def del_nh_group(self, gid: int) -> bool:
        """Remove one ECMP group. Routes still referencing it FAIL
        CLOSED on the device (fib_grp_n == 0 resolves as a no-route
        miss) until they are repointed — dropping beats forwarding to
        a withdrawn next-hop."""
        if int(gid) not in self.nh_groups:
            return False
        gid = int(gid)
        del self.nh_groups[gid]
        self.fib_grp_nh[gid] = 0
        self.fib_grp_tx_if[gid] = -1
        self.fib_grp_node[gid] = -1
        self.fib_grp_n[gid] = 0
        if self._rec is not None:
            self._rec.del_nh_group(gid)
        self._fib_dirty.update(("fib_grp_nh", "fib_grp_tx_if",
                                "fib_grp_node", "fib_grp_n"))
        self._mark("fib")
        return True

    # --- LPM plane staging (ops/lpm.py; ISSUE 15) ---
    def _restage_lpm(self) -> None:
        """Recompile the dirty per-length LPM planes from the per-slot
        FIB arrays: one vectorized pass per dirty length — select that
        length's slots, sort by (prefix, slot), keep the LOWEST slot
        per duplicate prefix (the dense argmax tie-break, so the two
        implementations stay bit-exact). Planes are strictly sorted
        after dedupe (`tools/lint.py --tables` pins it). Called lazily
        from host_arrays()/lpm_ok(); a no-op with nothing dirty."""
        if not self._lpm_dirty_lens or not self.lpm_enabled:
            self._lpm_dirty_lens.clear()
            return
        import time as _t

        from vpp_tpu.ops.lpm import LPM_PAD, lpm_field

        t0 = _t.perf_counter()
        for length in sorted(self._lpm_dirty_lens):
            cap = self.lpm_caps[length]
            slots = np.nonzero(self.fib_plen == length)[0]
            pfx = self.fib_prefix[slots]
            order = np.argsort(pfx, kind="stable")
            pfx, slots = pfx[order], slots[order]
            if len(pfx):
                keep = np.ones(len(pfx), bool)
                keep[1:] = pfx[1:] != pfx[:-1]
                pfx, slots = pfx[keep], slots[keep]
            n = len(pfx)
            self.lpm_counts[length] = n
            field = lpm_field(length)
            plane = np.zeros((2, cap), np.uint32)
            plane[0, :] = LPM_PAD
            nc = min(n, cap)   # overflow => lpm_ok() false, never read
            plane[0, :nc] = pfx[:nc]
            plane[1, :nc] = slots[:nc]
            self.lpm_planes[field] = plane
            self.lpm_cnt[length] = nc
            self._fib_dirty.add(field)
            # stride hint rows of this length (ops/lpm.py): the
            # insertion point of every top-bits bucket boundary, so
            # the device bisection starts inside ONE bucket
            b, off, _steps = self._lpm_layout[length]
            if off >= 0:
                bounds = (np.arange((1 << b) + 1, dtype=np.uint64)
                          << (32 - b))
                self.lpm_hint[off:off + (1 << b) + 1] = np.searchsorted(
                    pfx[:nc], bounds).astype(np.int32)
                self._fib_dirty.add("fib_lpm_hint")
        self._fib_dirty.add("fib_lpm_cnt")
        self._lpm_dirty_lens.clear()
        self.lpm_build_ms = (_t.perf_counter() - t0) * 1e3

    def lpm_ok(self) -> bool:
        """Whether the LPM implementation can serve THIS staged FIB:
        planes allocated, and every populated length fits its
        configured capacity (cap 0 = length not served). False falls
        the selection ladder back to dense — the BV ok=False pattern."""
        if not self.lpm_enabled:
            return False
        self._restage_lpm()
        caps = np.asarray(self.lpm_caps, np.int64)
        return bool((self.lpm_counts <= caps).all())

    def fib_route_count(self) -> int:
        """Live FIB routes staged (the fib_lpm_min_routes ladder input
        and the vpp_tpu_fib_routes gauge)."""
        return int(np.count_nonzero(self.fib_plen >= 0))

    # --- NAT ---
    def set_nat_mapping(
        self,
        slot: int,
        ext_ip: int,
        ext_port: int,
        proto: int,
        backends: Sequence[Tuple[int, int, int]],  # (ip, port, weight)
        boff: int,
        self_snat: bool = False,
    ) -> None:
        """Install a DNAT static mapping with weighted backends at ``slot``,
        placing backends at ``boff`` in the backend arrays."""
        if boff + len(backends) > self.config.nat_backends:
            raise ValueError("NAT backend arrays full")
        cum = 0
        for j, (bip, bport, w) in enumerate(backends):
            cum += w
            self.natb_ip[boff + j] = bip
            self.natb_port[boff + j] = bport
            self.natb_cumw[boff + j] = cum
        self.nat_ext_ip[slot] = ext_ip
        self.nat_ext_port[slot] = ext_port
        self.nat_proto[slot] = proto
        self.nat_boff[slot] = boff
        self.nat_bcnt[slot] = len(backends)
        self.nat_total_w[slot] = cum
        self.nat_self_snat[slot] = int(self_snat)
        if self._rec is not None:
            self._rec.set_nat_mapping(
                slot, int(ext_ip), int(ext_port), int(proto),
                [(int(a), int(b), int(w)) for a, b, w in backends],
                int(boff), bool(self_snat))
        self._mark("nat")

    def clear_nat(self) -> None:
        self.nat_bcnt[:] = 0
        if self._rec is not None:
            self._rec.clear_nat()
        self._mark("nat")

    def set_snat_ip(self, ip: int) -> None:
        """Set the node's SNAT address (0 disables SNAT). The single
        mutation point for ``nat_snat_ip`` — agent bootstrap and the
        service configurator both route through here."""
        self.nat_snat_ip = np.uint32(ip)
        if self._rec is not None:
            self._rec.set_snat_ip(int(ip))
        self._mark("nat")

    # --- VXLAN overlay + service LB (ISSUE 19; docs/OVERLAY.md) ---
    def set_vtep_ip(self, ip: int) -> None:
        """Set the node's local VTEP address (the overlay stage's
        decap admission filter and encap outer source). Rides the tiny
        "config" upload group — a VTEP move ships bytes, not planes."""
        self.ovl_vtep_ip = np.uint32(ip)
        if self._rec is not None:
            self._rec.set_vtep_ip(int(ip))
        self._mark("config")

    def _restage_svc(self) -> None:
        """Compile the service registry into the "svc" upload-group
        arrays. VIP rows are sorted by (ip, port, proto) — the
        --tables invariant — and padding rows stay all-zero with
        bk_n 0, so they can never serve (the half-applied guard: a
        row only matches once its whole backend set is staged).
        Deterministic: the same registry always compiles
        byte-identical arrays (the _restage_tenants discipline)."""
        V, B = svc_capacity(self.config)
        z = np.zeros
        vip_ip = z(V, np.uint32)
        vip_port = z(V, np.int32)
        vip_proto = z(V, np.int32)
        vip_snat = z(V, np.int32)
        bk_n = z(V, np.int32)
        bk_ip = z((V, B), np.uint32)
        bk_port = z((V, B), np.int32)
        for r, key in enumerate(sorted(self.services)):
            e = self.services[key]
            ip, port, proto = key
            vip_ip[r] = ip
            vip_port[r] = port
            vip_proto[r] = proto
            vip_snat[r] = int(e["self_snat"])
            bk_n[r] = len(e["members"])
            bk_ip[r] = np.array([m[0] for m in e["assign"]], np.uint32)
            bk_port[r] = np.array([m[1] for m in e["assign"]], np.int32)
        self.svc = {
            "svc_vip_ip": vip_ip, "svc_vip_port": vip_port,
            "svc_vip_proto": vip_proto, "svc_vip_snat": vip_snat,
            "svc_bk_n": bk_n, "svc_bk_ip": bk_ip,
            "svc_bk_port": bk_port,
        }

    def set_service(self, vip_ip: int, port: int, proto: int,
                    backends: Sequence[Tuple[int, int, int]],
                    self_snat: bool = False) -> None:
        """Stage (or replace) one service VIP's backend set:
        ``backends`` is a sequence of ``(ip, port, weight)`` tuples.
        Way assignment is STICKY per service (the set_nh_group fill,
        weighted by largest remainder): surviving backends keep the
        ways they own up to their rebalanced share, so a rolling
        replacement only remaps the flows it must. Validates
        COMPLETELY before any staging mutates — a refused backend set
        leaves the previous one serving, and a half-applied set can
        never reach the device (the _fold_ml clean-refusal
        contract)."""
        c = self.config
        if int(getattr(c, "svc_vips", 0)) <= 0:
            raise ValueError(
                "dataplane.svc_vips is 0 — the svc planes carry "
                "placeholder shapes (raise the knob)")
        V, B = svc_capacity(c)
        if not (1 <= int(port) <= 65535):
            raise ValueError(
                f"service port must be in 1..65535 (exact match), "
                f"got {port}")
        key = (int(vip_ip) & 0xFFFFFFFF, int(port), int(proto))
        mset = []
        seen = set()
        for m in backends:
            bip, bport, w = int(m[0]), int(m[1]), int(m[2])
            if w <= 0:
                raise ValueError(
                    f"backend weight must be > 0, got {w}")
            if (bip, bport) not in seen:
                seen.add((bip, bport))
                mset.append((bip, bport, w))
        if not mset:
            raise ValueError(
                "service needs at least one backend "
                "(del_service removes a VIP)")
        if len(mset) > B:
            raise ValueError(
                f"{len(mset)} distinct backends exceed "
                f"svc_backend_ways {B}")
        if key not in self.services and len(self.services) >= V:
            raise ValueError(
                f"service table full ({V} VIP rows — raise "
                f"dataplane.svc_vips)")
        prev = self.services.get(key)
        prev_assign = list(prev["assign"]) if prev else [None] * B
        # weighted way targets by largest remainder (deterministic:
        # remainder ties break by member order)
        total_w = sum(m[2] for m in mset)
        raw = [B * m[2] / total_w for m in mset]
        target = [int(r) for r in raw]
        rest = B - sum(target)
        order = sorted(range(len(mset)),
                       key=lambda i: (-(raw[i] - target[i]), i))
        for i in order[:rest]:
            target[i] += 1
        counts = [0] * len(mset)
        assign_i: list = [None] * B
        by_ep = {(m[0], m[1]): i for i, m in enumerate(mset)}
        # pass 1: surviving backends keep their ways up to their share
        # (matched by endpoint, so a weight change alone never evicts)
        for w in range(B):
            pm = prev_assign[w]
            i = by_ep.get((pm[0], pm[1])) if pm is not None else None
            if i is not None and counts[i] < target[i]:
                assign_i[w] = i
                counts[i] += 1
        # pass 2: freed/new ways go to the most under-share backend
        for w in range(B):
            if assign_i[w] is None:
                i = min(range(len(mset)),
                        key=lambda j: (counts[j] - target[j], j))
                assign_i[w] = i
                counts[i] += 1
        assign = [mset[i] for i in assign_i]
        self.services[key] = {"members": mset, "assign": assign,
                              "self_snat": bool(self_snat)}
        self._restage_svc()
        if self._rec is not None:
            self._rec.set_service(key[0], key[1], key[2],
                                  [list(m) for m in mset],
                                  bool(self_snat))
        self._mark("svc")

    def del_service(self, vip_ip: int, port: int, proto: int) -> bool:
        """Remove one service VIP. Flows established to its backends
        keep translating through the NAT-session table until they
        age out; NEW flows to the VIP stop matching immediately."""
        key = (int(vip_ip) & 0xFFFFFFFF, int(port), int(proto))
        if key not in self.services:
            return False
        del self.services[key]
        self._restage_svc()
        if self._rec is not None:
            self._rec.del_service(key[0], key[1], key[2])
        self._mark("svc")
        return True

    def clear_services(self) -> None:
        self.services = {}
        self._restage_svc()
        if self._rec is not None:
            self._rec.clear_services()
        self._mark("svc")

    # staging-state array attributes (everything a mutator can touch,
    # besides the dict-of-arrays acl/glb and the scalars handled
    # explicitly in state_snapshot/state_restore)
    _STATE_ARRAYS = (
        "acl_nrules", "if_type", "if_local_table", "if_apply_global",
        "fib_prefix", "fib_mask", "fib_plen", "fib_tx_if", "fib_disp",
        "fib_next_hop", "fib_node_id", "fib_snat", "fib_grp",
        "fib_grp_nh", "fib_grp_tx_if", "fib_grp_node", "fib_grp_n",
        "lpm_cnt", "lpm_counts", "lpm_hint",
        "nat_ext_ip", "nat_ext_port", "nat_proto", "nat_boff", "nat_bcnt",
        "nat_total_w", "nat_self_snat", "natb_ip", "natb_port",
        "natb_cumw",
    )

    def state_snapshot(self) -> dict:
        """Copy of the whole staged (host) configuration — cheap numpy
        copies, no device state. Pair with state_restore for
        transactional rollback (pipeline/txn.py)."""
        # settle lazy LPM staging first so the snapshot's planes are
        # consistent with its per-slot arrays (restore clears the
        # dirty-length set on that assumption)
        self._restage_lpm()
        return {
            "arrays": {k: getattr(self, k).copy()
                       for k in self._STATE_ARRAYS},
            "acl": {k: v.copy() for k, v in self.acl.items()},
            "acl_bv": {k: v.copy() for k, v in self.acl_bv.items()},
            "acl_bv_ok": self.acl_bv_ok.copy(),
            "glb": {k: v.copy() for k, v in self.glb.items()},
            "glb_nrules": self.glb_nrules,
            "glb_mxu": self.glb_mxu,       # replaced wholesale, never
            "glb_bv": self.glb_bv,         # mutated in place
            "ml": self.ml,                 # replaced wholesale too
            "ml_kind": self.ml_kind,
            "tnt": self.tnt,               # replaced wholesale
            "tenants": {t: dict(e) for t, e in self.tenants.items()},
            "lpm_planes": {k: v.copy()
                           for k, v in self.lpm_planes.items()},
            "nh_groups": {g: {"members": list(e["members"]),
                              "assign": list(e["assign"])}
                          for g, e in self.nh_groups.items()},
            "nat_snat_ip": self.nat_snat_ip,
            "ovl_vtep_ip": self.ovl_vtep_ip,
            "svc": self.svc,               # replaced wholesale
            "services": {k: {"members": list(e["members"]),
                             "assign": list(e["assign"]),
                             "self_snat": e["self_snat"]}
                         for k, e in self.services.items()},
            "dirty": set(self._dirty),
            "rec_ops": list(self._rec.ops) if self._rec is not None else None,
        }

    def state_restore(self, snap: dict) -> None:
        """Restore a state_snapshot (in-place array writes so existing
        references — e.g. cluster builders — stay valid)."""
        for k, v in snap["arrays"].items():
            getattr(self, k)[...] = v
        for k, v in snap["acl"].items():
            self.acl[k][...] = v
        for k, v in snap["acl_bv"].items():
            self.acl_bv[k][...] = v
        self.acl_bv_ok[...] = snap["acl_bv_ok"]
        for k, v in snap["glb"].items():
            self.glb[k][...] = v
        self.glb_nrules = snap["glb_nrules"]
        self.glb_mxu = snap["glb_mxu"]
        self.glb_bv = snap["glb_bv"]
        self.ml = snap["ml"]
        self.ml_kind = snap["ml_kind"]
        self.tnt = snap["tnt"]
        self.tenants = {t: dict(e) for t, e in snap["tenants"].items()}
        for k, v in snap["lpm_planes"].items():
            self.lpm_planes[k][...] = v
        self.nh_groups = {g: {"members": list(e["members"]),
                              "assign": list(e["assign"])}
                          for g, e in snap["nh_groups"].items()}
        # restored planes are content-consistent with the restored
        # per-slot arrays (both came from one snapshot), but the device
        # cache may hold the rolled-back commit — re-ship every fib
        # field conservatively, and force a full per-slot upload
        self._lpm_dirty_lens = set()
        self._fib_dirty = set(_UPLOAD_GROUPS["fib"])
        self._fib_prev = None
        # the identity-diff caches describe the pre-restore rule list;
        # the next set_global_table must full-recompile. The BV device
        # cache may hold planes of the rolled-back commit — every BV
        # field re-uploads conservatively.
        self._glb_rules_ref = None
        self._glb_rows = None
        self._glb_bad = None
        self._bv_cols = None
        self._bv_dirty = set(_UPLOAD_GROUPS["glb_bv"])
        self.nat_snat_ip = snap["nat_snat_ip"]
        self.ovl_vtep_ip = snap["ovl_vtep_ip"]
        self.svc = snap["svc"]
        self.services = {k: {"members": list(e["members"]),
                             "assign": list(e["assign"]),
                             "self_snat": e["self_snat"]}
                         for k, e in snap["services"].items()}
        # the device cache may hold the rolled-back svc commit — force
        # the next upload full (the _fib_prev conservatism)
        self._svc_prev = None
        # union, not replace: groups the rolled-back ops touched stay
        # dirty — a redundant re-upload of identical data is harmless,
        # a stale device cache is not
        self._dirty |= set(snap["dirty"])
        if self._rec is not None and snap.get("rec_ops") is not None:
            self._rec.ops[:] = snap["rec_ops"]

    # --- device upload ---
    def host_arrays(self) -> Dict[str, np.ndarray]:
        """The staged configuration as numpy arrays keyed by
        DataplaneTables field name (everything except session state).
        Used directly by to_device() and, node-stacked, by the cluster
        data plane (vpp_tpu.parallel.cluster). Settles the lazy LPM
        plane staging first (dirty lengths recompile here, once)."""
        self._restage_lpm()
        return dict(
            acl_src_net=self.acl["src_net"],
            acl_src_mask=self.acl["src_mask"],
            acl_dst_net=self.acl["dst_net"],
            acl_dst_mask=self.acl["dst_mask"],
            acl_proto=self.acl["proto"],
            acl_sport_lo=self.acl["sport_lo"],
            acl_sport_hi=self.acl["sport_hi"],
            acl_dport_lo=self.acl["dport_lo"],
            acl_dport_hi=self.acl["dport_hi"],
            acl_action=self.acl["action"],
            acl_nrules=self.acl_nrules,
            acl_bv_bnd_src=self.acl_bv["bnd_src"],
            acl_bv_bnd_dst=self.acl_bv["bnd_dst"],
            acl_bv_bnd_sport=self.acl_bv["bnd_sport"],
            acl_bv_bnd_dport=self.acl_bv["bnd_dport"],
            acl_bv_nbnd=self.acl_bv["nbnd"],
            acl_bv_src=self.acl_bv["src"],
            acl_bv_dst=self.acl_bv["dst"],
            acl_bv_sport=self.acl_bv["sport"],
            acl_bv_dport=self.acl_bv["dport"],
            acl_bv_proto=self.acl_bv["proto"],
            glb_src_net=self.glb["src_net"],
            glb_src_mask=self.glb["src_mask"],
            glb_dst_net=self.glb["dst_net"],
            glb_dst_mask=self.glb["dst_mask"],
            glb_proto=self.glb["proto"],
            glb_sport_lo=self.glb["sport_lo"],
            glb_sport_hi=self.glb["sport_hi"],
            glb_dport_lo=self.glb["dport_lo"],
            glb_dport_hi=self.glb["dport_hi"],
            glb_action=self.glb["action"],
            glb_nrules=np.int32(self.glb_nrules),
            glb_mxu_coeff=self.glb_mxu.coeff,
            glb_mxu_k=self.glb_mxu.k,
            glb_mxu_act=self.glb_mxu.act,
            glb_bv_bnd_src=self.glb_bv.bnd_src,
            glb_bv_bnd_dst=self.glb_bv.bnd_dst,
            glb_bv_bnd_sport=self.glb_bv.bnd_sport,
            glb_bv_bnd_dport=self.glb_bv.bnd_dport,
            glb_bv_nbnd=self.glb_bv.nbnd,
            glb_bv_src=self.glb_bv.bm_src,
            glb_bv_dst=self.glb_bv.bm_dst,
            glb_bv_sport=self.glb_bv.bm_sport,
            glb_bv_dport=self.glb_bv.bm_dport,
            glb_bv_proto=self.glb_bv.bm_proto,
            **self.ml,
            **self.tnt,
            if_type=self.if_type,
            if_local_table=self.if_local_table,
            if_apply_global=self.if_apply_global,
            fib_prefix=self.fib_prefix,
            fib_mask=self.fib_mask,
            fib_plen=self.fib_plen,
            fib_tx_if=self.fib_tx_if,
            fib_disp=self.fib_disp,
            fib_next_hop=self.fib_next_hop,
            fib_node_id=self.fib_node_id,
            fib_snat=self.fib_snat,
            fib_grp=self.fib_grp,
            **self.lpm_planes,
            fib_lpm_cnt=self.lpm_cnt,
            fib_lpm_hint=self.lpm_hint,
            fib_grp_nh=self.fib_grp_nh,
            fib_grp_tx_if=self.fib_grp_tx_if,
            fib_grp_node=self.fib_grp_node,
            fib_grp_n=self.fib_grp_n,
            sess_max_age=np.int32(self.config.sess_max_age),
            nat_ext_ip=self.nat_ext_ip,
            nat_ext_port=self.nat_ext_port,
            nat_proto=self.nat_proto,
            nat_boff=self.nat_boff,
            nat_bcnt=self.nat_bcnt,
            nat_total_w=self.nat_total_w,
            nat_self_snat=self.nat_self_snat,
            natb_ip=self.natb_ip,
            natb_port=self.natb_port,
            natb_cumw=self.natb_cumw,
            nat_snat_ip=self.nat_snat_ip,
            ovl_vtep_ip=self.ovl_vtep_ip,
            **self.svc,
        )

    def to_device(self, sessions=None) -> DataplaneTables:
        """Produce the immutable device pytree. If ``sessions`` (a previous
        epoch's tables) is given, its live session arrays are carried over.

        ``sessions`` may also be a ``{field: host array}`` mapping of
        SESSION_FIELDS (the crash-consistent snapshot restore path,
        pipeline/snapshot.py): the arrays are uploaded and a restarted
        agent's established flows come back warm. Shapes must match the
        config geometry — the snapshot loader already refused a
        geometry mismatch, so a bad shape here is a programming error
        and raises.

        Incremental: only fields of groups mutated since the previous
        call are re-uploaded; clean groups reuse the cached device
        arrays (each upload is a host→device transfer — a full RPC
        round trip on remote transports — and the bit-plane matrix
        alone is several MB at 10k rules). Do NOT donate a tables
        pytree produced here into a jit (donate_argnums) if you will
        swap again afterwards: donation invalidates the cached buffers
        the next swap would reuse."""
        if isinstance(sessions, dict):
            missing = set(SESSION_FIELDS) - set(sessions)
            if missing:
                raise ValueError(
                    f"restored session state missing fields: "
                    f"{sorted(missing)}")
            shapes = session_shapes(self.config)
            for f, arr in sessions.items():
                if tuple(np.shape(arr)) != shapes[f]:
                    raise ValueError(
                        f"restored session field {f!r} shape "
                        f"{tuple(np.shape(arr))} != configured "
                        f"{shapes[f]}")
            sess = {f: jnp.asarray(np.asarray(sessions[f], dt))
                    for f, dt in SESSION_FIELDS.items()}
            # telemetry + tenancy state restart cold on a snapshot
            # restore by design: the snapshot format carries
            # SESSION_FIELDS only, and measurement state from before a
            # crash would mislabel the post-restart regime (the token
            # buckets refill within one step)
            tel = zero_telemetry_device(self.config)
            tnt_st = zero_tenancy_state_device(self.config)
            fib_st = zero_fib_state_device(self.config)
        elif sessions is not None:
            # carry-over is BY REFERENCE: the live device arrays flow
            # into the new epoch untouched — at 10M slots the session
            # state is ~100s of MB and must never re-ship on a swap.
            # The telemetry planes (ops/telemetry.py) and the tenancy
            # state (token buckets + accounting planes, ISSUE 14) ride
            # the same carry: an epoch swap must not reset them.
            sess = {f: getattr(sessions, f) for f in SESSION_FIELDS}
            tel = {f: getattr(sessions, f) for f in TELEMETRY_FIELDS}
            tnt_st = {f: getattr(sessions, f)
                      for f in TENANCY_STATE_FIELDS}
            fib_st = {f: getattr(sessions, f) for f in FIB_STATE_FIELDS}
        else:
            # device-side zero fill, not a host upload of zeros
            sess = zero_sessions_device(self.config)
            tel = zero_telemetry_device(self.config)
            tnt_st = zero_tenancy_state_device(self.config)
            fib_st = zero_fib_state_device(self.config)
        host_np = self.host_arrays()
        host = {}
        glb_full = False
        self.fib_last_shipped = False
        for group, fields in _UPLOAD_GROUPS.items():
            dirty = group in self._dirty
            if group == "fib":
                self._upload_fib(host, host_np, fields, dirty)
                continue
            if group == "svc":
                self._upload_svc(host, host_np, fields, dirty)
                continue
            if group == "glb_bv":
                # per-dimension-plane upload: only planes compile_bv
                # rebuilt since the last to_device re-ship (a port-only
                # churn keeps the multi-MB address bitmaps cached);
                # a field with no cache entry always uploads
                for name in fields:
                    if (dirty and name in self._bv_dirty) \
                            or name not in self._dev_cache:
                        self._dev_cache[name] = jnp.asarray(host_np[name])
                    host[name] = self._dev_cache[name]
                self._bv_dirty.clear()
                continue
            if group == "glb" and dirty:
                if self._glb_incremental(host_np):
                    # changed row/column BLOCKS were scattered into the
                    # cached device arrays with one blob upload — the
                    # multi-MB full-table re-upload at 10k rules is
                    # skipped
                    dirty = False
                else:
                    glb_full = True
            for name in fields:
                if dirty or name not in self._dev_cache:
                    self._dev_cache[name] = jnp.asarray(host_np[name])
                host[name] = self._dev_cache[name]
        if glb_full:
            # diff base refreshed only AFTER the full upload above
            # completed — refreshing before a device call that then
            # fails would desync the base and make a retried commit
            # no-op while the device serves stale rules
            self._set_glb_prev(host_np)
        self._dirty.clear()
        return DataplaneTables(**host, **sess, **tel, **tnt_st,
                               **fib_st)

    def _set_glb_prev(self, host_np: Dict[str, np.ndarray]) -> None:
        """Record the diff base for incremental glb commits. The ROW
        arrays are COPIED: state_restore writes into the live glb
        arrays in place, so a reference would alias the base with
        whatever a later rollback restores and a subsequent diff would
        see 'no change' against content the device never received. The
        bit-plane arrays are safe references (set_global_table and
        state_restore both replace the MxuTable wholesale)."""
        prev = {f: host_np[f].copy() for f in _GLB_ROW_FIELDS}
        for f in ("glb_mxu_coeff", "glb_mxu_k", "glb_mxu_act",
                  "glb_nrules"):
            prev[f] = host_np[f]
        self._glb_prev = prev

    def _glb_incremental(self, host_np: Dict[str, np.ndarray]) -> bool:
        """Try an incremental device update of the global-table group:
        diff against the last-uploaded host arrays, and when the
        changes confine to a block, upload ONE packed blob and scatter
        it into the cached device arrays (see _glb_update_fn). Returns
        True when the device cache now holds the new epoch (the caller
        skips the full re-upload); False falls back to full upload. The
        diff base refreshes ONLY on success — on the False path the
        caller must refresh it after the full upload completes
        (to_device does), so a failed device call never desyncs it."""
        from vpp_tpu.ops.acl_mxu import PLANES

        prev = self._glb_prev
        if prev is None or any(
            f not in self._dev_cache for f in _UPLOAD_GROUPS["glb"]
        ):
            return False
        n_rows = host_np["glb_action"].shape[0]
        n_cols = host_np["glb_mxu_k"].shape[0]
        changed_r = np.zeros(n_rows, bool)
        for f in _GLB_ROW_FIELDS:
            changed_r |= prev[f] != host_np[f]
        changed_c = (prev["glb_mxu_k"] != host_np["glb_mxu_k"]) \
            | (prev["glb_mxu_act"] != host_np["glb_mxu_act"]) \
            | np.any(prev["glb_mxu_coeff"] != host_np["glb_mxu_coeff"],
                     axis=0)
        blk_r = _block_of(changed_r, n_rows)
        blk_c = _block_of(changed_c, n_cols)
        if blk_r is None and blk_c is None:
            # content-identical commit (e.g. rolled-back txn): only the
            # rule-count scalar may differ
            if int(prev["glb_nrules"]) != int(host_np["glb_nrules"]):
                self._dev_cache["glb_nrules"] = jnp.asarray(
                    host_np["glb_nrules"]
                )
            self._set_glb_prev(host_np)
            return True
        blk_r = blk_r or (0, min(256, n_rows))
        blk_c = blk_c or (0, min(256, n_cols))
        lo_r, w_r = blk_r
        lo_c, w_c = blk_c
        if w_r >= n_rows or w_c >= n_cols:
            return False  # change spans the table: full upload is best
        blob = np.empty(10 * w_r + 2 * w_c + PLANES * w_c, np.int32)
        for i, f in enumerate(_GLB_ROW_FIELDS):
            blob[i * w_r:(i + 1) * w_r] = \
                host_np[f][lo_r:lo_r + w_r].view(np.int32)
        base = 10 * w_r
        blob[base:base + w_c] = \
            host_np["glb_mxu_k"][lo_c:lo_c + w_c].view(np.int32)
        blob[base + w_c:base + 2 * w_c] = \
            host_np["glb_mxu_act"][lo_c:lo_c + w_c]
        blob[base + 2 * w_c:] = np.ascontiguousarray(
            host_np["glb_mxu_coeff"][:, lo_c:lo_c + w_c]
        ).reshape(-1).view(np.int32)
        fn = _glb_update_fn(w_r, w_c, PLANES)
        new_rows, new_k, new_act, new_coeff = fn(
            [self._dev_cache[f] for f in _GLB_ROW_FIELDS],
            self._dev_cache["glb_mxu_k"],
            self._dev_cache["glb_mxu_act"],
            self._dev_cache["glb_mxu_coeff"],
            jnp.asarray(blob), lo_r, lo_c,
        )
        for f, arr in zip(_GLB_ROW_FIELDS, new_rows):
            self._dev_cache[f] = arr
        self._dev_cache["glb_mxu_k"] = new_k
        self._dev_cache["glb_mxu_act"] = new_act
        self._dev_cache["glb_mxu_coeff"] = new_coeff
        self._dev_cache["glb_nrules"] = jnp.asarray(host_np["glb_nrules"])
        # base refreshed only now — after every device call succeeded
        self._set_glb_prev(host_np)
        return True

    # --- FIB upload (per-length planes + incremental slot blob) ---
    def _upload_fib(self, host: Dict[str, object],
                    host_np: Dict[str, np.ndarray],
                    fields: Tuple[str, ...], dirty: bool) -> None:
        """The "fib" group's to_device body (ISSUE 15): per-slot row
        arrays go through the incremental scatter-blob path when the
        commit's changes confine to a block (a route flap ships a
        few-KB blob, not 9 full columns); the per-length LPM planes
        and ECMP tables re-ship only when ``_fib_dirty`` names them —
        every other plane keeps its cached device-array identity.
        Records ``fib_upload`` for `show fib` / fib_bench."""
        import time as _t

        t0 = _t.perf_counter()
        shipped = []
        blob_bytes = 0
        slot_inc = False
        if dirty:
            blob_bytes = self._fib_incremental(host_np)
            slot_inc = blob_bytes is not None
        for name in fields:
            if name in _FIB_SLOT_FIELDS and slot_inc:
                # the blob already scattered this field's block into
                # the cached device array
                host[name] = self._dev_cache[name]
                continue
            if (dirty and name in self._fib_dirty) \
                    or name not in self._dev_cache:
                self._dev_cache[name] = jnp.asarray(host_np[name])
                shipped.append(name)
            host[name] = self._dev_cache[name]
        if dirty and not slot_inc:
            # full per-slot upload above: refresh the diff base only
            # after every device transfer succeeded (the glb rule)
            self._set_fib_prev(host_np)
        if dirty:
            self.fib_last_shipped = True
            self.fib_upload = {
                "fields": tuple(shipped),
                "blob_bytes": int(blob_bytes or 0),
                "bytes": int(sum(host_np[f].nbytes for f in shipped)
                             + (blob_bytes or 0)),
                "ms": (_t.perf_counter() - t0) * 1e3,
            }
            self._fib_dirty.clear()

    def _set_fib_prev(self, host_np: Dict[str, np.ndarray]) -> None:
        """Record the per-slot diff base (COPIES — state_restore
        writes the live arrays in place, the _set_glb_prev rationale)."""
        self._fib_prev = {f: host_np[f].copy() for f in _FIB_SLOT_FIELDS}

    def _fib_incremental(self, host_np: Dict[str, np.ndarray]):
        """Try an incremental device update of the per-slot FIB rows:
        diff against the last-uploaded arrays; when the changes
        confine to a block, upload ONE packed blob and scatter it into
        the cached device arrays (_fib_update_fn). Returns the blob's
        byte count on success (0 = content-identical commit), None to
        fall back to a full upload. The diff base refreshes only on
        success — a failed device call never desyncs it."""
        prev = self._fib_prev
        if prev is None or any(
            f not in self._dev_cache for f in _FIB_SLOT_FIELDS
        ):
            return None
        n = host_np["fib_plen"].shape[0]
        changed = np.zeros(n, bool)
        for f in _FIB_SLOT_FIELDS:
            changed |= prev[f] != host_np[f]
        blk = _block_of(changed, n)
        if blk is None:
            return 0   # content-identical commit: nothing to ship
        lo, w = blk
        if w >= n:
            return None  # change spans the table: full upload is best
        nf = len(_FIB_SLOT_FIELDS)
        blob = np.empty(nf * w, np.int32)
        for i, f in enumerate(_FIB_SLOT_FIELDS):
            blob[i * w:(i + 1) * w] = host_np[f][lo:lo + w].view(np.int32)
        fn = _fib_update_fn(w)
        new_rows = fn([self._dev_cache[f] for f in _FIB_SLOT_FIELDS],
                      jnp.asarray(blob), lo)
        for f, arr in zip(_FIB_SLOT_FIELDS, new_rows):
            self._dev_cache[f] = arr
        self._set_fib_prev(host_np)
        return blob.nbytes

    # --- service-plane upload (incremental VIP-row blob; ISSUE 19) --
    def _upload_svc(self, host: Dict[str, object],
                    host_np: Dict[str, np.ndarray],
                    fields: Tuple[str, ...], dirty: bool) -> None:
        """The "svc" group's to_device body (the _upload_fib twin):
        changed VIP rows go through the incremental scatter-blob path
        when they confine to a block — a rolling backend replacement
        ships a few-KB blob, never the full planes, and NEVER any
        other group's bytes. Records ``svc_upload`` for
        `show services` / overlay_bench's svc_churn_bytes."""
        import time as _t

        t0 = _t.perf_counter()
        shipped = []
        blob_bytes = 0
        inc = False
        if dirty:
            blob_bytes = self._svc_incremental(host_np)
            inc = blob_bytes is not None
        for name in fields:
            if inc:
                host[name] = self._dev_cache[name]
                continue
            if dirty or name not in self._dev_cache:
                self._dev_cache[name] = jnp.asarray(host_np[name])
                shipped.append(name)
            host[name] = self._dev_cache[name]
        if dirty and not inc:
            # full upload above: refresh the diff base only after
            # every device transfer succeeded (the glb/fib rule)
            self._set_svc_prev(host_np)
        if dirty:
            self.svc_last_shipped = True
            self.svc_upload = {
                "fields": tuple(shipped),
                "blob_bytes": int(blob_bytes or 0),
                "bytes": int(sum(host_np[f].nbytes for f in shipped)
                             + (blob_bytes or 0)),
                "ms": (_t.perf_counter() - t0) * 1e3,
            }

    def _set_svc_prev(self, host_np: Dict[str, np.ndarray]) -> None:
        """Record the svc diff base (safe references — _restage_svc
        replaces the staging arrays wholesale, never in place)."""
        self._svc_prev = {f: host_np[f]
                          for f in _SVC_1D_FIELDS + _SVC_2D_FIELDS}

    def _svc_incremental(self, host_np: Dict[str, np.ndarray]):
        """Try an incremental device update of the service planes:
        diff VIP rows against the last-uploaded arrays; when the
        changes confine to a row block, upload ONE packed blob and
        scatter it into the cached device arrays (_svc_update_fn).
        Returns the blob's byte count on success (0 =
        content-identical commit), None to fall back to a full
        upload."""
        prev = self._svc_prev
        all_fields = _SVC_1D_FIELDS + _SVC_2D_FIELDS
        if prev is None or any(
            f not in self._dev_cache for f in all_fields
        ):
            return None
        V, B = host_np["svc_bk_ip"].shape
        changed = np.zeros(V, bool)
        for f in _SVC_1D_FIELDS:
            changed |= prev[f] != host_np[f]
        for f in _SVC_2D_FIELDS:
            changed |= np.any(prev[f] != host_np[f], axis=1)
        idx = np.nonzero(changed)[0]
        if len(idx) == 0:
            return 0   # content-identical commit: nothing to ship
        # _block_of's 256-row floor suits rule/FIB tables; VIP tables
        # are small, so the blob ladder starts at 8 rows (x4 steps)
        lo, hi = int(idx[0]), int(idx[-1]) + 1
        w = 8
        while w < hi - lo:
            w *= 4
        if w >= V:
            return None  # change spans the table: full upload is best
        lo = min(lo, V - w)
        n1 = len(_SVC_1D_FIELDS)
        blob = np.empty(n1 * w + len(_SVC_2D_FIELDS) * w * B, np.int32)
        for i, f in enumerate(_SVC_1D_FIELDS):
            blob[i * w:(i + 1) * w] = host_np[f][lo:lo + w].view(np.int32)
        base = n1 * w
        for i, f in enumerate(_SVC_2D_FIELDS):
            blob[base + i * w * B:base + (i + 1) * w * B] = \
                np.ascontiguousarray(
                    host_np[f][lo:lo + w]).reshape(-1).view(np.int32)
        fn = _svc_update_fn(w, B)
        new_rows, new_grids = fn(
            [self._dev_cache[f] for f in _SVC_1D_FIELDS],
            [self._dev_cache[f] for f in _SVC_2D_FIELDS],
            jnp.asarray(blob), lo,
        )
        for f, arr in zip(_SVC_1D_FIELDS, new_rows):
            self._dev_cache[f] = arr
        for f, arr in zip(_SVC_2D_FIELDS, new_grids):
            self._dev_cache[f] = arr
        self._set_svc_prev(host_np)
        return blob.nbytes
