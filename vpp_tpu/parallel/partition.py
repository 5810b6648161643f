"""Declarative partition-rule layer: name-regex → PartitionSpec (ISSUE 12).

Every DataplaneTables field gets its mesh placement from ONE ordered
rule list — the ``match_partition_rules`` / ``parameter_spec_from_name``
pattern (SNIPPETS.md [1]/[2]) applied to the data plane's table pytree
instead of a model's parameters. First match wins; a field no rule
matches is an ERROR (``PartitionError``), never a silent replicate —
``spec_manifest()`` names every field's spec and the rule that assigned
it, and the ``--partitions`` lint pass (tools/analysis/registries.py)
fails tier-1 on an unmatched new field or a stale rule matching
nothing.

The shipped rule set is what unlocks the mesh (docs/PARTITIONING.md):

* **BV interval-bitmap planes** shard along the rule-WORD axis: a
  segment's bitmap row packs the rule axis into uint32 words
  ([I, W] → P(node, None, rule)), so each chip ANDs its word block and
  first-matches locally, and one encoded ``pmin`` over the rule axis
  yields the cluster-wide first match (parallel/cluster.py
  ``sharded_global_classify_bv``). The boundary arrays span ALL rules
  and stay replicated along the rule axis — which is exactly why the
  pre-partition mesh excluded the whole ``glb_bv_*`` group and pinned
  itself dense; the word axis was the shardable one all along.
* **ML weight planes** shard along the hidden axis (MLP: W1 columns,
  b1/W2 rows) and the tree axis (forest): each chip computes a partial
  int32 score and one ``psum`` finishes it — integer adds are
  associative, so sharded scores are bit-exact vs standalone
  (ops/mlscore.py).
* **Session bucket grids** shard along the bucket axis: the flow hash
  is computed against the GLOBAL bucket count, each shard owns a
  contiguous bucket range (ownership = high hash bits), and
  lookup/insert/sweep/aging are shard-local with per-packet results
  combined by one ``psum`` — each packet's bucket lives on exactly one
  shard (ops/session.py ``shard_buckets``).

The sweep cursors stay replicated: every shard's local bucket ring has
the same geometry and advances by the same stride, so one scalar per
node describes all shards' cursors identically.
"""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Tuple

from jax.sharding import PartitionSpec as P

from vpp_tpu.pipeline.tables import DataplaneTables, natsess_slots_of

NODE_AXIS = "node"
RULE_AXIS = "rule"


class PartitionError(ValueError):
    """A DataplaneTables field resolved to no partition rule."""


class PartitionRule(NamedTuple):
    """One ordered rule: fields whose name ``re.search``-matches
    ``pattern`` take ``spec``. ``reason`` documents the axis choice (or
    why the field is replicated-by-design along the rule axis) — it is
    what ``show partitions`` and the manifest print."""

    pattern: str
    spec: P
    reason: str


class SpecEntry(NamedTuple):
    """One manifest row: the resolved placement of one field."""

    field: str
    spec: P
    pattern: str
    reason: str


# The ordered cluster rule set. FIRST MATCH WINS — order is load-bearing
# (e.g. the boundary/nbnd rules must outrank the glb_bv_ bitmap rule,
# and sess_max_age must outrank the session bucket-grid rule). Every
# DataplaneTables field MUST match a rule; the explicit-replicate
# entries at the bottom are the "replicated-by-design" ledger the
# --partitions lint accepts — adding a field without extending this
# list is a lint error, not a silent replicate.
PARTITION_RULES: Tuple[PartitionRule, ...] = (
    # --- BV interval-bitmap structure (ops/acl_bv.py) ---
    PartitionRule(r"^glb_bv_(bnd_|nbnd$)", P(NODE_AXIS),
                  "interval boundaries span ALL rules (segment space is "
                  "data-dependent): replicated along the rule axis"),
    PartitionRule(r"^glb_bv_proto$", P(NODE_AXIS, None, RULE_AXIS),
                  "[PR, W] direct proto plane: rule-WORD axis sharded"),
    PartitionRule(r"^glb_bv_", P(NODE_AXIS, None, RULE_AXIS),
                  "[I, W] segment->rule bitmaps: rule-WORD axis sharded "
                  "(per-shard word-AND + encoded pmin first-match)"),
    # --- per-packet ML model (ops/mlscore.py) ---
    PartitionRule(r"^glb_ml_w1$", P(NODE_AXIS, None, RULE_AXIS),
                  "[F, H] layer-1 weights: hidden axis sharded (partial "
                  "matmul + psum, bit-exact integer reduce)"),
    PartitionRule(r"^glb_ml_(b1|w2)$", P(NODE_AXIS, RULE_AXIS),
                  "[H] hidden-axis vectors follow the W1 column shards"),
    PartitionRule(r"^glb_ml_f_", P(NODE_AXIS, RULE_AXIS),
                  "[T, ...] forest planes: tree axis sharded (partial "
                  "vote sums + psum)"),
    PartitionRule(r"^glb_ml_", P(NODE_AXIS),
                  "model scalars (shift/bias/threshold/policy/version): "
                  "replicated along the rule axis"),
    # --- global ACL dense rows + MXU bit-planes (ops/acl.py, acl_mxu) --
    PartitionRule(r"^glb_nrules$", P(NODE_AXIS),
                  "rule-count scalar: replicated (the unmatched-default "
                  "fold needs the FULL count on every shard)"),
    PartitionRule(r"^glb_mxu_coeff$", P(NODE_AXIS, None, RULE_AXIS),
                  "[PLANES, R'] bit-plane coeffs: rule-column sharded"),
    PartitionRule(r"^glb_", P(NODE_AXIS, RULE_AXIS),
                  "dense rule rows + MXU k/act: rule-row sharded "
                  "(per-shard first-match + encoded pmin)"),
    # --- session bucket grids (ops/session.py) ---
    PartitionRule(r"^sess_max_age$", P(NODE_AXIS),
                  "timeout scalar: replicated"),
    PartitionRule(r"^(sess|natsess)_sweep_cursor$", P(NODE_AXIS),
                  "sweep cursors: replicated — every shard's local ring "
                  "has identical geometry and advances identically"),
    PartitionRule(r"^(sess|natsess)_", P(NODE_AXIS, RULE_AXIS),
                  "[NB, W] bucket grids: bucket axis sharded (global "
                  "flow hash, contiguous bucket-range ownership; "
                  "lookup/insert/sweep/aging shard-local)"),
    # --- multi-tenant gateway planes (vpp_tpu/tenancy/; ISSUE 14) --
    # Everything tenant-scoped is a [T]/[S] per-tenant vector and MUST
    # replicate along the rule axis: the slice base/mask vectors
    # address GLOBAL session-bucket indices, so the bucket-axis shards
    # above compose with tenant slicing unchanged (a sliced bucket is
    # still owned by exactly one shard) — partition_lint() hard-errors
    # a tnt_ field that ever resolves rule-sharded.
    PartitionRule(r"^tnt_", P(NODE_AXIS),
                  "per-tenant vectors (prefix map, token buckets, "
                  "slice base/mask in GLOBAL bucket units, accounting "
                  "planes): replicated along the rule axis so tenant "
                  "slices compose with the bucket-axis session shards "
                  "bit-exactly"),
    # --- replicated-by-design ledger -------------------------------
    PartitionRule(r"^acl_", P(NODE_AXIS),
                  "per-interface local tables are small (max_rules "
                  "rows): replicated-by-design along the rule axis"),
    PartitionRule(r"^if_", P(NODE_AXIS),
                  "interface attributes: per-node config, "
                  "replicated-by-design"),
    # LPM per-length prefix planes + ECMP group tables + per-member
    # accounting (ISSUE 15; ops/lpm.py, ops/fib.py): registered from
    # day one so the mesh upload path serves million-route FIBs
    # unchanged. Replicated along the rule axis by design — every
    # shard needs the WHOLE route table (a packet's longest match can
    # live anywhere), and the planes are read-only gathers, so
    # replication costs memory only, never a collective.
    PartitionRule(r"^fib_(lpm_|grp|ecmp_c)", P(NODE_AXIS),
                  "LPM length planes / ECMP member tables / per-member "
                  "accounting: per-node routing state, replicated "
                  "along the rule axis (lookups are pure gathers — "
                  "every shard holds the whole FIB)"),
    PartitionRule(r"^fib_", P(NODE_AXIS),
                  "FIB slots: per-node routing config, "
                  "replicated-by-design"),
    PartitionRule(r"^(nat_|natb_)", P(NODE_AXIS),
                  "NAT mappings/backends: per-node service config, "
                  "replicated-by-design"),
    # service LB planes + overlay config (ISSUE 19): [V]/[V, B] VIP
    # rows and the VTEP scalar are per-node service/tunnel config.
    # Replicated along the rule axis BY DESIGN — the flow-hash backend
    # pick needs every row's whole way table on every shard (the
    # nat_/natb_ rationale); partition_lint() hard-errors a svc_ field
    # that ever resolves rule-sharded.
    PartitionRule(r"^svc_", P(NODE_AXIS),
                  "service VIP rows + backend way tables: per-node "
                  "service config, replicated-by-design along the "
                  "rule axis (the backend pick gathers whole rows)"),
    PartitionRule(r"^ovl_", P(NODE_AXIS),
                  "overlay config scalars (local VTEP): per-node "
                  "tunnel config, replicated-by-design"),
    PartitionRule(r"^tel_", P(NODE_AXIS),
                  "telemetry planes: cluster node configs keep the "
                  "knob off (placeholder shapes), replicated-by-design"),
)


def match_partition_rules(
    name: str,
    rules: Tuple[PartitionRule, ...] = PARTITION_RULES,
) -> Optional[PartitionRule]:
    """First rule whose pattern matches ``name`` (None = unmatched)."""
    for rule in rules:
        if re.search(rule.pattern, name) is not None:
            return rule
    return None


def spec_for(
    name: str,
    rules: Tuple[PartitionRule, ...] = PARTITION_RULES,
) -> P:
    """The PartitionSpec of one field. An unmatched field RAISES — a
    new DataplaneTables field must be placed deliberately (sharded or
    listed replicated-by-design), never silently replicated."""
    rule = match_partition_rules(name, rules)
    if rule is None:
        raise PartitionError(
            f"DataplaneTables field {name!r} matches no partition rule "
            "(vpp_tpu/parallel/partition.py PARTITION_RULES): add a "
            "sharding rule or a replicated-by-design entry")
    return rule.spec


def spec_manifest(
    rules: Tuple[PartitionRule, ...] = PARTITION_RULES,
) -> Dict[str, SpecEntry]:
    """Every DataplaneTables field's resolved placement, in field
    order. Raises PartitionError on any unmatched field — building the
    manifest IS the completeness check (the mesh sharding tree, the
    --partitions lint and ``show partitions`` all build it)."""
    out: Dict[str, SpecEntry] = {}
    for f in DataplaneTables._fields:
        rule = match_partition_rules(f, rules)
        if rule is None:
            raise PartitionError(
                f"DataplaneTables field {f!r} matches no partition rule "
                "(vpp_tpu/parallel/partition.py PARTITION_RULES): add a "
                "sharding rule or a replicated-by-design entry")
        out[f] = SpecEntry(field=f, spec=rule.spec, pattern=rule.pattern,
                           reason=rule.reason)
    return out


def table_specs() -> DataplaneTables:
    """The PartitionSpec pytree for node-stacked DataplaneTables —
    resolved from PARTITION_RULES (parallel/mesh.py re-exports this as
    the mesh's sharding source of truth)."""
    manifest = spec_manifest()
    return DataplaneTables(**{f: e.spec for f, e in manifest.items()})


def rule_sharded_fields() -> Tuple[str, ...]:
    """Fields whose spec mentions the rule axis (observability/tests)."""
    return tuple(
        f for f, e in spec_manifest().items()
        if any(RULE_AXIS == ax for ax in e.spec if ax is not None)
    )


def partition_lint() -> List[str]:
    """The ``--partitions`` pass: every DataplaneTables field must
    resolve to an explicit rule, and every rule must match at least one
    field (stale rules are findings). Returns problem strings."""
    problems: List[str] = []
    hit = [0] * len(PARTITION_RULES)
    for f in DataplaneTables._fields:
        matched = False
        for i, rule in enumerate(PARTITION_RULES):
            if re.search(rule.pattern, f) is not None:
                hit[i] += 1
                matched = True
                break
        if not matched:
            problems.append(
                f"partitions: DataplaneTables field {f!r} matches no "
                "partition rule (add a sharding rule or a "
                "replicated-by-design entry)")
    for i, rule in enumerate(PARTITION_RULES):
        if not hit[i]:
            problems.append(
                f"partitions: rule {rule.pattern!r} matches no "
                "DataplaneTables field (stale rule?)")
    # tenancy hard errors (ISSUE 14): every tenant plane (the tnt_*
    # slice/bucket/accounting vectors and the per-tenant ML policy
    # vectors) must resolve REPLICATED along the rule axis — a
    # rule-sharded [T] vector would hand each shard a different slice
    # base and silently break the global-bucket math the bucket-axis
    # session shards rely on.
    for f in DataplaneTables._fields:
        if not (f.startswith("tnt_") or f.startswith("glb_ml_tnt_")):
            continue
        rule = match_partition_rules(f)
        if rule is None:
            continue  # already reported as unmatched above
        if any(ax == RULE_AXIS for ax in rule.spec if ax is not None):
            problems.append(
                f"partitions: tenant plane {f!r} resolves rule-sharded "
                f"({rule.pattern!r}) — tenant vectors must replicate "
                "along the rule axis (docs/TENANCY.md)")
    # service-plane hard errors (ISSUE 19): the flow-hash backend pick
    # gathers a VIP row's WHOLE way table — a rule-sharded svc plane
    # would hand each shard a different backend subset and silently
    # split one flow's pick across members.
    for f in DataplaneTables._fields:
        if not f.startswith("svc_"):
            continue
        rule = match_partition_rules(f)
        if rule is None:
            continue  # already reported as unmatched above
        if any(ax == RULE_AXIS for ax in rule.spec if ax is not None):
            problems.append(
                f"partitions: service plane {f!r} resolves rule-sharded "
                f"({rule.pattern!r}) — svc planes must replicate along "
                "the rule axis (docs/OVERLAY.md)")
    if not problems:
        entries = spec_manifest()
        for ax in (NODE_AXIS, RULE_AXIS):
            used = any(
                ax in tuple(a for a in e.spec if a is not None)
                for e in entries.values()
            )
            if not used:
                problems.append(
                    f"partitions: mesh axis {ax!r} is named by no spec")
    return problems


def select_impl(knob: str, bv_ok: bool, mxu_ok: bool, nrules: int,
                bv_min_rules: int, mxu_threshold: int,
                pallas_ok: bool = False, local_nrules: int = 0) -> str:
    """The ONE classifier-selection ladder, shared by the standalone
    Dataplane, ClusterDataplane and MultiHostCluster (each resolves
    its own eligibility bits — builder state, all-nodes agreement, or
    the fleet allgather — then applies this identical mapping, so the
    mesh can never silently select a different rung than standalone).

    Explicit knobs are honored when compilable (an operator knob beats
    a size heuristic); ``auto`` ladders pallas (when eligible — a real
    TPU backend, ISSUE 16) >= BV >= bv_min_rules > MXU >=
    mxu_threshold > dense, every ineligible structure falling to the
    next rung. The pallas rung rides the BV planes, so its structural
    eligibility IS ``bv_ok`` — ``pallas_ok`` carries only the backend
    bit (default False keeps mesh callers on the proven rungs until
    they resolve it themselves). ``nrules`` is the global table's rule
    count, ``local_nrules`` the largest staged local table's: BV serves
    both stages, so either table reaching ``bv_min_rules`` engages it;
    MXU classifies the global table only, so only ``nrules`` gates it."""
    if knob == "dense":
        return "dense"
    if knob == "mxu":
        return "mxu" if mxu_ok else "dense"
    if knob in ("pallas", "bv"):
        if bv_ok:
            return "pallas" if (knob == "pallas" and pallas_ok) else "bv"
        return "mxu" if mxu_ok and nrules >= mxu_threshold else "dense"
    if bv_ok and max(nrules, local_nrules) >= bv_min_rules:
        return "pallas" if pallas_ok else "bv"
    if mxu_ok and nrules >= mxu_threshold:
        return "mxu"
    return "dense"


def select_fib_impl(knob: str, lpm_ok: bool, n_routes: int,
                    min_routes: int, pallas_ok: bool = False) -> str:
    """The ONE FIB-implementation ladder (ISSUE 15), the
    ``select_impl`` twin: explicit knobs are honored when compilable
    (``lpm`` with an ineligible table — planes disabled or a length
    over its cap — falls back to dense rather than serving wrong
    routes); ``auto`` engages LPM at ``min_routes`` staged routes,
    upgrading to the fused pallas rung (ISSUE 16) when the backend
    carries it — the rung rides the SAME planes, so eligibility is
    ``lpm_ok`` plus the backend bit."""
    if knob == "dense":
        return "dense"
    if knob == "pallas":
        if lpm_ok:
            return "pallas" if pallas_ok else "lpm"
        return "dense"
    if knob == "lpm":
        return "lpm" if lpm_ok else "dense"
    if lpm_ok and n_routes >= min_routes:
        return "pallas" if pallas_ok else "lpm"
    return "dense"


def select_session_impl(knob: str, pallas_ok: bool) -> str:
    """The session-probe ladder (ISSUE 16): ``gather`` is the proven
    row-gather rung (always compilable — the session columns ARE the
    structure); ``pallas``/``auto`` take the fused probe kernel when
    the backend and the VMEM budget carry it
    (ops/session.session_pallas_fits — callers fold it into
    ``pallas_ok``), falling back to gather otherwise."""
    if knob == "gather":
        return "gather"
    return "pallas" if pallas_ok else "gather"


def agree_ml(ml_stage: str, kinds) -> Tuple[str, str]:
    """The ONE ML-stage agreement rule for multi-node planes:
    ``kinds`` is the set of staged model kinds across nodes (0 = none;
    -1 = a host reported internally-mixed kinds). The stage engages
    only when every node staged a model of the SAME kernel kind —
    returns (ml_mode, ml_kind)."""
    kinds = set(int(k) for k in kinds)
    if ml_stage != "off" and len(kinds) == 1 and kinds not in \
            ({0}, {-1}):
        return ml_stage, ("forest" if kinds == {2} else "mlp")
    return "off", "mlp"


class ShardCtx(NamedTuple):
    """Trace-time-static rule-shard context the sharded kernels thread:
    the bound mesh axis name and its size. Built by the cluster step
    factory (parallel/cluster.py); ``None`` everywhere standalone."""

    axis: str
    shards: int


def validate_partitioning(config, rule_shards: int) -> None:
    """Fail FAST (the validate_dataplane_config discipline) on a config
    whose sharded axes don't divide by ``rule_shards``: session/NAT
    bucket grids, and — when the ML stage is on — the hidden and tree
    axes. The BV word axis is checked separately (``bv_mesh_ok``): BV
    eligibility degrades to the next classifier rung instead of
    refusing the whole mesh."""
    if rule_shards <= 1:
        return
    # Pallas rungs are standalone-only for now (ISSUE 16): the fused
    # kernels probe whole VMEM-resident structures and none of them
    # shard via PARTITION_RULES yet. An explicit pallas knob on a mesh
    # is rejected HERE, at config time, with a recoverable message —
    # never deep inside a pallas_call trace. (``auto`` stays legal:
    # mesh selection ladders resolve pallas_ok=False and keep the
    # proven sharded rungs.)
    for knob_name, sharded_rung in (("classifier", "bv"),
                                    ("fib_impl", "lpm"),
                                    ("session_impl", "gather")):
        if getattr(config, knob_name, None) == "pallas":
            raise ValueError(
                f"dataplane.{knob_name}: the pallas rung does not "
                f"shard across {rule_shards} rule shards — no "
                "PARTITION_RULES spec covers the fused kernels yet. "
                f"Use '{sharded_rung}' or 'auto' on a mesh (auto "
                "selects the sharded rungs)")
    ways = int(getattr(config, "sess_ways", 4))
    for name, slots in (("sess_slots", config.sess_slots),
                        ("natsess_slots", natsess_slots_of(config))):
        buckets = slots // ways
        if buckets % rule_shards:
            raise ValueError(
                f"dataplane.{name}: {buckets} buckets "
                f"({slots} slots / {ways} ways) not divisible by "
                f"{rule_shards} rule shards")
    if getattr(config, "ml_stage", "off") != "off":
        hidden = int(getattr(config, "ml_hidden", 16))
        trees = int(getattr(config, "ml_trees", 4))
        if hidden % rule_shards:
            raise ValueError(
                f"dataplane.ml_hidden {hidden} not divisible by "
                f"{rule_shards} rule shards")
        if trees % rule_shards:
            raise ValueError(
                f"dataplane.ml_trees {trees} not divisible by "
                f"{rule_shards} rule shards")


def bv_mesh_ok(config, rule_shards: int) -> bool:
    """Whether the BV structure can serve THIS mesh: the rule-word axis
    (W = ceil(R/32)) and the dense action rows must shard into aligned
    blocks — i.e. ``max_global_rules`` divisible by ``32·shards`` so a
    shard's word block covers exactly its action-row block. When False
    the cluster selection ladder falls to MXU/dense (the ok=False
    degradation pattern of ops/acl_bv.py)."""
    from vpp_tpu.ops.acl_bv import bv_enabled_for

    if not bv_enabled_for(config):
        return False
    if rule_shards <= 1:
        return True
    return config.max_global_rules % (32 * rule_shards) == 0
