"""Static sizing of the device tables, and its validation.

JAX-free on purpose: the config loader (cmd/config.py) runs in
processes that never touch the device — vpp-tpu-init and the IO
daemon — and must not import JAX to read a YAML file.
"""

from __future__ import annotations

from typing import NamedTuple


class DataplaneConfig(NamedTuple):
    """Static sizing of the device tables (shapes are compile-time)."""

    max_tables: int = 16       # local ACL table slots
    max_rules: int = 128       # rules per local table (padded)
    max_global_rules: int = 128
    max_ifaces: int = 64
    fib_slots: int = 128
    # FIB lookup implementation (ops/fib.py dense masked-compare,
    # ops/lpm.py binary-search-over-prefix-lengths): "dense" | "lpm" |
    # "auto". ``auto`` picks LPM once the staged route count reaches
    # ``fib_lpm_min_routes`` (and the per-length planes fit
    # ``fib_lpm_mem_mb``, and every staged route fits its length's
    # plane — the BV ok-gate pattern). Re-evaluated at every epoch
    # swap; plane SHAPES are config-static, so only the selection
    # flips per epoch, never the compiled programs' signatures
    # (docs/ROUTING.md).
    fib_impl: str = "auto"
    fib_lpm_min_routes: int = 256
    fib_lpm_mem_mb: int = 256
    # Per-length plane capacities, index = prefix length /0../32
    # (missing tail entries = 0 = length unpopulated, SKIPPED at trace
    # time). Empty (the default) sizes every length to ``fib_slots`` —
    # correct for any route mix; internet-scale configs set the feed's
    # real length distribution to keep plane memory at ~8 bytes/route
    # (ops/lpm.py has the formula).
    fib_lpm_plen_caps: tuple = ()
    # ECMP next-hop groups (ops/fib.py resolve_fib_slot): group slots
    # and member ways per group (power of two — the flow-hash member
    # pick masks with W-1). 0 groups (the default) carries [1, 1]
    # placeholders and set_nh_group is refused.
    fib_ecmp_groups: int = 0
    fib_ecmp_ways: int = 8
    # Reflective-session table: total slots (power of 2), organized as
    # sess_slots/sess_ways buckets of sess_ways ways each (W-way
    # set-associative — ops/session.py). Memory is ~6 uint32 columns x
    # sess_slots (24 B/slot): 1<<24 slots ≈ 402 MB serves 10M+
    # concurrent sessions at ~0.6 load factor (docs/SESSIONS.md).
    sess_slots: int = 4096
    # Ways per bucket (power of 2, divides sess_slots). 4 is the VPP/
    # CPU-cache sweet spot: one bucket row gather fetches the whole
    # associativity set.
    sess_ways: int = 4
    # Session probe implementation: "gather" (the proven row-gather
    # rung), "pallas" (the fused probe kernel, ISSUE 16 — requires a
    # TPU backend and the table to fit the kernel's VMEM budget,
    # ops/session.session_pallas_fits; falls back to gather when
    # ineligible), or "auto" (pallas when eligible). Standalone only:
    # a mesh with an explicit pallas knob is rejected at config time
    # (parallel/partition.py validate_partitioning).
    session_impl: str = "auto"
    # Session bucket hash family (ops/session.py): "fwd" hashes the
    # forward 5-tuple (the classic single-instance layout); "sym"
    # canonicalizes the tuple (address-pair ordered) so BOTH directions
    # of a flow land in the same bucket without knowing direction —
    # required by the fleet steering tier (vpp_tpu/fleet/,
    # docs/FLEET.md), which maps packets to instances by session
    # bucket range from OUTSIDE the dataplane. Only bucket placement
    # changes; stored keys, key comparison and hit semantics are
    # identical. Trace-time static (part of the step-factory key).
    sess_hash: str = "fwd"
    # NAT-session table slots; 0 = same as sess_slots (shares sess_ways)
    natsess_slots: int = 0
    # Amortized on-device aging: every fused pipeline step sweeps this
    # many buckets per table (idle-expired entries are invalidated and
    # the cursor advances; a full cycle takes n_buckets/stride steps).
    # 0 disables the in-step sweep (bulk expire_sessions only).
    sess_sweep_stride: int = 256
    # Session/NAT idle timeout in clock ticks (Dataplane.TICKS_PER_SEC =
    # 10/s, so 3000 = 300 s — VPP's default TCP established timeout
    # order). Enforced in-kernel: lookups ignore expired entries and
    # inserts reclaim their slots, so timeout precision doesn't depend
    # on the host aging loop's cadence.
    sess_max_age: int = 3000
    nat_mappings: int = 64     # DNAT static mapping slots
    nat_backends: int = 512    # total backend slots across mappings
    # Two-tier established-flow fast path (pipeline/graph.py
    # pipeline_step_auto): batches where every valid packet hits a live
    # reflective session dispatch to a classify-free kernel. ``fastpath``
    # is the master switch; ``fastpath_min_rules`` gates engagement on
    # the global table size (below it the classifier is cheap enough
    # that the dispatch predicate buys nothing — the mxu_threshold
    # analog). Both kernels (and their MXU variants) are compiled and
    # cached per epoch by the Dataplane exactly like the full chain.
    fastpath: bool = True
    fastpath_min_rules: int = 0
    # Global-classify implementation (ops/acl.py dense VPU compare,
    # ops/acl_mxu.py bit-plane matmul, ops/acl_bv.py interval-bitmap
    # bit-vector): "dense" | "mxu" | "bv" | "auto". ``auto`` picks BV
    # once the global table reaches ``classifier_bv_min_rules`` (and
    # the worst-case interval-bitmap structure fits
    # ``classifier_bv_mem_mb`` — ~5 x 2R x R/32 uint32 words, ~105 MB
    # at 10,240 rules), the MXU kernel above Dataplane.mxu_threshold,
    # dense below. Re-evaluated at every epoch swap against the staged
    # rule count; the structure's SHAPES are config-static, so only
    # the selection flips per epoch, never the compiled programs'
    # signatures. BV also serves the per-interface local tables (MXU
    # is global-only); the multi-chip mesh keeps its rule-sharded
    # dense/MXU classify (docs/CLASSIFIER.md).
    classifier: str = "auto"
    classifier_bv_min_rules: int = 1024
    classifier_bv_mem_mb: int = 256
    # Per-packet ML scoring stage (ops/mlscore.py; docs/ML_STAGE.md):
    # "off" elides the stage from the compiled step entirely (and the
    # glb_ml_* fields carry minimal placeholder shapes, the BV
    # allocation-gating pattern); "score" computes + counts + exports
    # verdicts only; "enforce" additionally folds the model's
    # drop/ratelimit decisions into the pipeline verdict (ordered
    # deny > ml-drop > permit). The staged MODEL arrives through
    # TableBuilder.set_ml_model (epoch-swapped like ACL rules); with
    # no model staged the stage stays compiled-out even when the knob
    # says score/enforce (re-gated at every swap, the fastpath
    # pattern).
    ml_stage: str = "off"
    # capacity ceilings of the staged model (compile-time SHAPES; a
    # smaller model zero-pads, a larger one is refused at staging)
    ml_hidden: int = 16        # MLP hidden width
    ml_trees: int = 4          # oblivious-forest tree count
    ml_depth: int = 3          # oblivious-forest depth (leaves = 2^D)
    # Device-resident telemetry plane (ops/telemetry.py; ISSUE 11):
    # "off" compiles the stage out entirely and carries minimal
    # placeholder shapes (the ml_stage pattern — the off-state programs
    # are byte-identical to pre-telemetry); "latency" enables the
    # in-step wire-latency log2 histogram; "full" adds the count-min
    # heavy-hitter flow sketch + top-K candidate table. The planes ride
    # this pytree like the sweep cursors (epoch swaps carry them by
    # reference; the persistent ring threads them window-to-window).
    telemetry: str = "off"
    telemetry_lat_buckets: int = 24   # log2 µs bins (last saturates)
    telemetry_sketch_rows: int = 2    # count-min depth d
    telemetry_sketch_cols: int = 1024  # count-min width w (power of 2)
    telemetry_topk: int = 8           # heavy-hitter candidate slots
    # Multi-tenant gateway mode (ISSUE 14; vpp_tpu/tenancy/,
    # docs/TENANCY.md): "off" compiles the tenant stage out entirely
    # and the tnt_* fields carry minimal placeholder shapes (the
    # telemetry/ml gating pattern); "on" derives a per-packet tenant
    # id at ip4-input from the src/dst prefix map (its own "tenant"
    # upload group), runs the per-tenant token-bucket rate limit
    # inside the fused step (overage → DROP_TENANT, attributed
    # drops_total{reason="tenant_quota"}), slices session/NAT bucket
    # capacity per tenant (TableBuilder.set_tenant sess_buckets — a
    # full slice fails/evicts only WITHIN the owning tenant, never
    # across), and keys the ML flag threshold/mode by tenant.
    tenancy: str = "off"
    tenancy_tenants: int = 8          # tenant-id capacity (1..64)
    tenancy_prefixes: int = 64        # prefix-map slots
    # Device-resident VXLAN overlay (ops/vxlan.py; ISSUE 19;
    # docs/OVERLAY.md): "off" compiles the stage pair out entirely —
    # the step programs are byte-identical to pre-overlay; "vxlan"
    # decaps VTEP-addressed UDP/4789 frames at ip4-input (outer header
    # + VNI validated on-device, the inner vector re-admitted in
    # place, VNI → tenant handed to the tenancy derivation) and
    # builds the per-destination-node outer header at tx (entropy
    # sport from the inner 5-tuple, outer endpoint resolved by a
    # SECOND walk over the same FIB planes — LPM/ECMP carry over
    # unchanged). ONE new step-form dimension in the process-wide jit
    # cache; zero io_callbacks on the wire path.
    overlay: str = "off"
    # Service NAT44 LB planes (ops/nat44.py svc path; ISSUE 19): VIP
    # row capacity of the svc_* tables. 0 (default) carries [1, B]
    # placeholders with bk_n 0 — rows that can never serve — and
    # set_service is refused; the svc consult then costs one gather
    # against a 1-row table. The planes ride their OWN "svc" upload
    # group, so rolling backend churn ships a few-KB blob and ZERO
    # ACL/ML/FIB bytes.
    svc_vips: int = 0
    # Backend ways per VIP row (power of two — the flow-hash backend
    # pick masks with B-1). Way assignment is STICKY across backend
    # churn (the set_nh_group fill), so a rolling replacement only
    # remaps the ways it must.
    svc_backend_ways: int = 8


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def validate_dataplane_config(config: DataplaneConfig) -> None:
    """Fail FAST (and intelligibly) on session-table misconfiguration.
    The hash kernels mask with ``& (n_buckets - 1)`` and the sweep
    relies on power-of-two divisibility, so a bad knob that once
    surfaced as a shape error deep inside a jit trace is rejected at
    config load instead. Called from TableBuilder (every dataplane) and
    cmd/config.py (YAML load)."""
    c = config
    ways = int(getattr(c, "sess_ways", 4))
    stride = int(getattr(c, "sess_sweep_stride", 256))
    if not _is_pow2(c.sess_slots):
        raise ValueError(
            f"dataplane.sess_slots must be a power of two, got "
            f"{c.sess_slots}")
    if not _is_pow2(ways):
        raise ValueError(
            f"dataplane.sess_ways must be a power of two, got {ways}")
    if ways > c.sess_slots:
        raise ValueError(
            f"dataplane.sess_ways ({ways}) exceeds sess_slots "
            f"({c.sess_slots})")
    nns = int(getattr(c, "natsess_slots", 0) or 0)
    if nns and not _is_pow2(nns):
        raise ValueError(
            f"dataplane.natsess_slots must be a power of two (or 0 = "
            f"sess_slots), got {nns}")
    if nns and ways > nns:
        raise ValueError(
            f"dataplane.sess_ways ({ways}) exceeds natsess_slots ({nns})")
    if stride < 0 or (stride and not _is_pow2(stride)):
        raise ValueError(
            f"dataplane.sess_sweep_stride must be 0 (disabled) or a "
            f"power of two, got {stride}")
    fib_impl = getattr(c, "fib_impl", "auto")
    if fib_impl not in ("dense", "lpm", "pallas", "auto"):
        raise ValueError(
            f"dataplane.fib_impl must be dense | lpm | pallas | auto, "
            f"got {fib_impl!r}")
    session_impl = getattr(c, "session_impl", "auto")
    if session_impl not in ("gather", "pallas", "auto"):
        raise ValueError(
            f"dataplane.session_impl must be gather | pallas | auto, "
            f"got {session_impl!r}")
    sess_hash = getattr(c, "sess_hash", "fwd")
    if sess_hash not in ("fwd", "sym"):
        raise ValueError(
            f"dataplane.sess_hash must be fwd | sym, got {sess_hash!r}")
    if int(getattr(c, "fib_lpm_min_routes", 256)) < 0:
        raise ValueError(
            f"dataplane.fib_lpm_min_routes must be >= 0, got "
            f"{c.fib_lpm_min_routes}")
    caps = tuple(getattr(c, "fib_lpm_plen_caps", ()) or ())
    if len(caps) > 33:
        raise ValueError(
            f"dataplane.fib_lpm_plen_caps has {len(caps)} entries "
            f"(index = prefix length, max 33: /0../32)")
    for L, cap in enumerate(caps):
        if int(cap) < 0:
            raise ValueError(
                f"dataplane.fib_lpm_plen_caps[/{L}] must be >= 0, "
                f"got {cap}")
    eg = int(getattr(c, "fib_ecmp_groups", 0))
    if not (0 <= eg <= 4096):
        raise ValueError(
            f"dataplane.fib_ecmp_groups must be in 0..4096, got {eg}")
    ew = int(getattr(c, "fib_ecmp_ways", 8))
    if eg and (not _is_pow2(ew) or ew > 256):
        raise ValueError(
            f"dataplane.fib_ecmp_ways must be a power of two <= 256 "
            f"(the flow-hash member pick masks with W-1), got {ew}")
    ml_stage = getattr(c, "ml_stage", "off")
    if ml_stage not in ("off", "score", "enforce"):
        raise ValueError(
            f"dataplane.ml_stage must be off | score | enforce, got "
            f"{ml_stage!r}")
    if int(getattr(c, "ml_hidden", 16)) < 1:
        raise ValueError(
            f"dataplane.ml_hidden must be >= 1, got {c.ml_hidden}")
    if int(getattr(c, "ml_trees", 4)) < 1:
        raise ValueError(
            f"dataplane.ml_trees must be >= 1, got {c.ml_trees}")
    if not (1 <= int(getattr(c, "ml_depth", 3)) <= 8):
        raise ValueError(
            f"dataplane.ml_depth must be in 1..8 (leaf table is "
            f"2^depth), got {c.ml_depth}")
    tel = getattr(c, "telemetry", "off")
    if tel not in ("off", "latency", "full"):
        raise ValueError(
            f"dataplane.telemetry must be off | latency | full, got "
            f"{tel!r}")
    nb = int(getattr(c, "telemetry_lat_buckets", 24))
    if not (4 <= nb <= 31):
        raise ValueError(
            f"dataplane.telemetry_lat_buckets must be in 4..31 "
            f"(log2 µs bins in int32), got {nb}")
    d = int(getattr(c, "telemetry_sketch_rows", 2))
    if not (1 <= d <= 8):
        raise ValueError(
            f"dataplane.telemetry_sketch_rows must be in 1..8, got {d}")
    w = int(getattr(c, "telemetry_sketch_cols", 1024))
    if not _is_pow2(w):
        raise ValueError(
            f"dataplane.telemetry_sketch_cols must be a power of two "
            f"(column masking), got {w}")
    k = int(getattr(c, "telemetry_topk", 8))
    if not (1 <= k <= 64):
        raise ValueError(
            f"dataplane.telemetry_topk must be in 1..64, got {k}")
    tnt = getattr(c, "tenancy", "off")
    if tnt not in ("off", "on"):
        raise ValueError(
            f"dataplane.tenancy must be off | on, got {tnt!r}")
    t = int(getattr(c, "tenancy_tenants", 8))
    if not (1 <= t <= 64):
        raise ValueError(
            f"dataplane.tenancy_tenants must be in 1..64, got {t}")
    s = int(getattr(c, "tenancy_prefixes", 64))
    if not (1 <= s <= 1024):
        raise ValueError(
            f"dataplane.tenancy_prefixes must be in 1..1024, got {s}")
    ovl = getattr(c, "overlay", "off")
    if ovl not in ("off", "vxlan"):
        raise ValueError(
            f"dataplane.overlay must be off | vxlan, got {ovl!r}")
    v = int(getattr(c, "svc_vips", 0))
    if not (0 <= v <= 4096):
        raise ValueError(
            f"dataplane.svc_vips must be in 0..4096, got {v}")
    b = int(getattr(c, "svc_backend_ways", 8))
    if not _is_pow2(b) or b > 256:
        raise ValueError(
            f"dataplane.svc_backend_ways must be a power of two <= 256 "
            f"(the flow-hash backend pick masks with B-1), got {b}")
