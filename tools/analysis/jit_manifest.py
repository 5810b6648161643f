"""The jit-registry manifest: every jitted entry point of the traced
roots (vpp_tpu/ops, vpp_tpu/pipeline, vpp_tpu/parallel), ENUMERATED —
the jax pass (tools/analysis/jaxlint.py) fails on any ``jax.jit`` call
site not registered here, and on any entry here that no longer matches
a call site (stale manifest). Adding a jit is a reviewed decision: it
changes what recompiles when, so it lands with a reason string.

Keys are ``(repo-relative path, enclosing scope qualname)``; the scope
is ``<module>`` for module-level calls, ``@name`` for a decorator on
``name``, else the dotted qualname of the enclosing function/method.

``TRACED_ROOTS`` additionally names functions that are traced INTO a
jit program but whose wrapping is indirect (the first argument of the
``jax.jit`` call is an expression the AST pass cannot resolve — e.g.
``jax.jit(_packed_call(fn))``). These are the roots the host-sync /
tracer-branch rules start their reachability closure from; a root that
names a function that no longer exists is a finding.
"""

# (relpath, scope) -> why this site exists / what caches it
JIT_SITES = {
    ("vpp_tpu/pipeline/dataplane.py", "_jitted_step"):
        "THE step factory: process-wide _JIT_STEPS cache keyed "
        "(impl, skip, fast, form, sweep_stride); compile counting "
        "wraps fn here",
    ("vpp_tpu/ops/session.py", "<module>"):
        "session_expire: the on-demand BULK session reclaim (tests, "
        "CLI, idle-node maintenance) — one fused program instead of a "
        "dozen eager whole-table ops at the 10M-slot regime; "
        "now/max_age are traced scalars so values never retrace. "
        "Steady-state aging is NOT this: session_sweep rides the "
        "fused pipeline step (graph._finish_step)",
    ("vpp_tpu/pipeline/dataplane.py", "Dataplane.encap_remote"):
        "lazy vxlan_encap jit; module-level target fn, built once per "
        "dataplane on first remote-disposed frame",
    ("vpp_tpu/pipeline/dataplane.py", "Dataplane.time_classifier"):
        "diagnostic classify probe; per-impl cache on the instance, "
        "bench/operator path — never hot",
    ("vpp_tpu/pipeline/graph.py", "<module>"):
        "pipeline_step_jit: the module-level reference step (tests)",
    ("vpp_tpu/pipeline/tables.py", "_glb_update_fn"):
        "incremental glb-blob upload kernel; memoized per (w_r, w_c, "
        "planes) block geometry",
    ("vpp_tpu/pipeline/tables.py", "_fib_update_fn"):
        "incremental per-slot FIB blob scatter (ISSUE 15): a route "
        "flap at the 1M-route regime ships a few-KB blob instead of "
        "9 full columns; memoized per block width",
    ("vpp_tpu/pipeline/tables.py", "_svc_update_fn"):
        "incremental svc-plane blob scatter (ISSUE 19): a rolling "
        "backend replacement ships the changed VIP rows as one "
        "few-KB packed blob — zero ACL/ML/FIB bytes; memoized per "
        "(block width, backend ways)",
    ("vpp_tpu/parallel/cluster.py", "make_cluster_step"):
        "the SPMD cluster step (shard_map over the node mesh); built "
        "once per mesh by ClusterDataplane",
    ("vpp_tpu/ops/acl_mxu.py", "@mxu_first_match"):
        "pallas first-match kernel entry; static interpret flag only",
    ("vpp_tpu/ops/acl_bv.py", "@bv_first_set"):
        "pallas BV word-AND + first-set-bit kernel entry (ISSUE 16); "
        "static interpret flag only — the fused rung gathers segment "
        "rows on-device and reduces them in VMEM tiles",
    ("vpp_tpu/ops/acl_bv.py", "@acl_local_bv_first_set"):
        "the same pallas kernel for the per-interface local tables, "
        "under its own kernel name so a device trace times it apart; "
        "static interpret flag only",
    ("vpp_tpu/ops/lpm.py", "@lpm_fused_lookup"):
        "pallas LPM kernel entry (ISSUE 16): VMEM-resident planes, "
        "a compare-scan of each populated length's live entries, "
        "longest-first first hit wins; static interpret flag only",
    ("vpp_tpu/ops/session.py", "@sess_probe_ways"):
        "pallas session bucket-probe kernel entry (ISSUE 16): whole "
        "key columns staged to VMEM, per-packet way election in-core; "
        "static interpret flag only",
    ("vpp_tpu/pipeline/snapshot.py", "_fetch_fn"):
        "bounded chunk drain for the crash-consistent session "
        "snapshot (ISSUE 8): one [C, CB, W] stacked fetch per chunk, "
        "lru_cache-memoized per chunk_buckets geometry; the start "
        "offset is a traced scalar so draining the ring never "
        "retraces",
    ("vpp_tpu/pipeline/snapshot.py", "_digest_fn"):
        "per-chunk content digest for incremental snapshots (ISSUE "
        "8): one on-device O(table) pass returning [n_chunks] uint32 "
        "— only chunks whose digest moved drain; memoized per "
        "chunk_buckets geometry",
    ("vpp_tpu/tenancy/derive.py", "<module>"):
        "tenant_occupancy: per-tenant live-session slice counts for "
        "`show tenants` / vpp_tpu_tenant_sess_occupancy (ISSUE 14) — "
        "one on-device prefix sum returning [T] ints, compiled once "
        "per table geometry; an observability path, never hot",
}

# (relpath, dotted def qualname) traced into jit programs indirectly
TRACED_ROOTS = {
    # the step factory composition: jax.jit(make_pipeline_step(...))
    ("vpp_tpu/pipeline/graph.py", "make_pipeline_step.step"),
    ("vpp_tpu/pipeline/graph.py", "pipeline_step"),
    ("vpp_tpu/pipeline/graph.py", "pipeline_step_fast"),
    ("vpp_tpu/pipeline/graph.py", "pipeline_step_auto"),
    # set-associative session table (ISSUE 6): the insert core and the
    # amortized in-step sweep are traced INTO every step variant via
    # graph.py; session_expire's impl is wrapped by the module-level
    # jit registered above; the linear-probe baseline is traced only by
    # bench.py's jitted old-vs-new shoot-out
    ("vpp_tpu/ops/session.py", "hashmap_insert"),
    ("vpp_tpu/ops/session.py", "session_sweep"),
    ("vpp_tpu/ops/session.py", "_session_expire_impl"),
    ("vpp_tpu/ops/session.py", "hashmap_insert_linear"),
    # the packed/chained IO boundary wrappers: jax.jit(_packed_call(fn))
    # — each has an off-signature and a telemetry-signature variant
    # (ISSUE 11) sharing one _core/_loop body
    # (_packed_tables, filtered to the leaves the step writes)
    ("vpp_tpu/pipeline/dataplane.py", "_packed_tables._core"),
    ("vpp_tpu/pipeline/dataplane.py", "_packed_tables.run"),
    ("vpp_tpu/pipeline/dataplane.py", "_packed_call.run"),
    ("vpp_tpu/pipeline/dataplane.py", "_chained_call.run_off"),
    ("vpp_tpu/pipeline/dataplane.py", "_chained_call.run_tel"),
    # the device-ring window program (ISSUE 7): jax.jit(_ring_call(fn,
    # slots)) through _jitted_step — the persistent pump's steady
    # state; the old per-instance PersistentPump.__init__ jit site is
    # GONE (the ring form rides the process-wide step cache, so an
    # epoch-swap pump restart recompiles nothing)
    ("vpp_tpu/pipeline/dataplane.py", "_ring_call.run"),
    ("vpp_tpu/pipeline/dataplane.py", "_ring_call.run_tel"),
    ("vpp_tpu/pipeline/dataplane.py", "_ring_call._loop"),
    # the per-packet ML stage (ISSUE 10): traced into every step
    # variant whose ml_mode gate is on via graph._ml_eval — the stage
    # rides the SAME process-wide _jitted_step cache (no jit site of
    # its own, so an ML-enabled step compiles once, never per epoch)
    ("vpp_tpu/ops/mlscore.py", "ml_features"),
    ("vpp_tpu/ops/mlscore.py", "ml_score"),
    ("vpp_tpu/ops/mlscore.py", "ml_policy"),
    ("vpp_tpu/ops/session.py", "session_hit_age"),
    # the device telemetry plane (ISSUE 11): the flow sketch rides
    # every "full"-gated step variant via graph._finish_step, the
    # latency histogram + rider ride the packed/chained/ring boundary
    # wrappers via dataplane._packed_call/_ring_call — all through the
    # SAME process-wide _jitted_step cache (no jit site of their own)
    ("vpp_tpu/ops/telemetry.py", "tel_flow_update"),
    ("vpp_tpu/ops/telemetry.py", "tel_flow_hash"),
    ("vpp_tpu/ops/telemetry.py", "tel_latency_update"),
    ("vpp_tpu/ops/telemetry.py", "lat_bucket"),
    ("vpp_tpu/ops/telemetry.py", "sketch_cols"),
    ("vpp_tpu/ops/telemetry.py", "pack_tel_rider"),
    # the LPM FIB + shared resolver (ISSUE 15): reached through the
    # step factory's _fib_fn indirection (the _classifier_fns twin),
    # so the reachability closure needs them named explicitly
    ("vpp_tpu/ops/lpm.py", "fib_lookup_lpm"),
    ("vpp_tpu/ops/lpm.py", "fib_lookup_lpm_fused"),
    ("vpp_tpu/ops/fib.py", "fib_lookup_dense"),
    ("vpp_tpu/ops/fib.py", "resolve_fib_slot"),
    ("vpp_tpu/ops/fib.py", "fib_flow_mix"),
    ("vpp_tpu/ops/fib.py", "ip4_lookup"),
    # classifier implementations reach jit through _classifier_fns /
    # time_classifier's subscripted call — enumerate them explicitly
    ("vpp_tpu/ops/acl.py", "acl_classify_global"),
    ("vpp_tpu/ops/acl.py", "acl_classify_local"),
    ("vpp_tpu/ops/acl.py", "acl_local_none"),
    ("vpp_tpu/ops/acl_mxu.py", "acl_classify_global_mxu"),
    ("vpp_tpu/ops/acl_bv.py", "acl_classify_global_bv"),
    ("vpp_tpu/ops/acl_bv.py", "acl_classify_local_bv"),
    ("vpp_tpu/ops/acl_bv.py", "acl_classify_global_pallas"),
    ("vpp_tpu/ops/acl_bv.py", "acl_classify_local_pallas"),
    # mesh-sharded classify substitutions (parallel/cluster.py body)
    ("vpp_tpu/parallel/cluster.py", "sharded_global_classify"),
    ("vpp_tpu/parallel/cluster.py", "sharded_global_classify_mxu"),
    # vxlan encap rides its own jit (Dataplane.encap_remote) AND the
    # overlay-gated step forms (ISSUE 19: graph._finish_step builds
    # the outer header in-step); decap + the VNI→tenant map are traced
    # into the same overlay step forms via the decap stage ahead of
    # ip4-input — all through the ONE _jitted_step cache dimension
    ("vpp_tpu/ops/vxlan.py", "vxlan_encap"),
    ("vpp_tpu/ops/vxlan.py", "vxlan_decap_step"),
    ("vpp_tpu/tenancy/derive.py", "vni_tenant"),
    # the svc DNAT consult (ISSUE 19) rides every step variant via
    # ops/nat44.nat44_dnat (inert one-row gather when svc_vips == 0)
    ("vpp_tpu/ops/nat44.py", "_svc_lookup"),
    # the tenant stage (ISSUE 14): derivation + token bucket +
    # accounting are traced into every tenancy-gated step variant via
    # graph._tenant_eval/_finish_step, and the tenant-sliced bucket
    # computation into the session/NAT ops — all through the SAME
    # process-wide _jitted_step cache (exactly one new step form)
    ("vpp_tpu/tenancy/derive.py", "addr_tenant"),
    ("vpp_tpu/tenancy/derive.py", "key_tenant"),
    ("vpp_tpu/tenancy/derive.py", "tenant_ids"),
    ("vpp_tpu/tenancy/derive.py", "tenant_limit"),
    ("vpp_tpu/tenancy/derive.py", "tnt_account"),
    ("vpp_tpu/tenancy/derive.py", "_tenant_occupancy_impl"),
    ("vpp_tpu/ops/session.py", "tenant_bucket"),
}

# --- the Pallas kernel registry (ISSUE 16) ---------------------------
# Every ``pl.pallas_call`` entry point in the tree, ENUMERATED with the
# DataplaneTables fields its operands are built from and the ladder
# knob that selects it. The --partitions lint
# (tools/analysis/registries.py) walks this: each entry must import,
# each named field must resolve in the partition spec, and the knob
# must be REJECTED by validate_partitioning on a rule-sharded mesh
# until a PARTITION_RULES spec covers the fused kernel — a pallas rung
# must never fail inside pallas_call at trace time.
#
# (relpath, jit-entry scope) -> {"fn": dispatch-root qualname,
#                                "knob": config knob that selects it,
#                                "fields": DataplaneTables operands}
PALLAS_KERNELS = {
    ("vpp_tpu/ops/acl_mxu.py", "@mxu_first_match"): {
        "fn": "acl_classify_global_mxu",
        "knob": "classifier",
        "fields": ("glb_mxu_coeff", "glb_mxu_k", "glb_mxu_act"),
    },
    ("vpp_tpu/ops/acl_bv.py", "@bv_first_set"): {
        "fn": "acl_classify_global_pallas",
        "knob": "classifier",
        "fields": (
            "glb_bv_bnd_src", "glb_bv_bnd_dst", "glb_bv_bnd_sport",
            "glb_bv_bnd_dport", "glb_bv_nbnd", "glb_bv_src",
            "glb_bv_dst", "glb_bv_sport", "glb_bv_dport",
            "glb_bv_proto",
        ),
    },
    ("vpp_tpu/ops/acl_bv.py", "@acl_local_bv_first_set"): {
        "fn": "acl_classify_local_pallas",
        "knob": "classifier",
        "fields": (
            "acl_bv_bnd_src", "acl_bv_bnd_dst", "acl_bv_bnd_sport",
            "acl_bv_bnd_dport", "acl_bv_nbnd", "acl_bv_src",
            "acl_bv_dst", "acl_bv_sport", "acl_bv_dport",
            "acl_bv_proto", "if_local_table",
        ),
    },
    ("vpp_tpu/ops/lpm.py", "@lpm_fused_lookup"): {
        "fn": "fib_lookup_lpm_fused",
        "knob": "fib_impl",
        "fields": tuple(f"fib_lpm_p{i}" for i in range(33))
        + ("fib_lpm_cnt",),
    },
    ("vpp_tpu/ops/session.py", "@sess_probe_ways"): {
        "fn": "_sess_probe_dispatch",
        "knob": "session_impl",
        "fields": ("sess_valid", "sess_src", "sess_dst", "sess_ports",
                   "sess_proto", "sess_time"),
    },
}


# --- donation registry (ISSUE 20, the --donate pass) ----------------------
#
# Every jax.jit call with a NON-EMPTY donate_argnums must be registered
# here by (relpath, enclosing scope): donation is an ownership transfer,
# and an unregistered donating jit is a use-after-donate bug waiting for
# a reader (the PR-8 checkpoint_sessions hazard).  The reason documents
# who owns the buffers and why donating is safe.
DONATED_JIT_SITES = {
    ("vpp_tpu/pipeline/dataplane.py", "_jitted_step"): (
        "the packed/ring/chain step factories: packed+chain donate only "
        "the flat input column block (a fresh jnp.asarray temp at every "
        "call site); ring donates the tables carry + cursor + rx window, "
        "owned by the persistent pump which threads the returned carry"),
    ("bench.py", "sub_benches"): (
        "throughput loop donates its private dataplane's tables; the "
        "carry is rebound from StepResult every iteration"),
    ("bench.py", "session_scale_bench"): (
        "hashmap shoot-out donates the pristine() column sets (rebuilt "
        "per window) and the 10M-resident insert carry (rebound from "
        "the result tuple)"),
    ("bench.py", "_run"): (
        "headline loop donates its private dataplane's tables; carry "
        "rebound from StepResult; commit_bench runs on its OWN "
        "dataplane for exactly this reason (its docstring)"),
}

# Donating CALL sites the use-after-donate dataflow checks:
# (relpath, enclosing scope, callee expression) -> (argnums, reason).
# The pass finds every matching call in that scope, tracks the donated
# name arguments, and flags any read that can observe the invalidated
# buffer (straight-line reads after the call, and loop-carried reads
# with no rebind in between).  Donated values may only be re-exposed
# via the sanctioned copy points (checkpoint_sessions / _serve_ckpt
# jnp.copy, the stager hand-off) — those live in OTHER scopes and get
# a fresh reference, never the donated one.
DONATING_CALLS = {
    ("vpp_tpu/pipeline/persistent.py", "PersistentPump._stage_loop",
     "self._step"): (
        (0, 1, 2),
        "ring window program: donates tables carry + cursor + rx "
        "window; _stage_loop rebinds all three from the result tuple "
        "in the same statement"),
    ("vpp_tpu/pipeline/dataplane.py", "Dataplane.process_packed",
     "step"): (
        (1,),
        "packed column block: the donated arg is a fresh "
        "jnp.asarray(flat) temp, never a named value"),
    ("vpp_tpu/pipeline/dataplane.py",
     "Dataplane.process_packed_chain", "step"): (
        (1,),
        "chained packed block: same fresh-temp discipline as "
        "process_packed"),
    ("bench.py", "measure_mpps", "step"): (
        (0,),
        "tables carry donated and rebound from res.tables each "
        "iteration"),
    ("bench.py", "session_scale_bench", "fn"): (
        (0, 1, 2, 3, 4, 5),
        "the six hashmap columns are rebuilt by pristine() before "
        "every donating call"),
    ("bench.py", "session_scale_bench", "insert"): (
        (0,),
        "10M-resident carry: rebound from the result tuple in the "
        "same statement"),
    ("bench.py", "_run", "step"): (
        (0,),
        "headline tables carry: rebound from res.tables each "
        "iteration"),
}
