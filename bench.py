#!/usr/bin/env python
"""North-star benchmark: ACL-path classification at 10k rules.

Reproduces BASELINE.md config #2/#5 — the reference's policy-perf regime
(tests/policy/perf/gen-policy.py: 1000 CIDR blocks x excepts x 20 ports)
— through the FULL fused pipeline (ip4-input → reflective sessions →
NAT44 → 10k-rule global ACL classify → ip4-lookup), measured in Mpps on
one chip against the driver-set 40 Mpps north star (BASELINE.json).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

METRIC = "acl_nat_pipeline_mpps_10k_rules"
BASELINE_MPPS = 40.0  # BASELINE.json north star, TPU v5e


def _jit_compiles_now() -> int:
    """Total pipeline-step XLA compiles so far (the runtime jit-compile
    guard, pipeline/dataplane.py). Recorded per priority-ladder section
    as <section>_jit_compiles so a recompile regression — the PR-4
    fresh-closure class — shows up in the BENCH_* trajectory, not just
    in wall-clock drift."""
    try:
        from vpp_tpu.pipeline.dataplane import jit_compile_totals

        return sum(jit_compile_totals().values())
    except Exception:  # noqa: BLE001 — accounting must never kill a run
        return 0


def _transfer_bytes_now() -> int:
    """Total device->host bytes through the counted fetch sites so far
    (the runtime device-transfer guard, pipeline/dataplane.py).
    Recorded per priority-ladder section as <section>_transfer_bytes so
    a table-column fetch creeping onto a measured path — the PR-6/8/12
    "aggregate on host" class — shows up in the BENCH_* trajectory."""
    try:
        from vpp_tpu.pipeline.dataplane import device_transfer_totals

        return sum(device_transfer_totals().values())
    except Exception:  # noqa: BLE001 — accounting must never kill a run
        return 0


def build_rules(n_rules: int):
    """Policy rule set shaped like tests/policy/perf/gen-policy.py:
    CIDR-block x port permits with interleaved deny excepts, then a
    terminal deny-all (the renderer-cache table form)."""
    import ipaddress

    from vpp_tpu.ir.rule import Action, ContivRule, Protocol

    rules = []
    i = 0
    while len(rules) < n_rules - 1:
        block = i % 1000
        port = 8000 + (i // 1000) % 20
        net = ipaddress.ip_network(
            f"172.{16 + block // 256}.{block % 256}.0/24"
        )
        action = Action.DENY if i % 6 == 5 else Action.PERMIT
        rules.append(
            ContivRule(
                action=action,
                src_network=net,
                protocol=Protocol.TCP,
                dest_port=port,
            )
        )
        i += 1
    rules.append(ContivRule(action=Action.DENY))
    return rules


def build_dataplane(n_rules: int, n_backends: int, ml_stage: str = "off",
                    telemetry: str = "off"):
    from vpp_tpu.ir.rule import Action, ContivRule
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import Disposition, ip4

    config = DataplaneConfig(
        max_tables=2,
        max_rules=16,
        max_global_rules=n_rules,
        max_ifaces=16,
        fib_slots=64,
        sess_slots=1 << 15,
        nat_mappings=4,
        nat_backends=max(n_backends, 1),
        ml_stage=ml_stage,
        telemetry=telemetry,
    )
    dp = Dataplane(config)
    uplink = dp.add_uplink()
    server_if = dp.add_pod_interface(("default", "server"))
    dp.builder.add_route("10.1.1.0/24", server_if, Disposition.LOCAL)
    dp.builder.add_route("0.0.0.0/0", uplink, Disposition.REMOTE, node_id=1)
    dp.builder.set_global_table(build_rules(n_rules))
    # NAT44 VIP with weighted backends (BASELINE config #3 shape).
    dp.builder.set_nat_mapping(
        0,
        ext_ip=ip4("10.96.0.10"),
        ext_port=80,
        proto=6,
        backends=[(ip4("10.1.1.2") + i, 80, 1 + (i % 2)) for i in range(n_backends)],
        boff=0,
    )
    dp.swap()
    return dp, uplink


def build_traffic(n_pkts: int, uplink: int, seed: int = 7):
    """Uplink traffic: TCP flows from the rule-space CIDR blocks toward
    the local pod subnet + a slice of VIP (NAT) traffic."""
    import jax.numpy as jnp

    from vpp_tpu.pipeline.vector import FLAG_VALID, PacketVector, ip4

    rng = np.random.default_rng(seed)
    block = rng.integers(0, 1000, n_pkts)
    src = (
        (172 << 24)
        | ((16 + block // 256) << 16)
        | ((block % 256) << 8)
        | rng.integers(1, 255, n_pkts)
    ).astype(np.uint32)
    dst = (ip4("10.1.1.0") + rng.integers(2, 250, n_pkts)).astype(np.uint32)
    # ~1/8 of traffic targets the service VIP (exercises DNAT + session).
    vip_mask = rng.random(n_pkts) < 0.125
    dst = np.where(vip_mask, np.uint32(ip4("10.96.0.10")), dst)
    dport = np.where(
        vip_mask, 80, 8000 + rng.integers(0, 20, n_pkts)
    ).astype(np.int32)
    return PacketVector(
        src_ip=jnp.asarray(src),
        dst_ip=jnp.asarray(dst),
        proto=jnp.full((n_pkts,), 6, jnp.int32),
        sport=jnp.asarray(rng.integers(1024, 65535, n_pkts).astype(np.int32)),
        dport=jnp.asarray(dport),
        ttl=jnp.full((n_pkts,), 64, jnp.int32),
        pkt_len=jnp.full((n_pkts,), 512, jnp.int32),
        rx_if=jnp.full((n_pkts,), uplink, jnp.int32),
        flags=jnp.full((n_pkts,), FLAG_VALID, jnp.int32),
    )


def build_fwd_dataplane(telemetry: str = "off"):
    """BASELINE config #1: pod-to-pod ip4-lookup only (no policy/NAT).
    ``telemetry`` enables the device latency histogram for sections
    that tie host-side and on-device latency from the same round
    (the ISSUE 13 host-vs-device sanity check)."""
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import Disposition

    config = DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=16, max_ifaces=64,
        fib_slots=64, sess_slots=1 << 12, nat_mappings=1, nat_backends=1,
        telemetry=telemetry,
    )
    dp = Dataplane(config)
    for i in range(32):
        idx = dp.add_pod_interface(("default", f"p{i}"))
        dp.builder.add_route(f"10.1.1.{i + 2}/32", idx, Disposition.LOCAL)
    dp.swap()
    return dp


def build_pod_traffic(n_pkts: int, seed: int = 3):
    import jax.numpy as jnp

    from vpp_tpu.pipeline.vector import FLAG_VALID, PacketVector, ip4

    rng = np.random.default_rng(seed)
    src = (ip4("10.1.1.0") + rng.integers(2, 34, n_pkts)).astype(np.uint32)
    dst = (ip4("10.1.1.0") + rng.integers(2, 34, n_pkts)).astype(np.uint32)
    return PacketVector(
        src_ip=jnp.asarray(src),
        dst_ip=jnp.asarray(dst),
        proto=jnp.full((n_pkts,), 17, jnp.int32),
        sport=jnp.asarray(rng.integers(1024, 65535, n_pkts).astype(np.int32)),
        dport=jnp.full((n_pkts,), 5201, jnp.int32),
        ttl=jnp.full((n_pkts,), 64, jnp.int32),
        pkt_len=jnp.full((n_pkts,), 1400, jnp.int32),
        rx_if=jnp.asarray(rng.integers(1, 33, n_pkts).astype(np.int32)),
        flags=jnp.full((n_pkts,), FLAG_VALID, jnp.int32),
    )


def measure_mpps(step, tables, pkts, iters, warmup, now0=1):
    import jax
    import jax.numpy as jnp

    n = int(pkts.src_ip.shape[0])
    for i in range(warmup):
        res = step(tables, pkts, jnp.int32(now0 + i))
        tables = res.tables
    jax.block_until_ready(tables)
    t0 = time.perf_counter()
    for i in range(iters):
        res = step(tables, pkts, jnp.int32(now0 + warmup + i))
        tables = res.tables
    jax.block_until_ready(res)
    return n * iters / (time.perf_counter() - t0) / 1e6, res.tables


def commit_bench(args, iters: int = 10) -> dict:
    """Control-plane commit latency at the policy-churn regime
    (reference tests/policy/perf/gen-policy.py: 1000-CIDR x 20-port
    sets). Measures a full global-table commit (pack + bit-plane
    compile + upload + swap) and a CNI-style commit (route+interface
    only) that must NOT re-upload the rule planes.

    Runs on its OWN dataplane: the throughput loop donates its tables
    into the jit, which would invalidate the upload cache a subsequent
    swap relies on (tables.py to_device docstring)."""
    import jax

    n_rules = args.rules
    dp, _ = build_dataplane(n_rules, 4)
    # rule-set generation is not commit work: pre-build the churn
    # sequence outside the clock. Each iteration changes ONE policy's
    # worth of rules (~32 rows at a moving offset) — the reference's
    # policy-churn regime, where an ACL replace is an incremental
    # update, not a from-scratch table build
    # (acl_renderer.go:124-264). The first full-table commit (the
    # resync case) is reported separately.
    from vpp_tpu.ir.rule import ContivRule as _CR

    def shift_ports(rules, delta):
        return [
            _CR(action=r.action, src_network=r.src_network,
                protocol=r.protocol,
                dest_port=(r.dest_port + delta
                           if 0 < r.dest_port < 65000 else r.dest_port))
            for r in rules
        ]

    base_rules = build_rules(n_rules)
    # full-upload case: EVERY row differs from the already-committed
    # table (build_dataplane committed base_rules), so the incremental
    # path must fall back to the full device upload — the resync case
    full_rules = shift_ports(base_rules, 7)
    churn = min(32, n_rules)
    rule_sets = []
    rules = list(full_rules)
    for i in range(iters):
        off = (i * 977) % max(1, n_rules - churn + 1)
        for j in range(churn):
            r = rules[off + j]
            rules[off + j] = _CR(action=r.action,
                                 src_network=r.src_network,
                                 protocol=r.protocol,
                                 dest_port=9000 + i)
        rule_sets.append(list(rules))
    out = {"commit_rules": n_rules}
    # reset the incremental diff base so this measurement is the FULL
    # device upload by construction (at some rule counts the changed
    # span fits a block ladder width and would otherwise scatter)
    dp.builder._glb_prev = None
    t0 = time.perf_counter()
    with dp.commit_lock:
        dp.builder.set_global_table(full_rules)
        dp.swap()
    jax.block_until_ready(dp.tables.glb_mxu_coeff)
    out["commit_ms_global_full"] = round(
        (time.perf_counter() - t0) * 1e3, 2
    )
    # warm the incremental-update program (one-time jit, not commit work)
    with dp.commit_lock:
        dp.builder.set_global_table(rule_sets[0])
        dp.swap()
    jax.block_until_ready(dp.tables.glb_mxu_coeff)
    t0 = time.perf_counter()
    for rules in rule_sets[1:]:
        with dp.commit_lock:
            dp.builder.set_global_table(rules)
            dp.swap()
    jax.block_until_ready(dp.tables.glb_mxu_coeff)
    out["commit_ms_global_table"] = round(
        (time.perf_counter() - t0) / max(1, iters - 1) * 1e3, 2
    )
    from vpp_tpu.pipeline.vector import Disposition

    t0 = time.perf_counter()
    for i in range(iters):
        with dp.commit_lock:
            dp.builder.add_route(f"10.1.9.{i + 1}/32", 2,
                                 Disposition.LOCAL)
            dp.swap()
    jax.block_until_ready(dp.tables.fib_prefix)
    out["commit_ms_cni_route"] = round(
        (time.perf_counter() - t0) / iters * 1e3, 2
    )
    return out


def acl_classifier_bench(args, batch: int = 2048, iters: int = 20) -> dict:
    """Classifier shoot-out (ISSUE 4 tentpole): dense vs MXU vs BV
    global classify in isolation at 1,024 and the headline rule count,
    order-alternated medians like the ``sess_election_*`` pattern (a
    fixed order biased those r4 numbers by warmup/cache state). Each
    round re-validates the ``classifier: auto`` default with evidence:

      * ``acl_classifier_selected``      — what auto picked at the
        headline count on THIS backend
      * ``acl_classify_{dense,mxu,bv}_ns_pkt`` (+ ``_1k`` variants)
      * ``acl_bv_build_ms``              — commit-time structure build
      * ``acl_classifier_speedup_bv_vs_dense`` (acceptance: >= 5x at
        10,240 rules on the CPU harness)
    """
    import jax
    import jax.numpy as jnp

    from vpp_tpu.pipeline.graph import _classifier_fns

    out = {}
    for n_rules in sorted({1024, args.rules}):
        suffix = "" if n_rules == args.rules else "_1k"
        dp, uplink = build_dataplane(n_rules, 4)
        pkts = build_traffic(batch, uplink, seed=17)
        if n_rules == args.rules:
            out["acl_classifier_selected"] = dp.classifier_impl
            out["acl_classifier_rules"] = n_rules
        if dp.builder.bv_enabled:
            out[f"acl_bv_build_ms{suffix}"] = round(
                dp.builder.bv_build_ms, 2)
        impls = ["dense", "bv"] if dp.builder.bv_enabled else ["dense"]
        if dp.builder.mxu_enabled and dp.builder.glb_mxu.ok:
            impls.insert(1, "mxu")
        fns = {}
        for impl in impls:
            fns[impl] = jax.jit(_classifier_fns(impl)[0])
            jax.block_until_ready(fns[impl](dp.tables, pkts).permit)
        acc = {impl: [] for impl in impls}
        for rep in range(3):
            order = impls if rep % 2 == 0 else impls[::-1]
            for impl in order:
                t0 = time.perf_counter()
                for _ in range(iters):
                    v = fns[impl](dp.tables, pkts)
                jax.block_until_ready(v.permit)
                acc[impl].append(
                    (time.perf_counter() - t0) / iters / batch * 1e9)
        for impl, vals in acc.items():
            out[f"acl_classify_{impl}_ns_pkt{suffix}"] = round(
                float(np.median(vals)), 1)
        if n_rules == args.rules:
            # fold the probe time into the observability twin of this
            # measurement (vpp_tpu_pump_stage_seconds{stage="classify"})
            try:
                dp.time_classifier(batch=min(batch, 256), iters=4)
            except Exception:  # noqa: BLE001 — diagnostic only
                pass
    dense = out.get("acl_classify_dense_ns_pkt")
    bv = out.get("acl_classify_bv_ns_pkt")
    if dense and bv:
        out["acl_classifier_speedup_bv_vs_dense"] = round(dense / bv, 2)
    return out


def fib_bench(args, batch: int = 2048, iters: int = 12) -> dict:
    """Million-route LPM FIB capture (ISSUE 15 tentpole).

    Builds a BGP-shaped route table at 1M prefixes (memory-guarded
    downshift like snapshot_bench), validates the ``fib_impl: auto``
    ladder picked LPM, and measures:

      * ``fib_lookup_lpm_ns_pkt``    — LPM lookup at the full table
        (acceptance: within 2x of the small-table dense lookup at its
        native scale on real accelerators; the 1-core CPU harness
        measures ~4-6x because dense@64 is L1-resident while 1M-route
        probes end in cold DRAM — docs/LATENCY.md round 15)
      * ``fib_lookup_dense_ns_pkt``  — dense at its NATIVE node scale
        (64 routes — what the seed-era FIB actually served)
      * ``fib_lookup_dense_1m_ns_pkt_extrapolated`` — dense cost fit
        over two mid scales and extrapolated to the route count (the
        dense [P, F] compare cannot even be ALLOCATED at 1M:
        2048 x 1M bools is ~8 GB — which is the point); acceptance:
        LPM >= 10x faster than this
      * ``fib_build_ms`` / ``fib_churn_commit_ms`` — full staging+
        upload cost, and ONE /24 flap's commit: must re-ship only the
        touched length plane + the count vector + a bounded slot blob
        (``fib_churn_planes``/``fib_churn_bytes`` pin it)
      * ``fib_ecmp_spread_pct``      — min/max member share over an
        8-way group under hashed flows (the session hash family)
    """
    import jax
    import jax.numpy as jnp

    from vpp_tpu.ops.fib import fib_lookup_dense
    from vpp_tpu.ops.lpm import fib_lookup_lpm, lpm_plane_bytes
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import (
        FLAG_VALID,
        Disposition,
        PacketVector,
        ip4,
    )

    out = {}
    rng = np.random.default_rng(15)
    routes = 1 << 20
    avail = _mem_available_bytes()
    # per-slot columns + planes + host staging + diff base ~ 60 B/route
    # x a 4x safety factor; small boxes downshift instead of OOMing
    while routes > (1 << 16) and avail and routes * 240 > avail:
        routes //= 4
    out["fib_routes"] = routes

    # BGP-shaped length mix (fractions of the feed)
    mix = ((24, 0.55), (23, 0.10), (22, 0.08), (20, 0.07), (19, 0.05),
           (16, 0.06), (21, 0.04), (18, 0.03), (32, 0.015), (8, 0.005))

    def uniq_prefixes(plen, n):
        """n distinct pre-masked networks of one length."""
        shift = 32 - plen
        want = rng.integers(0, 1 << min(plen, 62), int(n * 1.15) + 8,
                            dtype=np.int64)
        want = np.unique(want)[:n]
        return (want.astype(np.uint64) << shift).astype(np.uint32)

    nets, plens = [], []
    left = routes - 1   # one /0 default staged separately
    for plen, frac in mix:
        n = min(int(routes * frac), left)
        if n <= 0:
            continue
        p = uniq_prefixes(plen, n)
        nets.append(p)
        plens.append(np.full(len(p), plen, np.int32))
        left -= len(p)
    if left > 0:  # remainder lands on /24
        p = uniq_prefixes(24, left)
        nets.append(p)
        plens.append(np.full(len(p), 24, np.int32))
    nets = np.concatenate(nets)
    plens = np.concatenate(plens)
    counts = np.bincount(plens, minlength=33)
    counts[0] += 1    # the default route
    counts[25] += 1   # the ECMP capture route (a length the random
    #                   feed never uses, so it can't be shadowed by an
    #                   equal-length duplicate)
    caps = [0] * 33
    for L in range(33):
        if counts[L]:
            caps[L] = int(counts[L] + 64)
    config = DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=16,
        fib_slots=len(nets) + 16, sess_slots=256, nat_mappings=1,
        nat_backends=1, fib_impl="auto", fib_lpm_min_routes=256,
        fib_lpm_mem_mb=512, fib_lpm_plen_caps=tuple(caps),
        fib_ecmp_groups=8, fib_ecmp_ways=8)
    t0 = time.perf_counter()
    dp = Dataplane(config)
    uplink = dp.add_uplink()
    dp.builder.set_nh_group(0, [(ip4("192.168.0.2") + i, uplink, i % 4)
                                for i in range(8)])
    dp.builder.add_routes_np(
        nets, plens, tx_if=np.full(len(nets), uplink, np.int32),
        disp=np.full(len(nets), int(Disposition.REMOTE), np.int32),
        node_id=1)
    dp.builder.add_route("0.0.0.0/0", uplink, Disposition.REMOTE,
                         slot=len(nets), node_id=1)
    # the ECMP spread capture rides a dedicated /25 (longest match
    # beats any feed /8../24 cover; the feed never stages /25s)
    dp.builder.add_route("230.77.0.0/25", uplink, Disposition.REMOTE,
                         slot=len(nets) + 1, group=0)
    dp.swap()
    out["fib_build_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    out["fib_impl_selected"] = dp.fib_impl
    out["fib_plane_mb"] = round(lpm_plane_bytes(config) / (1 << 20), 2)

    def traffic(n, inside_frac=0.7, seed=16):
        r2 = np.random.default_rng(seed)
        dst = r2.integers(0, 1 << 32, n).astype(np.uint32)
        picks = r2.integers(0, len(nets), n)
        host = r2.integers(0, 1 << 32, n).astype(np.uint32)
        masks = np.array([((1 << 32) - 1) ^ ((1 << (32 - p)) - 1)
                          if p else 0 for p in range(33)],
                         np.uint32)[plens[picks]]
        inside = nets[picks] | (host & ~masks)
        dst = np.where(r2.random(n) < inside_frac, inside, dst)
        return PacketVector(
            src_ip=jnp.asarray(r2.integers(0, 1 << 32, n)
                               .astype(np.uint32)),
            dst_ip=jnp.asarray(dst),
            proto=jnp.full((n,), 6, jnp.int32),
            sport=jnp.asarray(r2.integers(1024, 65000, n)
                              .astype(np.int32)),
            dport=jnp.full((n,), 443, jnp.int32),
            ttl=jnp.full((n,), 64, jnp.int32),
            pkt_len=jnp.full((n,), 512, jnp.int32),
            rx_if=jnp.full((n,), uplink, jnp.int32),
            flags=jnp.full((n,), FLAG_VALID, jnp.int32),
        )

    def time_lookup(fn, tables, pkts):
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn(tables, pkts).tx_if)
        ts = []
        for _ in range(iters):
            t1 = time.perf_counter()
            r = jfn(tables, pkts)
            jax.block_until_ready(r.tx_if)
            ts.append(time.perf_counter() - t1)
        n = int(pkts.dst_ip.shape[0])
        return float(np.median(ts)) / n * 1e9

    pkts = traffic(batch)
    out["fib_lookup_lpm_ns_pkt"] = round(
        time_lookup(fib_lookup_lpm, dp.tables, pkts), 1)

    def dense_at(n_routes, dense_batch):
        cfg = DataplaneConfig(
            max_tables=2, max_rules=8, max_global_rules=8,
            max_ifaces=16, fib_slots=n_routes + 4, sess_slots=64,
            nat_mappings=1, nat_backends=1, fib_impl="dense")
        d = Dataplane(cfg)
        up = d.add_uplink()
        k = min(n_routes, len(nets))
        d.builder.add_routes_np(
            nets[:k], plens[:k],
            tx_if=np.full(k, up, np.int32),
            disp=np.full(k, int(Disposition.REMOTE), np.int32))
        d.builder.add_route("0.0.0.0/0", up, Disposition.REMOTE,
                            slot=k)
        d.swap()
        return time_lookup(fib_lookup_dense, d.tables,
                           traffic(dense_batch))

    # native node scale: the seed-era FIB regime (tens of entries)
    out["fib_lookup_dense_ns_pkt"] = round(dense_at(64, batch), 1)
    # linear fit over two mid scales -> extrapolated 1M cost (the
    # [P, F] hit matrix makes a direct 1M dense run unallocatable)
    f1, f2 = 2048, 8192
    n1 = dense_at(f1, 256)
    n2 = dense_at(f2, 256)
    out["fib_lookup_dense_mid_ns_pkt"] = round(n2, 1)
    slope = max((n2 - n1) / (f2 - f1), 0.0)
    extrap = n2 + slope * (routes - f2)
    out["fib_lookup_dense_1m_ns_pkt_extrapolated"] = round(extrap, 1)
    out["fib_lpm_speedup_vs_dense_1m"] = round(
        extrap / max(out["fib_lookup_lpm_ns_pkt"], 1e-9), 1)
    out["fib_lpm_vs_dense_native_x"] = round(
        out["fib_lookup_lpm_ns_pkt"]
        / max(out["fib_lookup_dense_ns_pkt"], 1e-9), 2)

    # --- route churn: ONE /24 flap's commit cost + what it shipped ---
    slot = int(np.nonzero(plens == 24)[0][0])
    pfx = int(nets[slot])
    pfx_s = (f"{pfx >> 24 & 255}.{pfx >> 16 & 255}."
             f"{pfx >> 8 & 255}.{pfx & 255}/24")
    t1 = time.perf_counter()
    dp.builder.del_route(pfx_s)
    dp.builder.add_route(pfx_s, uplink, Disposition.REMOTE, slot=slot,
                         node_id=1)
    dp.swap()
    out["fib_churn_swap_ms"] = round(
        (time.perf_counter() - t1) * 1e3, 2)
    up = dp.builder.fib_upload
    out["fib_churn_commit_ms"] = round(float(up.get("ms", 0.0)), 2)
    out["fib_churn_bytes"] = int(up.get("bytes", 0))
    out["fib_churn_planes"] = sum(
        1 for f in up.get("fields", ()) if f.startswith("fib_lpm_p"))
    out["fib_churn_blob_bytes"] = int(up.get("blob_bytes", 0))

    # --- ECMP spread over the 8-member group (hashed distinct flows) --
    r3 = np.random.default_rng(18)
    n = 4096
    epkts = PacketVector(
        src_ip=jnp.asarray(r3.integers(0, 1 << 32, n)
                           .astype(np.uint32)),
        dst_ip=jnp.asarray((np.uint32(ip4("230.77.0.0"))
                            | r3.integers(0, 128, n)
                            .astype(np.uint32))),
        proto=jnp.full((n,), 6, jnp.int32),
        sport=jnp.asarray(r3.integers(1024, 65000, n)
                          .astype(np.int32)),
        dport=jnp.full((n,), 443, jnp.int32),
        ttl=jnp.full((n,), 64, jnp.int32),
        pkt_len=jnp.full((n,), 512, jnp.int32),
        rx_if=jnp.full((n,), uplink, jnp.int32),
        flags=jnp.full((n,), FLAG_VALID, jnp.int32),
    )
    res = jax.jit(fib_lookup_lpm)(dp.tables, epkts)
    on_grp = np.asarray(res.grp) >= 0
    nh = np.asarray(res.next_hop)[on_grp].astype(np.int64)
    shares = np.bincount(nh - nh.min(), minlength=8)
    shares = np.sort(shares[shares > 0])
    out["fib_ecmp_members_hit"] = int(len(shares))
    out["fib_ecmp_spread_pct"] = round(
        100.0 * float(shares[0]) / max(float(shares[-1]), 1.0), 1)
    return out


def fastpath_bench(args, iters: int = 12, batch: int = 2048) -> dict:
    """Two-tier fast path (ISSUE 3 tentpole): the classify-free
    established-flow kernel vs the full fused chain on an IDENTICAL
    all-established batch, at the headline rule count.

    Primes sessions with one full-chain pass over forward traffic,
    builds the reply batch from the POST-NAT forwarded outputs (what
    the wire would actually carry back), verifies the auto dispatcher
    takes the fast kernel (StepStats.fastpath == 1), then times both
    tiers on fixed tables/now. Reports:

      * ``pipeline_fastpath_us``  — auto-dispatched (fast) step, median
      * ``pipeline_fullpath_us``  — always-full-chain step, median
      * ``fastpath_speedup_x``    — full/fast (acceptance: >= 3x)
    """
    import jax
    import jax.numpy as jnp

    from vpp_tpu.pipeline.graph import make_pipeline_step
    from vpp_tpu.pipeline.vector import Disposition, FLAG_VALID, PacketVector

    dp, uplink = build_dataplane(args.rules, 4)
    # mirror the dataplane's own kernel selection (classifier impl +
    # local-skip gate) so the comparison is the DEPLOYED full chain vs
    # the deployed fast tier
    impl, skip = dp.classifier_impl, dp._skip_local
    step_full = jax.jit(make_pipeline_step(impl, skip, fast=False))
    step_auto = jax.jit(make_pipeline_step(impl, skip, fast=True))

    fwd = build_traffic(batch, uplink, seed=21)
    r1 = step_full(dp.tables, fwd, jnp.int32(1))
    jax.block_until_ready(r1.disp)
    tables = r1.tables
    # replies of every forwarded packet: swap the post-NAT endpoints,
    # ingress on the egress interface (rx_if 0 placeholder on the
    # non-forwarded slots, which are marked invalid)
    fwd_ok = np.asarray(r1.disp) != int(Disposition.DROP)
    pk = r1.pkts
    reply = PacketVector(
        src_ip=jnp.asarray(np.asarray(pk.dst_ip)),
        dst_ip=jnp.asarray(np.asarray(pk.src_ip)),
        proto=pk.proto,
        sport=jnp.asarray(np.asarray(pk.dport)),
        dport=jnp.asarray(np.asarray(pk.sport)),
        ttl=jnp.full((batch,), 64, jnp.int32),
        pkt_len=pk.pkt_len,
        rx_if=jnp.asarray(
            np.where(fwd_ok, np.asarray(r1.tx_if), 0).astype(np.int32)
        ),
        flags=jnp.asarray(
            np.where(fwd_ok, FLAG_VALID, 0).astype(np.int32)
        ),
    )
    out = {"fastpath_batch": batch, "fastpath_rules": args.rules}
    probe = step_auto(tables, reply, jnp.int32(2))
    jax.block_until_ready(probe.disp)
    out["fastpath_engaged"] = bool(int(probe.stats.fastpath) == 1)
    out["fastpath_hit_pkts"] = int(probe.stats.sess_hits)

    def med_us(step):
        jax.block_until_ready(step(tables, reply, jnp.int32(2)).disp)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(step(tables, reply, jnp.int32(2)).disp)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) * 1e6

    full_us = med_us(step_full)
    fast_us = med_us(step_auto)
    out["pipeline_fullpath_us"] = round(full_us, 1)
    out["pipeline_fastpath_us"] = round(fast_us, 1)
    out["fastpath_speedup_x"] = round(full_us / max(fast_us, 1e-9), 2)
    return out


def ml_stage_bench(args, iters: int = 12, batch: int = 2048) -> dict:
    """Per-packet ML scoring stage (ISSUE 10 tentpole): the ADDED cost
    of int8 MLP inference riding inside the fused step, at the
    headline rule count.

    Compiles the deployed chain twice — ml_mode off vs score (same
    classifier impl/local-skip selection, same tables: the glb_ml_*
    planes are staged either way, the off variant just never reads
    them) — and reports the delta. The stage rides INSIDE the one
    jitted program (no extra dispatch), so the delta IS the marginal
    matmul cost. Keys:

      * ``ml_stage_ns_pkt``           — (t_score − t_off)/batch
      * ``ml_headline_overhead_pct``  — 100·(t_score − t_off)/t_off
                                        (acceptance: < 10)
      * ``ml_enforce_overhead_pct``   — enforce-mode delta (the
                                        verdict fold's extra cost)
      * ``ml_swap_zero_reship``       — 1 when an ACL-only epoch swap
                                        reuses the staged model's
                                        device arrays by identity
                                        (acceptance: 1)
    """
    import jax
    import jax.numpy as jnp

    from vpp_tpu.ml.train import train_and_pack
    from vpp_tpu.pipeline.graph import make_pipeline_step

    dp, uplink = build_dataplane(args.rules, 4, ml_stage="score")
    model, report = train_and_pack(kind="mlp", hidden=16,
                                   samples=2048, action="drop")
    with dp.commit_lock:
        dp.builder.set_ml_model(model)
        dp.swap()
    out = {
        "ml_stage_batch": batch, "ml_stage_rules": args.rules,
        "ml_stage_kind": model.kind, "ml_stage_hidden": model.hidden,
        "ml_train_accuracy": round(report["accuracy"], 4),
    }
    impl, skip = dp.classifier_impl, dp._skip_local
    steps = {
        mode: jax.jit(make_pipeline_step(impl, skip, ml_mode=mode))
        for mode in ("off", "score", "enforce")
    }
    pkts = build_traffic(batch, uplink, seed=33)
    tables = dp.tables

    def med_us(step):
        jax.block_until_ready(step(tables, pkts, jnp.int32(2)).disp)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(step(tables, pkts, jnp.int32(2)).disp)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) * 1e6

    t_off = med_us(steps["off"])
    t_score = med_us(steps["score"])
    t_enforce = med_us(steps["enforce"])
    probe = steps["score"](tables, pkts, jnp.int32(2))
    out["ml_stage_scored"] = int(probe.stats.ml_scored)
    out["ml_stage_flagged_pct"] = round(
        100.0 * int(probe.stats.ml_flagged)
        / max(int(probe.stats.ml_scored), 1), 2)
    out["ml_fullpath_us"] = round(t_off, 1)
    out["ml_scorepath_us"] = round(t_score, 1)
    out["ml_stage_ns_pkt"] = round(
        max(t_score - t_off, 0.0) / batch * 1e3, 2)
    out["ml_headline_overhead_pct"] = round(
        100.0 * (t_score - t_off) / max(t_off, 1e-9), 2)
    out["ml_enforce_overhead_pct"] = round(
        100.0 * (t_enforce - t_off) / max(t_off, 1e-9), 2)
    # model epoch-swap plane reuse: an ACL-only churn must NOT re-ship
    # the model group — the cached device arrays carry over by identity
    ml_plane_before = dp.tables.glb_ml_w1
    with dp.commit_lock:
        dp.builder.set_global_table(build_rules(max(args.rules // 2, 2)))
        dp.swap()
    out["ml_swap_zero_reship"] = int(
        dp.tables.glb_ml_w1 is ml_plane_before)
    return out


def latency_telemetry_bench(args, iters: int = 12,
                            batch: int = 2048) -> dict:
    """Device telemetry plane (ISSUE 11 tentpole): the cost of the
    in-step wire-latency histogram + flow sketch, and the dataset the
    adaptive latency governor (ROADMAP item 3) will close its loop on.

    Three captures:

      * **overhead** — the fused chain compiled with telemetry off vs
        full over the same tables/traffic; the delta IS the marginal
        scatter-add/compare cost (``telemetry_overhead_pct``,
        acceptance: < 5).
      * **offered load vs on-device tail** — an open-loop sweep: each
        packed batch is stamped with its scheduled GENERATION time and
        paced at 50/80/95% of the measured service rate; the device
        histograms ``dispatch − stamp``, so queueing delay shows up in
        the on-device p99/p99.9 exactly as it would for a governor
        (``latency_telemetry_sweep`` + the headline
        ``wire_latency_{p50,p99,p999}_us_device`` from the top rung).
      * **sketch fidelity** — a Zipf flow mix through a fresh sketch;
        count-min estimates vs exact host counts
        (``flow_sketch_error_pct`` = aggregate overcount share) and
        the top-K candidate table's recall of the true heavy hitters
        (``flow_topk_recall``).
    """
    import jax
    import jax.numpy as jnp

    from vpp_tpu.ops.telemetry import (
        quantiles_from_bins,
        sketch_cols,
        tel_clock_us,
        tel_flow_hash_np,
    )
    from vpp_tpu.pipeline.dataplane import (
        pack_packet_columns,
        packed_input_zeros,
    )
    from vpp_tpu.pipeline.vector import FLAG_VALID, PacketVector, ip4

    out = {"latency_telemetry_batch": batch,
           "latency_telemetry_rules": args.rules}

    # --- (1) overhead: off vs full over the PACKED boundary ---
    # Timed on process_packed, not the plain step: the wire-latency
    # histogram update lives in the packed/chained/ring boundary
    # wrappers (dataplane._packed_call), so a plain-step delta would
    # structurally exclude it and only measure the sketch fold. The
    # packed delta is the telemetry cost the pump actually pays.
    dp_off, _up_off = build_dataplane(args.rules, 4, telemetry="off")
    dp, uplink = build_dataplane(args.rules, 4, telemetry="full")
    pkts = build_traffic(batch, uplink, seed=41)
    cols = {f: np.asarray(getattr(pkts, f))
            for f in ("src_ip", "dst_ip", "proto", "sport", "dport",
                      "ttl", "pkt_len", "rx_if", "flags")}
    flat = packed_input_zeros(batch)
    pack_packet_columns(flat.view(np.uint32), cols, batch)

    # interleaved windows, per-mode MINIMUM of window medians (the
    # session-bench honest estimator: sequential medians drift with
    # box load and can even read negative) — off-mode dataplanes
    # ignore the stamp kwargs, so one call shape serves both sides
    for d in (dp_off, dp):
        jax.block_until_ready(d.process_packed(flat, now=2,
                                               stamp_us=7, now_us=9))
    best = {"off": float("inf"), "full": float("inf")}
    for _w in range(max(iters // 2, 3)):
        for mode, d in (("off", dp_off), ("full", dp)):
            ts = []
            for _ in range(4):
                t0 = time.perf_counter()
                jax.block_until_ready(d.process_packed(
                    flat, now=3, stamp_us=7, now_us=9))
                ts.append(time.perf_counter() - t0)
            best[mode] = min(best[mode], float(np.median(ts)))
    t_off = best["off"] * 1e6
    t_full = best["full"] * 1e6
    out["telemetry_fullpath_us"] = round(t_off, 1)
    out["telemetry_telpath_us"] = round(t_full, 1)
    out["telemetry_ns_pkt"] = round(
        max(t_full - t_off, 0.0) / batch * 1e3, 2)
    out["telemetry_overhead_pct"] = round(
        100.0 * (t_full - t_off) / max(t_off, 1e-9), 2)

    # --- (2) open-loop offered-load sweep on the packed path ---
    service_us = max(t_full, 1.0)
    out["telemetry_service_us"] = round(service_us, 1)

    def run_rung(load_pct: int, rounds: int = 40) -> dict:
        before = dp.telemetry_snapshot()["bins"].copy()
        interarrival = service_us * 100.0 / load_pct
        g = float(tel_clock_us()) + 2 * interarrival
        for _ in range(rounds):
            # clamp the pace wait: a tel_clock_us() 31-bit wrap
            # mid-rung would otherwise compute a ~2^31 µs sleep and
            # hang the bench for half an hour (the device side already
            # discards wrap-spanning samples as negative latency)
            wait_us = min(g - tel_clock_us(), 5 * interarrival)
            if wait_us > 0:
                time.sleep(wait_us / 1e6)
            jax.block_until_ready(dp.process_packed(
                flat, now=4, stamp_us=int(g) & 0x7FFFFFFF))
            g += interarrival
        bins = dp.telemetry_snapshot()["bins"] - before
        p50, p99, p999 = quantiles_from_bins(bins)
        return {"load_pct": load_pct, "p50_us": round(p50, 1),
                "p99_us": round(p99, 1), "p999_us": round(p999, 1),
                "observed": int(bins.sum())}

    sweep = [run_rung(pct) for pct in (50, 80, 95)]
    out["latency_telemetry_sweep"] = sweep
    top = sweep[-1]
    out["wire_latency_p50_us_device"] = top["p50_us"]
    out["wire_latency_p99_us_device"] = top["p99_us"]
    out["wire_latency_p999_us_device"] = top["p999_us"]

    # --- (3) sketch fidelity on a FRESH sketch (small dataplane) ---
    dp3, up3 = build_dataplane(64, 2, telemetry="full")
    rng = np.random.default_rng(17)
    n_flows, rounds, b3 = 512, 40, 512
    ranks = np.arange(1, n_flows + 1, dtype=np.float64)
    probs = ranks ** -1.2
    probs /= probs.sum()
    true = np.zeros(n_flows, np.int64)
    base_src = ip4("198.18.0.0")
    dst = ip4("10.1.1.9")
    for r in range(rounds):
        ids = rng.choice(n_flows, b3, p=probs)
        np.add.at(true, ids, 1)
        pv = PacketVector(
            src_ip=jnp.asarray((base_src + ids).astype(np.uint32)),
            dst_ip=jnp.full((b3,), dst, jnp.uint32),
            proto=jnp.full((b3,), 6, jnp.int32),
            sport=jnp.asarray((1024 + ids).astype(np.int32)),
            dport=jnp.full((b3,), 8080, jnp.int32),
            ttl=jnp.full((b3,), 64, jnp.int32),
            pkt_len=jnp.full((b3,), 128, jnp.int32),
            rx_if=jnp.full((b3,), up3, jnp.int32),
            flags=jnp.full((b3,), FLAG_VALID, jnp.int32),
        )
        dp3.process(pv, now=2 + r)
    snap = dp3.telemetry_snapshot()
    sk = np.asarray(dp3.tables.tel_sketch)
    d, w = sk.shape
    ids = np.arange(n_flows)
    h0 = tel_flow_hash_np(
        (base_src + ids).astype(np.uint32),
        np.full(n_flows, dst, np.uint32), 1024 + ids,
        np.full(n_flows, 8080), np.full(n_flows, 6))
    est = np.min(np.stack(
        [sk[r_, sketch_cols(h0, r_, w)] for r_ in range(d)]), axis=0)
    over = est.astype(np.int64) - true
    out["flow_sketch_overcount_max"] = int(over.max())
    out["flow_sketch_error_pct"] = round(
        100.0 * float(over.sum()) / max(float(true.sum()), 1.0), 3)
    k = len(snap["top_key"])
    top_true = set(h0[np.argsort(-true)[:k]].tolist())
    out["flow_topk_recall"] = round(
        len(top_true & set(snap["top_key"].tolist())) / k, 3)
    return out


def latency_slo_bench(args, frame_pkts: int = 16,
                      rung_s: float = 1.2) -> dict:
    """Reflex-plane latency governor ladder (ISSUE 13 tentpole;
    ROADMAP item 3's bench keys). The ring-to-ring wire path under a
    mixed load — bulk UDP frames plus a paced priority lane (dport
    9999) — swept at 50/80/95/120% of the measured saturation rate,
    once UNGOVERNED (the open-loop pre-13 pump) and once GOVERNED
    (``latency_slo_us`` = 2x the lone-frame floor), plus a square-wave
    burst scenario for tail amplification. Headline keys:

      * ``latency_slo_p50/p99/p999_us`` — the governed PRIORITY lane
        at the 95% rung (acceptance: p99 within 2x of
        ``latency_slo_floor_us`` while
        ``latency_slo_goodput_ratio`` >= 0.9);
      * ``latency_slo_shed_pct`` — attributed overload shedding at
        the 120% rung (the SLO-unattainable regime — bulk drops are
        explicit ``drops_overload``, never silent queue growth);
      * ``latency_slo_burst_p99_us_{governed,ungoverned}`` — the
        priority tail under a square-wave offered load;
      * ``latency_slo_io_callbacks`` / ``latency_slo_new_step_variants``
        — the governor must keep the ring io_callback-free and trace
        ZERO new jitted step variants (host-side shaping only).
    """
    import collections
    import threading

    from vpp_tpu.io.governor import LatencyGovernor, PriorityFilter
    from vpp_tpu.io.pump import DataplanePump
    from vpp_tpu.io.rings import IORingPair
    from vpp_tpu.native.pktio import PacketCodec
    from vpp_tpu.pipeline.dataplane import jit_compile_totals
    from vpp_tpu.pipeline.vector import VEC

    dp = build_fwd_dataplane()
    client_if = dp.pod_if[("default", "p0")]
    bulk_wire = [wire_udp(i) for i in range(frame_pkts)]
    pri_wire = [wire_udp(7, dport=9999)]  # 1-pkt reflex frame

    def capture(bulk_fps, pri_fps, duration, slo_us=0,
                square=None) -> dict:
        """One pump lifecycle: paced bulk + priority producers,
        sequence-stamped ring-to-ring latency per frame, split by
        lane. ``square=(hi_fps, lo_fps, half_s)`` overrides bulk
        pacing with a square wave."""
        rings = IORingPair(n_slots=256, snap=512)
        codec = PacketCodec(snap=rings.rx.snap)
        scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
        gov = None
        if slo_us > 0:
            gov = LatencyGovernor(slo_us, tick_s=0.01,
                                  brownout_ticks=2, recover_ticks=3)
        pump = DataplanePump(dp, rings, mode="persistent",
                             governor=gov,
                             priority=PriorityFilter(ports=(9999,)))
        pump.warm()
        pump.start()
        push_log = {}   # seq -> (t_push, is_pri, n_pkts)
        lat = collections.defaultdict(list)   # lane -> [seconds]
        counts = {"offered_bulk": 0, "offered_pri": 0,
                  "delivered_bulk": 0, "delivered_pri": 0,
                  "pushed_fail": 0}
        seq_box = [0]
        stop = threading.Event()

        def push(wire, is_pri) -> None:
            cols, n = codec.parse(wire, client_if, scratch)
            seq = seq_box[0]
            cols["meta"][:n] = seq
            t = time.perf_counter()
            if rings.rx.push(cols, n, payload=scratch):
                push_log[seq] = (t, is_pri, n)
                seq_box[0] += 1
                counts["offered_pri" if is_pri else "offered_bulk"] += n
            else:
                counts["pushed_fail"] += 1

        def producer() -> None:
            t0 = time.perf_counter()
            bulk_credit = pri_credit = 0.0
            last = t0
            while not stop.is_set():
                now = time.perf_counter()
                dt, last = now - last, now
                fps = bulk_fps
                if square is not None:
                    hi, lo, half = square
                    fps = hi if int((now - t0) / half) % 2 == 0 else lo
                bulk_credit = min(bulk_credit + fps * dt, 64.0)
                pri_credit = min(pri_credit + pri_fps * dt, 8.0)
                while pri_credit >= 1.0:
                    push(pri_wire, True)
                    pri_credit -= 1.0
                while bulk_credit >= 1.0:
                    push(bulk_wire, False)
                    bulk_credit -= 1.0
                time.sleep(0.001)

        def drain_one() -> bool:
            g = rings.tx.peek()
            if g is None:
                return False
            seq = int(g.cols["meta"][0])
            rings.tx.release()
            rec = push_log.pop(seq, None)
            if rec is not None:
                t_push, is_pri, n = rec
                lat["pri" if is_pri else "bulk"].append(
                    time.perf_counter() - t_push)
                counts["delivered_pri" if is_pri
                       else "delivered_bulk"] += n
            return True

        prod = threading.Thread(target=producer, daemon=True)
        t_start = time.perf_counter()
        prod.start()
        while time.perf_counter() < t_start + duration:
            if not drain_one():
                time.sleep(0.0002)
        stop.set()
        prod.join()
        # bounded flush: shed frames never reach tx, so idle silence
        # (not an empty push_log) ends the drain
        idle_since = None
        flush_deadline = time.perf_counter() + 8.0
        while push_log and time.perf_counter() < flush_deadline:
            if drain_one():
                idle_since = None
                continue
            now = time.perf_counter()
            if idle_since is None:
                idle_since = now
            elif now - idle_since > 1.0:
                break
            time.sleep(0.002)
        elapsed = time.perf_counter() - t_start
        pump.stop()
        s = dict(pump.stats)
        rings.close()

        def pcts(xs):
            if not xs:
                return 0.0, 0.0, 0.0
            a = np.asarray(xs) * 1e6
            return (float(np.percentile(a, 50)),
                    float(np.percentile(a, 99)),
                    float(np.percentile(a, 99.9)))

        p50a, p99a, p999a = pcts(lat["pri"] + lat["bulk"])
        p50p, p99p, p999p = pcts(lat["pri"])
        offered = counts["offered_bulk"] + counts["offered_pri"]
        return {
            "p50_us": round(p50a, 1), "p99_us": round(p99a, 1),
            "p999_us": round(p999a, 1),
            "pri_p50_us": round(p50p, 1), "pri_p99_us": round(p99p, 1),
            "pri_p999_us": round(p999p, 1),
            "bulk_goodput_fps": round(
                len(lat["bulk"]) / max(elapsed, 1e-9), 1),
            "bulk_delivered_pkts": counts["delivered_bulk"],
            "offered_pkts": offered,
            "shed_pct": round(100.0 * int(s.get("drops_overload", 0))
                              / max(offered, 1), 2),
            "preempts": int(s.get("priority_preempts", 0)),
            "io_callbacks": int(s.get("io_callbacks", 0)),
            "mode": (gov.snapshot()["mode"] if gov is not None
                     else "off"),
            "frames_drained": len(lat["pri"]) + len(lat["bulk"]),
        }

    out = {"latency_slo_frame_pkts": frame_pkts}
    # (1) lone-frame floor: a paced priority-only trickle — the
    # latency the reflex lane is entitled to
    floor = capture(bulk_fps=0, pri_fps=50, duration=rung_s)
    floor_us = max(floor["pri_p50_us"], 1.0)
    out["latency_slo_floor_us"] = round(floor_us, 1)
    # every later capture must reuse the already-compiled ring
    # variants: the governor is host-side shaping ONLY
    jit_labels0 = set(jit_compile_totals())
    # (2) harness saturation rate (unpaced bulk)
    sat = capture(bulk_fps=1e9, pri_fps=0, duration=1.5)
    sat_fps = max(sat["bulk_goodput_fps"], 1.0)
    out["latency_slo_sat_fps"] = round(sat_fps, 1)
    slo_us = 2.0 * floor_us
    out["latency_slo_us"] = round(slo_us, 1)
    # (3) the offered-load ladder x {ungoverned, governed}
    ladder = []
    io_callbacks = 0
    for pct in (50, 80, 95, 120):
        for governed in (False, True):
            row = capture(bulk_fps=sat_fps * pct / 100.0, pri_fps=50,
                          duration=rung_s,
                          slo_us=slo_us if governed else 0)
            row["load_pct"] = pct
            row["governed"] = int(governed)
            io_callbacks += row.pop("io_callbacks")
            ladder.append(row)
    out["latency_slo_ladder"] = ladder

    def _row(pct, governed):
        return next(r for r in ladder
                    if r["load_pct"] == pct and r["governed"] == governed)

    g95, u95 = _row(95, 1), _row(95, 0)
    # all three headline quantiles are the PRIORITY lane's (the key
    # table's contract) — the combined distribution is bulk-dominated
    # at this rung and lives in the ladder rows as p*_us
    out["latency_slo_p50_us"] = g95["pri_p50_us"]
    out["latency_slo_p99_us"] = g95["pri_p99_us"]
    out["latency_slo_p999_us"] = g95["pri_p999_us"]
    out["latency_slo_p99_vs_floor_x"] = round(
        g95["pri_p99_us"] / max(floor_us, 1e-9), 2)
    out["latency_slo_p99_vs_ungoverned_x"] = round(
        u95["pri_p99_us"] / max(g95["pri_p99_us"], 1e-9), 2)
    out["latency_slo_goodput_ratio"] = round(
        g95["bulk_delivered_pkts"] / max(u95["bulk_delivered_pkts"], 1),
        3)
    out["latency_slo_shed_pct"] = _row(120, 1)["shed_pct"]
    out["latency_slo_ungoverned_p99_us"] = u95["p99_us"]
    # (4) tail amplification under burst: square-wave offered load
    # (130% / 10% of saturation), priority lane paced through it
    for governed in (False, True):
        row = capture(bulk_fps=0, pri_fps=50, duration=2.4,
                      slo_us=slo_us if governed else 0,
                      square=(sat_fps * 1.3, sat_fps * 0.1, 0.3))
        key = "governed" if governed else "ungoverned"
        out[f"latency_slo_burst_p99_us_{key}"] = row["pri_p99_us"]
        io_callbacks += row["io_callbacks"]
    out["latency_slo_burst_amplification_x"] = round(
        out["latency_slo_burst_p99_us_ungoverned"]
        / max(out["latency_slo_burst_p99_us_governed"], 1e-9), 2)
    out["latency_slo_io_callbacks"] = io_callbacks
    out["latency_slo_new_step_variants"] = len(
        set(jit_compile_totals()) - jit_labels0)
    return out


def tenant_isolation_bench(args, frame_pkts: int = 16,
                           phase_s: float = 1.0) -> dict:
    """Multi-tenant isolation scenario (ISSUE 14 acceptance;
    docs/TENANCY.md). Four tenants on the persistent wire path —
    device token buckets + capacity attribution + the pump's
    weighted-fair dequeue — with tenant 4 misbehaving at 4x its quota
    through a square-wave burst while tenants 1..3 stay inside
    theirs. Proof keys:

      * ``tenant_isolation_goodput_ratio_min`` — the worst
        well-behaved tenant's overload-phase goodput vs its SOLO run
        (acceptance: >= 0.9; one hog must not tax the rest);
      * ``tenant_isolation_p99_ratio_max`` — the worst well-behaved
        p99 amplification vs solo (acceptance: <= 2x);
      * ``tenant_isolation_attributed_pct`` — the misbehaving
        tenant's overage accounted as
        ``drops_total{reason="tenant_quota"}`` (device bucket) +
        per-tenant brownout sheds (``reason="overload"``) — nothing
        silent;
      * ``tenant_isolation_conserved`` — EXACT packet conservation
        over the whole overload phase:
        offered == goodput + tenant_quota + shed + shutdown/error.
    """
    import collections
    import threading

    from vpp_tpu.io.governor import LatencyGovernor
    from vpp_tpu.io.pump import DataplanePump
    from vpp_tpu.io.rings import IORingPair
    from vpp_tpu.native.pktio import PacketCodec
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import VEC, Disposition
    from vpp_tpu.tenancy.sched import (
        TenantClassifier,
        tenant_entries_from_config,
    )

    N, MIS = 4, 4  # tenants 1..N, tenant MIS misbehaves
    config = DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=16, max_ifaces=64,
        fib_slots=64, sess_slots=1 << 12, nat_mappings=1,
        nat_backends=1, tenancy="on", tenancy_tenants=N + 1,
    )
    dp = Dataplane(config)
    for i in range(32):
        idx = dp.add_pod_interface(("default", f"p{i}"))
        dp.builder.add_route(f"10.1.1.{i + 2}/32", idx,
                             Disposition.LOCAL)
    t_net = {t: f"10.{50 + t}.0.0/16" for t in range(1, N + 1)}
    t_src = {t: f"10.{50 + t}.0.9" for t in range(1, N + 1)}
    # WFQ weights: the well-behaved class outweighs the (eventual)
    # hog 4:1 — the gold-vs-bronze shape real gateways run; quotas
    # are staged after the sat capture (rate 0 = unlimited for now)
    t_weight = {t: (1 if t == MIS else 4) for t in range(1, N + 1)}
    for t in range(1, N + 1):
        dp.builder.set_tenant(t, prefixes=[t_net[t]],
                              weight=t_weight[t])
    dp.swap()
    client_if = dp.pod_if[("default", "p0")]
    wires = {t: [wire_udp(i, src=t_src[t]) for i in range(frame_pkts)]
             for t in range(1, N + 1)}
    classifier = TenantClassifier(tenant_entries_from_config(
        [{"id": t, "prefixes": [t_net[t]], "weight": t_weight[t]}
         for t in range(1, N + 1)]))

    def capture(offered_fps, duration, slo_us=0, square_t=None,
                square=None) -> dict:
        """One pump lifecycle: per-tenant paced producers
        (``offered_fps``: tenant -> frames/s; ``square`` overrides
        tenant ``square_t``'s pacing with (hi, lo, half_s)),
        sequence-stamped wire latency split per tenant, device
        tenant-plane DELTAS (the state planes persist across pump
        lifecycles) and the pump's per-tenant lane ledger."""
        snap0 = dp.tenant_snapshot()
        rings = IORingPair(n_slots=256, snap=512)
        codec = PacketCodec(snap=rings.rx.snap)
        scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
        gov = (LatencyGovernor(slo_us, tick_s=0.01, brownout_ticks=2,
                               recover_ticks=3) if slo_us > 0 else None)
        # latency-lean geometry for the whole scenario: 1-slot ring
        # windows + a one-frame (frame_pkts=16) WFQ service quantum
        # bound every frame's wait behind OTHER tenants' bulk in the
        # shared window pipeline (the WFQ delay bound scales with the
        # quantum), so the isolation comparison measures the bucket +
        # the lanes, not ring batching depth
        pump = DataplanePump(dp, rings, mode="persistent",
                             governor=gov, tenants=classifier,
                             ring_slots=1,
                             tenant_quantum=frame_pkts)
        pump.warm()
        pump.start()
        push_log = {}
        lat = collections.defaultdict(list)
        offered = {t: 0 for t in offered_fps}
        seq_box = [0]
        stop = threading.Event()

        def push(t) -> None:
            cols, n = codec.parse(wires[t], client_if, scratch)
            seq = seq_box[0]
            cols["meta"][:n] = seq
            tm = time.perf_counter()
            if rings.rx.push(cols, n, payload=scratch):
                push_log[seq] = (tm, t)
                seq_box[0] += 1
                offered[t] += n

        def producer() -> None:
            t0 = time.perf_counter()
            # staggered initial credits de-synchronize same-rate
            # producers: without the offsets every tenant's frame
            # lands in the same pacing tick and the WFQ tie-break
            # (by tenant id) turns into a fixed service-order bias
            credit = {t: i / max(1, len(offered_fps))
                      for i, t in enumerate(offered_fps)}
            last = t0
            while not stop.is_set():
                now = time.perf_counter()
                dt, last = now - last, now
                for t, fps in offered_fps.items():
                    if square is not None and t == square_t:
                        hi, lo, half = square
                        fps = (hi if int((now - t0) / half) % 2 == 0
                               else lo)
                    credit[t] = min(credit[t] + fps * dt, 32.0)
                    while credit[t] >= 1.0:
                        push(t)
                        credit[t] -= 1.0
                time.sleep(0.001)

        def drain_one() -> bool:
            g = rings.tx.peek()
            if g is None:
                return False
            seq = int(g.cols["meta"][0])
            rings.tx.release()
            rec = push_log.pop(seq, None)
            if rec is not None:
                lat[rec[1]].append(time.perf_counter() - rec[0])
            return True

        prod = threading.Thread(target=producer, daemon=True)
        t_start = time.perf_counter()
        prod.start()
        while time.perf_counter() < t_start + duration:
            if not drain_one():
                time.sleep(0.0002)
        stop.set()
        prod.join()
        idle_since = None
        flush_deadline = time.perf_counter() + 8.0
        while push_log and time.perf_counter() < flush_deadline:
            if drain_one():
                idle_since = None
                continue
            now = time.perf_counter()
            if idle_since is None:
                idle_since = now
            elif now - idle_since > 1.0:
                break
            time.sleep(0.002)
        elapsed = time.perf_counter() - t_start
        pump.stop()  # grafts the ring-carried tenant planes back
        s = dict(pump.stats)
        tsnap = pump.tenant_io_snapshot()
        tio = tsnap["io"]
        # WFQ-lane residue: frames still queued when the flush
        # deadline expired are neither goodput nor an attributed drop
        # (stop() abandons only DISPATCHED frames as drops_shutdown;
        # the scheduler queues are simply left) — the conservation
        # identity must count them or a slow flush reads as a
        # (nonexistent) conservation bug
        queued_residual = sum(q.get("pkts", 0)
                              for q in tsnap["queued"].values())
        # frames the stalled scan frontier never classified sit in the
        # rx ring at the deadline: offered minus scan-classified
        # (io["pkts"] counts at classification) — without this term a
        # slow flush on the 1-core harness reads as a conservation
        # violation
        unclassified = max(0, sum(offered.values())
                           - sum(v.get("pkts", 0)
                                 for v in tio.values()))
        rings.close()
        snap1 = dp.tenant_snapshot()

        def delta(key, t):
            d0 = int(snap0[key][t]) if snap0 is not None else 0
            return int(snap1[key][t]) - d0

        rows = {}
        for t in offered_fps:
            xs = np.asarray(lat[t]) * 1e6 if lat[t] else None
            rows[t] = {
                "offered_pkts": offered[t],
                "goodput_pkts": delta("tx", t),
                "goodput_fps": round(len(lat[t]) / max(elapsed, 1e-9),
                                     1),
                "quota_drop_pkts": delta("rl_drops", t),
                "dev_rx_pkts": delta("rx", t),
                "shed_pkts": int(tio.get(t, {}).get("shed_pkts", 0)),
                "p50_us": (round(float(np.percentile(xs, 50)), 1)
                           if xs is not None else 0.0),
                "p99_us": (round(float(np.percentile(xs, 99)), 1)
                           if xs is not None else 0.0),
            }
        return {
            "tenants": rows,
            "drops_shutdown": int(s.get("drops_shutdown", 0)),
            "drops_error": int(s.get("drops_error", 0)),
            "queued_residual": int(queued_residual) + int(unclassified),
            "io_callbacks": int(s.get("io_callbacks", 0)),
        }

    out = {"tenant_isolation_tenants": N,
           "tenant_isolation_frame_pkts": frame_pkts}
    # (1) floor + harness saturation (tenant 1, unlimited quota)
    floor = capture({1: 40}, duration=0.8)["tenants"][1]
    floor_us = max(floor["p50_us"], 1.0)
    sat = capture({1: 1e9}, duration=1.2)["tenants"][1]
    sat_fps = max(sat["goodput_fps"], 4.0)
    out["tenant_isolation_floor_us"] = round(floor_us, 1)
    out["tenant_isolation_sat_fps"] = round(sat_fps, 1)
    # (2) quotas: each tenant gets 5% of sat so even the hog's 4x
    # overage keeps TOTAL offered well under saturation (~32% avg,
    # 42% burst-high) — on this CPU harness a quota-dropped packet
    # costs the same device time as a forwarded one (the LATENCY.md
    # round-13 caveat), so the comparison must isolate the BUCKET and
    # the WFQ lanes, not queueing collapse; well-behaved tenants
    # offer 80% of quota, the hog 4x quota through a square wave
    quota_fps = max(1.0, 0.10 * sat_fps)
    quota_pps = quota_fps * frame_pkts
    rate = max(1, int(round(quota_pps / Dataplane.TICKS_PER_SEC)))
    with dp.commit_lock:
        for t in range(1, N + 1):
            dp.builder.set_tenant(t, prefixes=[t_net[t]],
                                  weight=t_weight[t],
                                  rate=rate, burst=4 * rate)
        dp.swap()
    out["tenant_isolation_quota_pps"] = round(quota_pps, 1)
    well_fps = 0.8 * quota_fps
    # (3) solo baselines for the well-behaved tenants
    solo = {}
    for t in range(1, N):
        solo[t] = capture({t: well_fps},
                          duration=3.0 * phase_s)["tenants"][t]
    out["tenant_isolation_solo"] = {
        str(t): {"goodput_fps": solo[t]["goodput_fps"],
                 "p99_us": solo[t]["p99_us"]} for t in solo}
    # (4) the overload phase: tenant MIS at 4x quota (square wave
    # 6x/2x), everyone else unchanged. The device token bucket
    # absorbs the overage (attributed tenant_quota) and WFQ keeps the
    # well-behaved tenants' queues empty; the shallow ring windows
    # above keep their in-flight depth solo-like
    over = capture(
        {**{t: well_fps for t in range(1, N)}, MIS: 4 * quota_fps},
        duration=5.0, square_t=MIS,
        square=(6 * quota_fps, 2 * quota_fps, 0.25))
    rows = over["tenants"]
    out["tenant_isolation_overload"] = {
        str(t): dict(rows[t]) for t in rows}
    ratios_g, ratios_p = [], []
    # the well-behaved tenants are configured IDENTICALLY (same rate/
    # burst/weight/offered), so the median of their solo p99s is one
    # shared baseline: a single tenant's ~75-sample solo p99 swings
    # 2x run-to-run on this 1-core harness (the dominant ratio noise),
    # the median-of-3 does not — per-tenant overload p99s still
    # compare individually against it
    solo_p99_med = max(float(np.median([s["p99_us"]
                                        for s in solo.values()])), 1e-9)
    for t in range(1, N):
        ratios_g.append(rows[t]["goodput_fps"]
                        / max(solo[t]["goodput_fps"], 1e-9))
        ratios_p.append(rows[t]["p99_us"] / solo_p99_med)
    out["tenant_isolation_goodput_ratio_min"] = round(min(ratios_g), 3)
    out["tenant_isolation_p99_ratio_max"] = round(max(ratios_p), 2)
    # (5) attribution + EXACT conservation over the overload phase
    mis = rows[MIS]
    overage = max(1, mis["offered_pkts"] - mis["goodput_pkts"])
    out["tenant_isolation_mis_quota_drop_pkts"] = mis["quota_drop_pkts"]
    out["tenant_isolation_mis_shed_pkts"] = mis["shed_pkts"]
    out["tenant_isolation_attributed_pct"] = round(
        100.0 * (mis["quota_drop_pkts"] + mis["shed_pkts"]) / overage,
        2)
    offered_total = sum(r["offered_pkts"] for r in rows.values())
    accounted = (sum(r["goodput_pkts"] + r["quota_drop_pkts"]
                     + r["shed_pkts"] for r in rows.values())
                 + over["drops_shutdown"] + over["drops_error"]
                 + over["queued_residual"])
    out["tenant_isolation_conserved"] = int(offered_total == accounted)
    out["tenant_isolation_residual_pkts"] = over["queued_residual"]
    out["tenant_isolation_io_callbacks"] = over["io_callbacks"]
    return out


def sub_benches(args):
    """BASELINE configs #1/#3/#4 as secondary metrics."""
    import jax
    import jax.numpy as jnp

    from vpp_tpu.pipeline.graph import pipeline_step
    from vpp_tpu.pipeline.vector import ip4

    out = {}
    step = jax.jit(pipeline_step, donate_argnums=(0,))

    # #1 pod-to-pod forwarding (iperf analog)
    dp = build_fwd_dataplane()
    mpps, _ = measure_mpps(
        step, dp.tables, build_pod_traffic(args.packets), args.iters, args.warmup
    )
    out["pod_to_pod_fwd_mpps"] = round(mpps, 1)

    # #3 NAT44 100-backend LB: all traffic through the VIP
    dp, uplink = build_dataplane(16, args.backends)
    pkts = build_traffic(args.packets, uplink, seed=5)
    pkts = pkts._replace(
        dst_ip=jnp.full_like(pkts.dst_ip, ip4("10.96.0.10")),
        dport=jnp.full_like(pkts.dport, 80),
    )
    mpps, _ = measure_mpps(step, dp.tables, pkts, args.iters, args.warmup)
    out["nat44_vip_lb_mpps"] = round(mpps, 1)

    # #4 VXLAN overlay: remote-disposed traffic + encap kernel
    from vpp_tpu.ops.vxlan import vxlan_encap
    from vpp_tpu.pipeline.vector import Disposition

    dp, uplink = build_dataplane(16, 1)
    dp.builder.add_route(
        "10.2.0.0/16", uplink, Disposition.REMOTE,
        next_hop=ip4("192.168.16.2"), node_id=2,
    )
    dp.swap()
    pkts = build_traffic(args.packets, uplink, seed=9)
    pkts = pkts._replace(
        dst_ip=(ip4("10.2.0.0") + np.random.default_rng(4).integers(
            2, 1 << 15, args.packets)).astype(np.uint32)
    )
    vtep = jnp.uint32(ip4("192.168.16.1"))
    encap = jax.jit(vxlan_encap)

    # Two jits, like the deployment shape (Dataplane.process +
    # encap_remote). Note: fusing encap INTO the step jit measured ~140x
    # slower on v5e (XLA scheduling pathology) — keep them separate.
    tables = dp.tables
    n = int(pkts.src_ip.shape[0])
    for i in range(args.warmup):
        res = step(tables, pkts, jnp.int32(1 + i))
        outer = encap(res.pkts, res.disp == int(Disposition.REMOTE),
                      vtep, res.next_hop)
        tables = res.tables
    jax.block_until_ready(outer)
    t0 = time.perf_counter()
    for i in range(args.iters):
        res = step(tables, pkts, jnp.int32(100 + i))
        outer = encap(res.pkts, res.disp == int(Disposition.REMOTE),
                      vtep, res.next_hop)
        tables = res.tables
    jax.block_until_ready(outer)
    mpps = n * args.iters / (time.perf_counter() - t0) / 1e6
    out["vxlan_overlay_encap_mpps"] = round(mpps, 1)

    # (the IO front-end wire sections — io_ring_bench / io_daemon_bench
    # — run in the PRIORITY capture phase of _run() now, before the
    # headline compile: VERDICT r5 Next #1)
    return out


def session_election_bench(args, batch: int = 2048, iters: int = 30) -> dict:
    """Time hashmap_insert under BOTH election strategies (claim
    scatter-min vs stable-sort — ops/session.py module doc) at the
    headline table size, on whatever backend this bench runs on.
    One random batch is built once and EVERY timed call inserts it
    into the same pristine table snapshot (``t`` is never threaded
    forward), so each iteration pays full insert pressure — threading
    the result tables back in would turn iterations 2+ into pure
    refresh hits and invalidate the numbers. Three repetitions with
    ALTERNATING mode order, median reported: a fixed order biased the
    r4-era numbers by warmup/cache state (fixed-order showed claim
    966 vs sort 893 where order-alternated medians showed 509 vs 442
    on the same host) — the whole point of this key is to flip the
    sort default with evidence if a backend disagrees, so the
    methodology must not bias it."""
    import os as _os

    import jax as _jax
    import jax.numpy as jnp

    from vpp_tpu.ops.session import session_insert
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import make_packet_vector
    from vpp_tpu.ops import session as _sess

    slots = 1 << 15  # the headline pipeline's session table size
    dp = Dataplane(DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=32, max_ifaces=8,
        fib_slots=32, sess_slots=slots, nat_mappings=4, nat_backends=4,
    ))
    dp.add_uplink()
    dp.swap()
    pv = make_packet_vector([{"src": "10.0.0.1", "dst": "10.1.1.3",
                              "proto": 6, "sport": 1024, "dport": 80,
                              "rx_if": 1}], n=batch)
    rng = np.random.default_rng(0)
    pv = pv._replace(
        src_ip=jnp.asarray(rng.integers(1, 1 << 30, batch).astype(np.uint32)),
        sport=jnp.asarray(rng.integers(1024, 65000, batch).astype(np.int32)),
        flags=jnp.ones(batch, np.int32))
    want = jnp.ones(batch, bool)

    out = {"sess_election_selected": _sess.election_mode(slots),
           "sess_election_slots": slots}
    saved = _os.environ.get("VPPT_SESS_ELECTION")
    try:
        fns = {}
        for mode in ("claim", "sort"):
            _os.environ["VPPT_SESS_ELECTION"] = mode
            fns[mode] = _jax.jit(session_insert)  # fresh jit per
            # mode: the strategy is baked in at trace time
            _jax.block_until_ready(fns[mode](dp.tables, pv, want,
                                             jnp.int32(1)))
        acc = {"claim": [], "sort": []}
        for rep in range(3):
            order = (("claim", "sort") if rep % 2 == 0
                     else ("sort", "claim"))
            for mode in order:
                t = dp.tables
                t0 = time.perf_counter()
                for i in range(iters):
                    t2, ins, fail, _ev_exp, _ev_vic = fns[mode](
                        t, pv, want, jnp.int32(2 + i))
                _jax.block_until_ready(t2)
                acc[mode].append(
                    (time.perf_counter() - t0) / iters / batch * 1e9)
        for mode, vals in acc.items():
            out[f"sess_election_{mode}_ns_pkt"] = round(
                float(np.median(vals)), 1)
    finally:
        if saved is None:
            _os.environ.pop("VPPT_SESS_ELECTION", None)
        else:
            _os.environ["VPPT_SESS_ELECTION"] = saved
    return out


def pallas_kernel_bench(args, batch: int = 2048, iters: int = 20) -> dict:
    """Pallas kernel shoot-out (ISSUE 16): time the fused rungs of the
    three gather-bound hot ops against their jnp reference rungs on
    this backend, and record whether the pair is bit-exact. On a TPU
    the kernels compile natively (the perf claim); elsewhere they run
    in INTERPRET mode at a reduced batch — an emulator priced per
    lowered op, so those ns/pkt rows validate semantics cost, not
    speed (``pallas_interpret`` = 1 marks the regime). Keys:
    pallas_{bv,lpm,sess}_ns_pkt + *_ref_ns_pkt + *_bitexact."""
    import functools as _ft

    import jax as _jax
    import jax.numpy as jnp

    from vpp_tpu.ops._pallas import pallas_available, use_pallas
    from vpp_tpu.ops.acl_bv import bv_first_match, bv_first_match_fused
    from vpp_tpu.ops.lpm import _fib_lookup_lpm_pallas, fib_lookup_lpm
    from vpp_tpu.ops.session import _probe_ways_reference, sess_probe_ways
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import Disposition

    on_tpu = use_pallas()
    interpret = not on_tpu
    if interpret:
        batch, iters = 256, 3
    out = {"pallas_backend": _jax.default_backend(),
           "pallas_available": int(pallas_available()),
           "pallas_interpret": int(interpret)}
    if not pallas_available():
        return out

    dp = Dataplane(DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=64, max_ifaces=8,
        fib_slots=256, sess_slots=1 << 12, nat_mappings=4,
        nat_backends=4, classifier="bv", fib_impl="lpm"))
    uplink = dp.add_uplink()
    rules = build_rules(48)
    dp.builder.set_global_table(rules)
    rng = np.random.default_rng(5)
    for i in range(60):
        plen = int(rng.choice([8, 16, 24, 24, 32]))
        net = int(rng.integers(0, 1 << 32)) & (0xFFFFFFFF << (32 - plen))
        dp.builder.add_route(
            f"{net >> 24 & 255}.{net >> 16 & 255}."
            f"{net >> 8 & 255}.{net & 255}/{plen}",
            1, Disposition.LOCAL)
    dp.swap()
    tables = dp.tables
    pkts = build_traffic(batch, uplink, seed=21)

    def ns_pkt(fn, *a):
        r = fn(*a)
        _jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*a)
        _jax.block_until_ready(r)
        return round((time.perf_counter() - t0) / iters / batch * 1e9,
                     1), r

    bv_args = (
        tables.glb_bv_bnd_src, tables.glb_bv_bnd_dst,
        tables.glb_bv_bnd_sport, tables.glb_bv_bnd_dport,
        tables.glb_bv_nbnd, tables.glb_bv_src, tables.glb_bv_dst,
        tables.glb_bv_sport, tables.glb_bv_dport, tables.glb_bv_proto,
        pkts)
    out["pallas_bv_ns_pkt"], got = ns_pkt(
        _jax.jit(_ft.partial(bv_first_match_fused, interpret=interpret)),
        *bv_args)
    out["pallas_bv_ref_ns_pkt"], ref = ns_pkt(_jax.jit(bv_first_match),
                                              *bv_args)
    out["pallas_bv_bitexact"] = int(
        bool(jnp.all(got[0] == ref[0]) & jnp.all(got[1] == ref[1])))

    out["pallas_lpm_ns_pkt"], got = ns_pkt(
        _jax.jit(_ft.partial(_fib_lookup_lpm_pallas, interpret=interpret)),
        tables, pkts)
    out["pallas_lpm_ref_ns_pkt"], ref = ns_pkt(_jax.jit(fib_lookup_lpm),
                                               tables, pkts)
    out["pallas_lpm_bitexact"] = int(all(
        bool(jnp.all(g == r)) for g, r in zip(got, ref)))

    nb, ways = tables.sess_valid.shape
    b = jnp.asarray(rng.integers(0, nb, batch).astype(np.int32))
    keys = [jnp.asarray(rng.integers(0, 1 << 32, batch, dtype=np.uint64)
                        .astype(np.uint32)) for _ in range(4)]
    sess_args = (b, *keys, tables.sess_valid, tables.sess_src,
                 tables.sess_dst, tables.sess_ports, tables.sess_proto,
                 tables.sess_time, jnp.int32(0), jnp.int32(1 << 30))
    out["pallas_sess_ns_pkt"], got = ns_pkt(
        _ft.partial(sess_probe_ways, interpret=interpret), *sess_args)
    out["pallas_sess_ref_ns_pkt"], ref = ns_pkt(
        _jax.jit(_probe_ways_reference), *sess_args)
    out["pallas_sess_bitexact"] = int(
        bool(jnp.all(got[0] == ref[0]) & jnp.all(got[1] == ref[1])))
    out["pallas_sess_ways"] = int(ways)
    return out


def _mem_available_bytes() -> int:
    """Best-effort MemAvailable (0 when unreadable) — gates the
    10M-session scale config so a small CI box downshifts instead of
    getting OOM-killed mid-run."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def session_scale_bench(args, batch: int = 2048, iters: int = 24) -> dict:
    """Set-associative session-table capture (ISSUE 6), two parts.

    **Old-vs-new** at the headline table size (1<<15 slots): the W-way
    single-election insert (ops/session.hashmap_insert) against the
    retained linear-probe baseline (hashmap_insert_linear — the
    pre-rework algorithm, verbatim). Methodology (docs/SESSIONS.md):

      * **kernel-level, donated, scan-chained** — both inserts run
        directly over the six session COLUMNS with donated buffers,
        and all `calls` chained inserts execute inside ONE jitted
        lax.scan program, exactly how the fused pipeline step runs
        them in production (in-place updates, no per-call table copy,
        no per-call dispatch). Whole-DataplaneTables dispatch was
        measured at ~325 ns/pkt of pure pytree/donation overhead and
        the per-call jit dispatch at ~700 us/call on this harness —
        additive constants on BOTH sides that compressed the real
        algorithmic ratio.
      * **fresh distinct flows per call** (pre-built outside the
        clock, stacked [calls, batch] for the scan) keep every chained
        insert at full pressure without the refresh-hit pollution that
        forward-threading one batch would cause; 8 calls x batch into
        1<<15 slots tops out at 50% load, well under the eviction
        regime.
      * **per-mode MINIMUM over interleaved windows** — the unloaded-
        cost estimator. This box runs concurrent load with multi-x
        wall-clock swings; medians of long runs inherit whatever
        landed on top of them, while tightly alternated small windows
        give every mode the same shot at the quiet slices.

    Keys: ``sess_insert_ns_pkt`` / ``sess_insert_linear_ns_pkt`` /
    ``sess_insert_speedup_x`` (acceptance: >= 3x).

    **Scale**: a 10M+-resident config (``sess_slots`` 1<<24, override
    with VPPT_SESS_SCALE_SLOTS; downshifts automatically when
    MemAvailable can't hold ~3x the table) is prefilled on-device to
    ~62% live occupancy, then fresh-flow batches are admitted through
    a tables-donating jit (in-place threading — the production-step
    donation story lives in docs/SESSIONS.md). Keys:
    ``sessions_resident_millions`` (live entries after admission) and
    ``session_admission_ksps`` (inserted flows/sec at that residency),
    plus ``sess_scale_insert_ns_pkt``. The new insert's cost is
    O(batch), table-size independent — which is the whole point of the
    sort-rank election — so the scale rows measure memory pressure,
    not an algorithmic cliff.
    """
    import os as _os

    import jax as _jax
    import jax.numpy as jnp

    from vpp_tpu.ops.session import session_insert
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import make_packet_vector

    out = {}

    def flow_batch(rng, n):
        pv = make_packet_vector([{"src": "10.0.0.1", "dst": "10.1.1.3",
                                  "proto": 6, "sport": 1024, "dport": 80,
                                  "rx_if": 1}], n=n)
        return pv._replace(
            src_ip=jnp.asarray(
                rng.integers(1, 1 << 30, n).astype(np.uint32)),
            sport=jnp.asarray(
                rng.integers(1024, 65000, n).astype(np.int32)),
            flags=jnp.ones(n, np.int32))

    # --- part 1: old-vs-new at the headline table size ---
    from vpp_tpu.ops.session import (
        _hash, _pack_ports, hashmap_insert, hashmap_insert_linear)

    slots = 1 << 15
    ways = 4
    nb = slots // ways
    calls = 8          # flows offered per window: 8 x batch = 50% load
    windows = 10
    out["sess_insert_slots"] = slots
    out["sess_insert_ways"] = ways

    rng = np.random.default_rng(1)
    # distinct flows per call, stacked [calls, batch], built OUTSIDE
    # the clock — the scan below consumes one row per chained insert
    kvs = (
        jnp.asarray(np.stack(
            [(1 + i * batch + np.arange(batch)).astype(np.uint32)
             for i in range(calls)])),
        jnp.full((calls, batch), 0x0A010103, jnp.uint32),
        _pack_ports(
            jnp.asarray(rng.integers(
                1024, 65000, (calls, batch)).astype(np.int32)),
            jnp.full((calls, batch), 80, jnp.int32)),
        jnp.full((calls, batch), 6, jnp.int32),
    )
    nows = jnp.arange(2, 2 + calls, dtype=jnp.int32)
    want = jnp.ones(batch, bool)
    max_age = jnp.int32(3000)

    # both modes run their `calls` chained inserts inside ONE jitted
    # lax.scan program: production runs the insert inside the fused
    # step, so per-dispatch overhead (~700 us/call measured on this
    # harness) is not kernel cost — paying it per call was an additive
    # constant on BOTH sides that compressed the algorithmic ratio
    def assoc_prog(valid, tme, k0, k1, k2, k3, kvs, nows):
        def body(carry, x):
            valid, tme, ks = carry
            kv, now = tuple(x[:4]), x[4]
            h = _hash(*kv, nb)
            r = hashmap_insert(valid, tme, ks, kv, (), (), h, want,
                               now, max_age=max_age)
            return (r[0], r[1], r[2]), 0
        (valid, tme, ks), _ = _jax.lax.scan(
            body, (valid, tme, (k0, k1, k2, k3)), (*kvs, nows))
        return valid, tme, ks

    def linear_prog(valid, tme, k0, k1, k2, k3, kvs, nows):
        def body(carry, x):
            valid, tme, ks = carry
            kv, now = tuple(x[:4]), x[4]
            h = _hash(*kv, slots)
            r = hashmap_insert_linear(valid, tme, ks, kv, h, want,
                                      now, max_age=max_age)
            return (r[0], r[1], r[2]), 0
        (valid, tme, ks), _ = _jax.lax.scan(
            body, (valid, tme, (k0, k1, k2, k3)), (*kvs, nows))
        return valid, tme, ks

    fns = {
        "assoc": (_jax.jit(assoc_prog, donate_argnums=(0, 1, 2, 3, 4, 5)),
                  (nb, ways)),
        "linear": (_jax.jit(linear_prog, donate_argnums=(0, 1, 2, 3, 4, 5)),
                   (slots,)),
    }

    def pristine(shape):
        cols = [jnp.zeros(shape, jnp.int32), jnp.zeros(shape, jnp.int32),
                jnp.zeros(shape, jnp.uint32), jnp.zeros(shape, jnp.uint32),
                jnp.zeros(shape, jnp.uint32), jnp.zeros(shape, jnp.int32)]
        _jax.block_until_ready(cols)
        return cols

    for fn, shape in fns.values():  # compile + warm outside the clock
        _jax.block_until_ready(
            _jax.tree.leaves(fn(*pristine(shape), kvs, nows)))
    mins = {"assoc": float("inf"), "linear": float("inf")}
    for rep in range(windows):
        order = (("assoc", "linear") if rep % 2 == 0
                 else ("linear", "assoc"))
        for mode in order:
            fn, shape = fns[mode]
            cols = pristine(shape)
            t0 = time.perf_counter()
            res = fn(*cols, kvs, nows)
            _jax.block_until_ready((res[0], res[1]))
            mins[mode] = min(
                mins[mode],
                (time.perf_counter() - t0) / calls / batch * 1e9)
    new_ns = mins["assoc"]
    old_ns = mins["linear"]
    out["sess_insert_ns_pkt"] = round(new_ns, 1)
    out["sess_insert_linear_ns_pkt"] = round(old_ns, 1)
    out["sess_insert_speedup_x"] = round(old_ns / max(new_ns, 1e-9), 2)

    # --- part 2: 10M-resident scale config ---
    scale_slots = int(_os.environ.get("VPPT_SESS_SCALE_SLOTS", 1 << 24))
    # ~24 B/slot across the 6 session columns; require ~3x headroom
    # (donation transients + the numpy-free device fill)
    need = scale_slots * 24 * 3
    avail = _mem_available_bytes()
    while avail and need > avail and scale_slots > (1 << 18):
        scale_slots >>= 1
        need = scale_slots * 24 * 3
    ways = 4
    cfg = DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=32, max_ifaces=8,
        fib_slots=32, sess_slots=scale_slots, sess_ways=ways,
        natsess_slots=1 << 12, nat_mappings=4, nat_backends=4,
    )
    dp2 = Dataplane(cfg)
    dp2.add_uplink()
    dp2.swap()
    n_buckets = scale_slots // ways
    target = min(int(scale_slots * 0.625), scale_slots)
    full_ways = target // n_buckets            # whole ways filled
    part = target - full_ways * n_buckets      # buckets with one more
    t = dp2.tables
    valid = t.sess_valid
    if full_ways:
        valid = valid.at[:, :full_ways].set(1)
    if part:
        valid = valid.at[:part, full_ways].set(1)
    # unique synthetic keys (bucket id / way) — residency + admission
    # probe the live/free way machinery, not key recall
    bid = jnp.arange(n_buckets, dtype=jnp.uint32)[:, None]
    t = t._replace(
        sess_valid=valid,
        sess_time=jnp.where(valid == 1, jnp.int32(1), 0),
        sess_src=jnp.broadcast_to(bid, valid.shape),
        sess_dst=jnp.broadcast_to(
            jnp.arange(ways, dtype=jnp.uint32)[None, :], valid.shape),
    )
    insert = _jax.jit(
        lambda tt, p, w, n: session_insert(tt, p, w, n),
        donate_argnums=(0,))
    rng2 = np.random.default_rng(9)
    # fresh-flow batches built OUTSIDE the clock (host-side numpy +
    # packet-vector assembly would otherwise dominate the timed loop)
    pvs = [flow_batch(rng2, batch) for _ in range(iters + 1)]
    _jax.block_until_ready([pv.src_ip for pv in pvs])
    t, ins, _f, _e, _v = insert(t, pvs[0], want, jnp.int32(2))  # compile
    _jax.block_until_ready(t.sess_valid)
    inserted = int(np.asarray(ins).sum())
    ins_acc = jnp.int32(0)      # accumulate on-device; one sync at the end
    t0 = time.perf_counter()
    for i in range(iters):
        t, ins, _f, _e, _v = insert(t, pvs[1 + i], want, jnp.int32(3 + i))
        ins_acc = ins_acc + jnp.sum(ins, dtype=jnp.int32)
    _jax.block_until_ready((t.sess_valid, ins_acc))
    dt = time.perf_counter() - t0
    inserted += int(np.asarray(ins_acc).item())
    resident = int(np.asarray(jnp.sum(t.sess_valid)).item())
    out["sess_scale_slots"] = scale_slots
    out["sess_scale_ways"] = ways
    out["sessions_resident_millions"] = round(resident / 1e6, 3)
    out["session_admission_ksps"] = round(iters * batch / dt / 1e3, 1)
    out["sess_scale_insert_ns_pkt"] = round(
        dt / iters / batch * 1e9, 1)
    out["sess_scale_insert_failed"] = iters * batch + batch - inserted
    return out


def snapshot_bench(args, batch: int = 2048, iters: int = 24) -> dict:
    """Crash-consistent snapshot capture (ISSUE 8) at the scale config.

    Prefills the 1<<24-slot table (VPPT_SESS_SCALE_SLOTS override;
    memory/disk-guarded downshift like session_scale_bench) to ~62%
    live, then measures:

      * ``snapshot_drain_s`` / ``snapshot_chunks`` / ``snapshot_mb`` /
        ``snapshot_chunk_ms`` — the FULL first-generation drain in
        bounded chunks (the ~400 MB sess column set must never ship
        as one transfer — chunk_ms is the bound that proves it);
      * ``snapshot_incremental_s`` — the clean second generation
        (content digests: nothing re-ships);
      * ``snapshot_step_stall_pct`` — the headline number: median
        fused-step time while a full drain runs concurrently vs
        unloaded, as a percentage increase. Acceptance: < 10% — the
        snapshot must never stall the hot path.
    """
    import shutil as _shutil
    import tempfile as _tempfile
    import threading as _threading

    import jax as _jax
    import jax.numpy as jnp

    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.snapshot import SessionSnapshotter
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import make_packet_vector

    out = {}
    scale_slots = int(os.environ.get("VPPT_SESS_SCALE_SLOTS", 1 << 24))
    # ~24 B/slot on device + the host chunk staging + the on-disk
    # snapshot copy: require ~4x headroom, and the snapshot dir must
    # hold ~1.5x the column bytes
    need = scale_slots * 24 * 4
    avail = _mem_available_bytes()
    while avail and need > avail and scale_slots > (1 << 18):
        scale_slots >>= 1
        need = scale_slots * 24 * 4
    td = _tempfile.mkdtemp(prefix="snapbench_")
    free_disk = _shutil.disk_usage(td).free
    while scale_slots * 24 * 1.5 > free_disk and scale_slots > (1 << 18):
        scale_slots >>= 1
    ways = 4
    cfg = DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=32, max_ifaces=8,
        fib_slots=32, sess_slots=scale_slots, sess_ways=ways,
        natsess_slots=1 << 12, nat_mappings=4, nat_backends=4,
    )
    dp = Dataplane(cfg)
    from vpp_tpu.pipeline.vector import Disposition

    up = dp.add_uplink()
    dp.builder.add_route("10.1.0.0/16", up, Disposition.LOCAL)
    dp.swap()
    n_buckets = scale_slots // ways
    target = int(scale_slots * 0.625)
    full_ways = target // n_buckets
    part = target - full_ways * n_buckets
    t = dp.tables
    valid = t.sess_valid
    if full_ways:
        valid = valid.at[:, :full_ways].set(1)
    if part:
        valid = valid.at[:part, full_ways].set(1)
    bid = jnp.arange(n_buckets, dtype=jnp.uint32)[:, None]
    dp.tables = t._replace(
        sess_valid=valid,
        sess_time=jnp.where(valid == 1, jnp.int32(1), 0),
        sess_src=jnp.broadcast_to(bid, valid.shape),
        sess_dst=jnp.broadcast_to(
            jnp.arange(ways, dtype=jnp.uint32)[None, :], valid.shape),
    )
    dp._now = 2
    out["snapshot_slots"] = scale_slots

    # fresh-flow step batches (prebuilt outside the clock) for the
    # stall probe: the production-shaped hot path next to the drain
    rng = np.random.default_rng(11)

    def flow_batch(n):
        pv = make_packet_vector(
            [{"src": "10.0.0.1", "dst": "10.1.1.3", "proto": 6,
              "sport": 1024, "dport": 80, "rx_if": up}], n=n)
        import jax.numpy as _jnp

        return pv._replace(
            src_ip=_jnp.asarray(
                rng.integers(1, 1 << 30, n).astype(np.uint32)),
            sport=_jnp.asarray(
                rng.integers(1024, 65000, n).astype(np.int32)),
            flags=_jnp.ones(n, np.int32))

    pvs = [flow_batch(batch) for _ in range(iters * 4 + 2)]
    _jax.block_until_ready([pv.src_ip for pv in pvs])
    dp.process(pvs[0], now=3)  # compile + warm
    pv_i = 1

    def step_samples(k, now0):
        nonlocal pv_i
        samples = []
        for i in range(k):
            t0 = time.perf_counter()
            res = dp.process(pvs[pv_i], now=now0 + i)
            _jax.block_until_ready(res.tables.sess_valid)
            samples.append(time.perf_counter() - t0)
            pv_i += 1
        return samples

    try:
        base = step_samples(iters, 10)
        base_ms = float(np.median(base) * 1e3)

        # pace_s: breathe between chunk drains so the drain never
        # monopolizes the transport/host — the agent default a
        # latency-sensitive deployment would run with
        snap = SessionSnapshotter(dp, td, chunk_buckets=4096,
                                  pace_s=0.005)
        # concurrent: the FULL first-generation drain against live
        # steps — the stall number the acceptance bar cares about
        overlap: list = []
        th = _threading.Thread(target=snap.snapshot, daemon=True)
        t0 = time.perf_counter()
        th.start()
        while th.is_alive():
            overlap.extend(step_samples(2, 1000 + pv_i))
            if pv_i >= len(pvs) - 1:
                pv_i = 1  # reuse batches; refresh-vs-insert mix is
                # stable enough for a median
        th.join()
        drain_s = time.perf_counter() - t0
        s = snap.stats_snapshot()
        if s["snapshot_failures"]:
            raise RuntimeError(f"snapshot failed: {s['last_error']}")
        over_ms = float(np.median(overlap) * 1e3) if overlap else base_ms
        out["snapshot_drain_s"] = round(drain_s, 2)
        out["snapshot_chunks"] = s["chunks_written"]
        out["snapshot_mb"] = round(s["bytes_written"] / 1e6, 1)
        out["snapshot_chunk_ms"] = round(
            s["chunk_seconds"] / max(1, s["chunks_written"]) * 1e3, 2)
        out["snapshot_step_ms_unloaded"] = round(base_ms, 3)
        out["snapshot_step_ms_draining"] = round(over_ms, 3)
        out["snapshot_step_stall_pct"] = round(
            max(0.0, (over_ms - base_ms) / base_ms * 100.0), 1)
        # clean incremental generation: digests unchanged except the
        # buckets the stall probe dirtied
        t1 = time.perf_counter()
        snap.snapshot()
        out["snapshot_incremental_s"] = round(
            time.perf_counter() - t1, 2)
        s2 = snap.stats_snapshot()
        out["snapshot_incremental_chunks"] = (
            s2["chunks_written"] - s["chunks_written"])
    finally:
        _shutil.rmtree(td, ignore_errors=True)
    return out


def wire_udp(i: int, dport: int = 80, src: str = "10.1.1.2") -> bytes:
    """One test UDP frame ``src`` → 10.1.1.3 (shared by the ring bench
    and the daemon-bench sender subprocess; ``dport`` lets the
    latency-SLO ladder tag priority-lane traffic, ``src`` lets the
    tenant-isolation scenario derive per-tenant flows)."""
    import ipaddress
    import struct

    src = ipaddress.ip_address(src).packed
    dst = ipaddress.ip_address("10.1.1.3").packed
    eth = b"\x02\x00\x00\x00\x00\x02\x02\x00\x00\x00\x00\x01\x08\x00"
    l4 = struct.pack("!HHHH", 40000 + (i % 1024), dport, 16, 0) + b"y" * 8
    hdr = struct.pack("!BBHHHBBH4s4s", 0x45, 0, 20 + len(l4), i & 0xFFFF,
                      0x4000, 64, 17, 0, src, dst)
    return eth + hdr + l4


def io_ring_bench(args, frame_pkts: int = 256,
                  sat_s: float = 5.0, paced_s: float = 5.0) -> dict:
    import collections
    import threading

    import jax as _jax

    from vpp_tpu.io.pump import DataplanePump
    from vpp_tpu.io.rings import IORingPair
    from vpp_tpu.native.pktio import PacketCodec
    from vpp_tpu.pipeline.vector import VEC

    dp = build_fwd_dataplane()
    client_if = dp.pod_if[("default", "p0")]

    frames = [wire_udp(i) for i in range(frame_pkts)]
    # deep ring + large coalesce + parallel fetchers
    max_batch, workers = 16384, 8
    rings = IORingPair(n_slots=512, snap=512)
    codec = PacketCodec(snap=rings.rx.snap)
    scratch = np.zeros((VEC, rings.rx.snap), np.uint8)


    # transport bandwidth floor: the packed boundary is 20 B/packet
    # each way, so host↔device bandwidth IS the wire-path ceiling on a
    # transfer-limited transport (report the floor so a low Mpps number
    # is attributable). Median of 3 runs of a 2 MB block each way.
    probe = np.zeros((128, 4096), np.int32)  # 2 MiB
    ups, downs = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        dev = _jax.block_until_ready(_jax.device_put(probe))
        ups.append(probe.nbytes / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        _jax.device_get(dev)
        downs.append(probe.nbytes / (time.perf_counter() - t0))
        del dev
    up_mbps = float(np.median(ups)) / 1e6
    down_mbps = float(np.median(downs)) / 1e6
    bytes_per_pkt = 20.0
    ceiling_mpps = min(up_mbps, down_mbps) / bytes_per_pkt

    pump = DataplanePump(dp, rings, max_batch=max_batch,
                         workers=workers)
    pump.warm()  # compile every dispatch bucket rung before measuring
    pump.start()

    def warm_barrier() -> None:
        # push one frame through the full ring→device→ring path and
        # wait for it to drain, so the measured phases never pay
        # time-to-first-drain (dispatch ramp + first fetch RTT) out of
        # their window
        warm_cols, warm_n = codec.parse(frames, client_if, scratch)
        warm_cols["meta"][:warm_n] = -1
        if rings.rx.push(warm_cols, warm_n, payload=scratch):
            warm_deadline = time.perf_counter() + 120
            while time.perf_counter() < warm_deadline:
                g = rings.tx.peek()
                if g is not None:
                    rings.tx.release()
                    break
                time.sleep(0.005)

    warm_barrier()
    seq_counter = [0]

    def run_phase(duration: float, pace_fps: float = 0.0) -> dict:
        # frames are sequence-stamped through the ring's meta column so
        # latency pairing survives drops (tx-ring-full discards a frame
        # without a tx counterpart; positional pairing would then skew
        # every later sample)
        push_times: "collections.deque" = collections.deque()
        stop = threading.Event()
        stats = {"pushed": 0, "drained": 0, "dropped": 0, "lat": []}

        def producer():
            period = 1.0 / pace_fps if pace_fps else 0.0
            next_t = time.perf_counter()
            while not stop.is_set():
                if period:
                    now = time.perf_counter()
                    if now < next_t:
                        time.sleep(min(period / 8, next_t - now))
                        continue
                    next_t += period
                cols, n = codec.parse(frames, client_if, scratch)
                seq = seq_counter[0]
                cols["meta"][:n] = seq
                # enqueue BEFORE push: the drain thread may see the tx
                # frame before a post-push append would land
                push_times.append((seq, time.perf_counter()))
                if rings.rx.push(cols, n, payload=scratch):
                    seq_counter[0] += 1
                    stats["pushed"] += 1
                else:
                    push_times.pop()
                    time.sleep(0.0002)

        def drain_one(record: bool) -> bool:
            g = rings.tx.peek()
            if g is None:
                return False
            seq = int(g.cols["meta"][0])
            if record:
                codec.rewrite(g.cols, g.payload, g.n)
            rings.tx.release()
            now = time.perf_counter()
            while push_times and push_times[0][0] < seq:
                push_times.popleft()           # frame dropped in-pump
                stats["dropped"] += 1
            if push_times and push_times[0][0] == seq:
                _, t_push = push_times.popleft()
                if record:
                    stats["lat"].append(now - t_push)
            stats["drained"] += 1
            return True

        prod = threading.Thread(target=producer, daemon=True)
        t0 = time.perf_counter()
        prod.start()
        deadline = t0 + duration
        while time.perf_counter() < deadline:
            if not drain_one(record=True):
                time.sleep(0.0002)
        stop.set()
        prod.join()
        stats["elapsed"] = time.perf_counter() - t0
        # flush everything still in flight so the next phase starts
        # clean; a second of continuous silence means the pump is idle
        # (trailing entries whose frames were dropped never drain)
        flush_deadline = time.perf_counter() + 10
        idle_since = None
        while push_times and time.perf_counter() < flush_deadline:
            if drain_one(record=False):
                idle_since = None
                continue
            now = time.perf_counter()
            if idle_since is None:
                idle_since = now
            elif now - idle_since > 1.0:
                break
            time.sleep(0.002)
        push_times.clear()
        return stats

    try:
        try:
            sat = run_phase(sat_s)
            fps = sat["drained"] / sat["elapsed"]
            mpps = fps * frame_pkts / 1e6
            # paced phase at ~50% of saturation: queueing-free
            # experienced latency (what a packet actually waits,
            # ring to ring)
            paced = run_phase(paced_s, pace_fps=max(fps * 0.5, 1.0))
            lat_us = (np.asarray(paced["lat"][5:]) * 1e6
                      if len(paced["lat"]) > 5 else np.asarray([0.0]))
            out = {
                "io_ring_wire_mpps": round(mpps, 4),
                "io_wire_frame_pkts": frame_pkts,
                "io_wire_max_coalesce": pump.stats["max_coalesce"],
                "io_wire_lat_p50_us": round(
                    float(np.percentile(lat_us, 50)), 1),
                "io_wire_lat_p99_us": round(
                    float(np.percentile(lat_us, 99)), 1),
                "io_wire_paced_mpps": round(
                    paced["drained"] * frame_pkts / paced["elapsed"] / 1e6,
                    4),
                "xfer_up_MBps": round(up_mbps, 2),
                "xfer_down_MBps": round(down_mbps, 2),
                "io_wire_bytes_per_pkt": bytes_per_pkt,
                "io_wire_xfer_ceiling_mpps": round(ceiling_mpps, 3),
            }
        finally:
            pump.stop()

        # Overlap-ladder phase (r6 tentpole): the SAME path with the
        # adaptive chainer armed — backlog past one max_batch bucket
        # folds into one process_packed_chain K-stack, so a fetch
        # round trip is paid once per K buckets. Reported next to the
        # unchained row so the ladder's win (or its CPU-harness
        # neutrality) is a measured fact, not an inference. jit cache
        # note: the bucket rungs are already compiled on this
        # dataplane; only the chain rungs compile here.
        try:
            opump = DataplanePump(dp, rings, max_batch=max_batch,
                                  workers=workers, chain_k=8)
            try:
                opump.warm()
                opump.start()
                warm_barrier()
                osat = run_phase(sat_s)
                out.update({
                    "io_wire_overlap_mpps": round(
                        osat["drained"] / osat["elapsed"]
                        * frame_pkts / 1e6, 4),
                    "io_wire_chain_batches":
                        opump.stats["chain_batches"],
                    "io_wire_chain_k_peak":
                        opump.stats["chain_k_peak"],
                    "io_wire_inflight_peak":
                        opump.stats["inflight_peak"],
                    "io_wire_fetch_workers": opump.workers,
                })
            finally:
                opump.stop()
        except Exception as exc:  # noqa: BLE001 — additive phase
            out["io_wire_overlap_error"] = f"{type(exc).__name__}: {exc}"

        # Persistent resident-loop mode (docs/LATENCY.md lever #2,
        # VERDICT r4 Next #2): the SAME ring-to-ring path served by
        # mode="persistent" — one resident device program fed through
        # ordered io_callbacks instead of per-batch dispatches. Its
        # regime is the latency floor, so the paced-latency rows are
        # the headline; the sat row shows what that trade costs in
        # throughput. Failures here must not void the dispatch-mode
        # numbers above.
        try:
            # a telemetry-enabled twin of the forwarding dataplane:
            # the persistent round then histograms per-packet wire
            # latency ON DEVICE while the harness measures the same
            # frames host-side — the two tails are tied below (ISSUE
            # 13 satellite) so governor acceptance can trust one
            # source. A separate dp keeps the dispatch-mode rows
            # above byte-comparable with earlier rounds.
            dp_tel = build_fwd_dataplane(telemetry="latency")
            ppump = DataplanePump(dp_tel, rings, mode="persistent")
            try:
                ppump.warm()
                ppump.start()
                warm_barrier()
                psat = run_phase(min(sat_s, 4.0))
                pfps = psat["drained"] / psat["elapsed"]
                tel_before = ppump.tel_snapshot()
                bins0 = (np.asarray(tel_before["bins"], np.int64)
                         if tel_before is not None else None)
                ppaced = run_phase(min(paced_s, 4.0),
                                   pace_fps=max(pfps * 0.5, 1.0))
                plat_us = (np.asarray(ppaced["lat"][5:]) * 1e6
                           if len(ppaced["lat"]) > 5
                           else np.asarray([0.0]))
                pmpps = pfps * frame_pkts / 1e6
                # the io_callback-free claim as MEASURED keys (ISSUE
                # 7): windows exchanged vs host callbacks the device
                # program made (the ring steady state makes none —
                # this key regressing above 0 means the two-blocking-
                # callbacks-per-frame design came back), and the
                # persistent path as a fraction of the SAME capture's
                # transfer ceiling (acceptance: ratio >= 0.5, i.e.
                # within 2x of the ceiling)
                rwin = int(ppump.stats.get("ring_windows", 0))
                out.update({
                    "io_wire_persistent_mpps": round(pmpps, 4),
                    "io_wire_persistent_lat_p50_us": round(
                        float(np.percentile(plat_us, 50)), 1),
                    "io_wire_persistent_lat_p99_us": round(
                        float(np.percentile(plat_us, 99)), 1),
                    "io_wire_ceiling_ratio": round(
                        pmpps / ceiling_mpps, 4) if ceiling_mpps else 0.0,
                    "io_wire_ring_windows": rwin,
                    "io_wire_ring_frames": int(
                        ppump.stats.get("ring_frames", 0)),
                    "io_wire_callbacks_per_window": round(
                        int(ppump.stats.get("io_callbacks", 0))
                        / max(1, rwin), 4),
                })
                # host↔device latency tie (ISSUE 13 satellite): the
                # host-side p99 (ring-to-ring, sequence-stamped) and
                # the device-histogram p99 (pack → device tx-append)
                # from the SAME paced round. The host leg is a strict
                # superset (rx-ring wait + result fetch + tx write +
                # drain), so a ratio far above 2 — or below 1 — means
                # one of the two clocks is lying and neither source
                # should anchor governor acceptance.
                tel_after = ppump.tel_snapshot()
                if tel_after is not None and bins0 is not None:
                    from vpp_tpu.ops.telemetry import quantiles_from_bins

                    dbins = (np.asarray(tel_after["bins"], np.int64)
                             - bins0)
                    if int(dbins.sum()) > 0:
                        _d50, d99, _d999 = quantiles_from_bins(dbins)
                        host_p99 = float(np.percentile(plat_us, 99))
                        ratio = (host_p99 / d99) if d99 > 0 else 0.0
                        out.update({
                            "wire_latency_p99_us_device_wire": round(
                                d99, 1),
                            "wire_latency_host_vs_device_ratio": round(
                                ratio, 3),
                            "wire_latency_host_device_divergent": int(
                                ratio > 2.0 or (0 < ratio < 1.0)),
                        })
            finally:
                ppump.stop()
        except Exception as exc:  # noqa: BLE001 — report, keep section
            out["io_wire_persistent_error"] = (
                f"{type(exc).__name__}: {exc}")
        return out
    finally:
        # unconditional: an exception in the DISPATCH phase must not
        # leak the shared-memory ring pair either
        rings.close()


def hoststack_bench(args, duration_s: float = 2.5) -> dict:
    """RPS/CPS under policy — the reference's wrk perf harness analog
    (tests/policy/perf/RPS.sh, CPS.sh: 50 connections, keep-alive vs
    Connection: close) over the VCL session-filtered host stack.

    A server app namespace answers a minimal request/response protocol
    on loopback; a client namespace drives it with the session-rule
    engine packed to a gen-policy.py-shaped 1000-rule set. Session
    rules filter connection SETUP (VPP session-layer semantics), so RPS
    measures the steady state while CPS pays an admission check per
    wave — client connects ride connect_batch (one engine batch per
    wave), server accepts are admission-checked in waves too. Also
    reports the engine's raw batched admission capacity, the device
    ceiling on CPS."""
    import threading

    from vpp_tpu.hoststack.session_rules import (
        RuleAction,
        RuleScope,
        SessionRule,
        SessionRuleEngine,
    )
    from vpp_tpu.hoststack.vcl import HostStackApp, _ip_int

    LOOP = _ip_int("127.0.0.1")
    engine = SessionRuleEngine(capacity=2048)

    # gen-policy-shaped filler: 1000 CIDR x port rules (5:1 permit:deny)
    filler = []
    for i in range(996):
        net = ((10 << 24) | ((i // 250) << 16) | ((i % 250) << 8))
        filler.append(SessionRule(
            scope=int(RuleScope.LOCAL), appns_index=1, transport_proto=6,
            lcl_net=0, lcl_plen=0, rmt_net=net, rmt_plen=24,
            lcl_port=0, rmt_port=8000 + i % 20,
            action=int(RuleAction.DENY if i % 6 == 5 else RuleAction.ALLOW),
        ))
    engine.apply(add=filler)

    server = HostStackApp(engine, appns_index=2)
    srv = server.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(256)
    port = srv.getsockname()[1]

    # specific admits over default-deny in BOTH scopes, so the connect
    # check (LOCAL) and the accept check (GLOBAL) each decide something
    # real — the engine default-allows unmatched connections, so the
    # deny-alls are what make the allows load-bearing
    engine.apply(add=[
        SessionRule(scope=int(RuleScope.LOCAL), appns_index=1,
                    transport_proto=6, lcl_net=0, lcl_plen=0,
                    rmt_net=LOOP, rmt_plen=32, lcl_port=0, rmt_port=port,
                    action=int(RuleAction.ALLOW)),
        SessionRule(scope=int(RuleScope.LOCAL), appns_index=1,
                    transport_proto=6, lcl_net=0, lcl_plen=0,
                    rmt_net=0, rmt_plen=0, lcl_port=0, rmt_port=0,
                    action=int(RuleAction.DENY)),
        SessionRule(scope=int(RuleScope.GLOBAL), appns_index=-1,
                    transport_proto=6, lcl_net=LOOP, lcl_plen=32,
                    rmt_net=0, rmt_plen=0, lcl_port=port, rmt_port=0,
                    action=int(RuleAction.ALLOW)),
        SessionRule(scope=int(RuleScope.GLOBAL), appns_index=-1,
                    transport_proto=6, lcl_net=0, lcl_plen=0,
                    rmt_net=0, rmt_plen=0, lcl_port=0, rmt_port=0,
                    action=int(RuleAction.DENY)),
    ])

    client = HostStackApp(engine, appns_index=1)

    # warm every engine batch shape the timed windows can hit: check()
    # pads to powers of two and jits per padded shape, and a first
    # compile (20-40 s on TPU) inside a 2.5 s window would make the
    # reported RPS/CPS a compile-time artifact
    for shape in (8, 16, 32, 64):
        engine.check_connect([(1, 6, 0, 0, LOOP, port)] * shape)
        engine.check_accept([(6, LOOP, port, LOOP, 40000)] * shape)

    stop = threading.Event()

    def serve_conn(conn):
        try:
            while True:
                req = conn.recv(64)
                if not req:
                    return
                conn.sendall(b"HTTP/1.1 200 OK\r\n\r\nok")
        except OSError:
            pass
        finally:
            conn.close()

    def acceptor():
        """Wave admission via FilteredSocket.accept_batch: one engine
        batch per wave of pending connections (VPP filters inbound
        sessions in its session tables; waves are the batched form)."""
        while not stop.is_set():
            try:
                wave = srv.accept_batch(max_n=64, first_timeout=0.01)
            except OSError:
                return  # listener closed: shutdown
            for fconn, _peer in wave:
                threading.Thread(target=serve_conn, args=(fconn.sock,),
                                 daemon=True).start()

    acc = threading.Thread(target=acceptor, daemon=True)
    acc.start()
    out = {"hoststack_rules": engine.num_rules}
    try:
        # --- RPS: 50 persistent session-admitted connections ---
        conns = [c for c in client.connect_batch(
            [("127.0.0.1", port)] * 50) if c is not None]
        if len(conns) != 50:
            raise RuntimeError(f"admission failed: {len(conns)}/50")
        for c in conns:
            c.settimeout(10)
        reqs = 0
        deadline = time.perf_counter() + duration_s
        t0 = time.perf_counter()
        while time.perf_counter() < deadline:
            c = conns[reqs % 50]
            c.send(b"GET / HTTP/1.1\r\n\r\n")
            if not c.recv(64):
                raise RuntimeError("server closed mid-RPS")
            reqs += 1
        out["hoststack_rps"] = round(reqs / (time.perf_counter() - t0), 1)
        for c in conns:
            c.close()

        # --- CPS: connect+request+close, 32-wide admission waves ---
        done = 0
        deadline = time.perf_counter() + duration_s
        t0 = time.perf_counter()
        while time.perf_counter() < deadline:
            wave = [c for c in client.connect_batch(
                [("127.0.0.1", port)] * 32) if c is not None]
            for c in wave:
                c.settimeout(10)
                c.send(b"GET / HTTP/1.1\r\nConnection: close\r\n\r\n")
                if c.recv(64):
                    done += 1
                c.close()
        out["hoststack_cps"] = round(done / (time.perf_counter() - t0), 1)

        # --- raw admission capacity: 4096-conn batched checks ---
        rng = np.random.default_rng(5)
        batch = [(1, 6, 0, 0, int(x), 8000 + int(x) % 20)
                 for x in rng.integers(10 << 24, (10 << 24) + (1 << 20),
                                       4096)]
        engine.check_connect(batch)  # compile/warm
        t0 = time.perf_counter()
        iters = 20
        for _ in range(iters):
            engine.check_connect(batch)
        # hoststack policy-engine connect-check rate — renamed from
        # "session_admission_ksps" when the session-table scale bench
        # (session_scale_bench) claimed that key: hoststack_bench runs
        # AFTER the priority sections merge into the final details, so
        # the shared name silently overwrote the table's admission rate
        out["hoststack_admission_ksps"] = round(
            4096 * iters / (time.perf_counter() - t0) / 1e3, 1
        )

        # --- ldpreload iperf analog (BASELINE row: pod<->pod iperf,
        # kernel stack vs VCL/ldpreload,
        # tests/robot/suites/one_node_two_pods_ldpreload_iperf.robot):
        # bulk TCP between two REAL subprocesses, once bare-kernel and
        # once under libvclshim.so admission. Session rules filter
        # connection SETUP only, so the two should track each other —
        # the VCL number proves policy admission costs nothing on the
        # data path.
        try:
            out.update(vcl_iperf_bench(engine))
        except Exception as e:  # noqa: BLE001 — optional, env-dependent
            out["vcl_iperf_error"] = f"{type(e).__name__}: {e}"
        return out
    finally:
        stop.set()
        srv.close()


def proxy_chain_bench(args, duration_s: float = 2.5,
                      n_rules: int = 10240) -> dict:
    """nginx-istio analog (BASELINE config #5, reference
    tests/nginx-istio/nginx-envoy.yaml): HTTP client → proxy → backend
    with the session-policy engine at gen-policy scale (10,240 rules)
    between EVERY hop — four jitted admission verdicts per fresh chain
    (client connect, proxy accept, proxy upstream connect, backend
    accept). RPS = keep-alive steady state through both hops (the
    wrk-shaped number); CPS = full fresh chains per second. The e2e
    form of the same chain (real subprocesses under the LD_PRELOAD
    shim, fail-closed) is tests/test_proxy_chain_e2e.py."""
    import threading

    from vpp_tpu.hoststack.scenarios import (
        gen_policy_filler,
        proxy_chain_rules,
    )
    from vpp_tpu.hoststack.session_rules import SessionRuleEngine
    from vpp_tpu.hoststack.vcl import HostStackApp, _ip_int

    LOOP = _ip_int("127.0.0.1")
    CLIENT_NS, PROXY_NS, BACKEND_NS = 1, 2, 3
    engine = SessionRuleEngine(capacity=16384)
    engine.apply(add=gen_policy_filler(n_rules - 7))

    backend_app = HostStackApp(engine, appns_index=BACKEND_NS)
    bsrv = backend_app.socket()
    bsrv.bind(("127.0.0.1", 0))
    bsrv.listen(256)
    bport = bsrv.getsockname()[1]
    proxy_app = HostStackApp(engine, appns_index=PROXY_NS)
    psrv = proxy_app.socket()
    psrv.bind(("127.0.0.1", 0))
    psrv.listen(256)
    pport = psrv.getsockname()[1]

    # the mesh seam: each namespace may reach exactly its next hop,
    # deny-all underneath — the verdicts are load-bearing at 10k rules
    engine.apply(add=proxy_chain_rules(LOOP, CLIENT_NS, PROXY_NS,
                                       pport, bport))
    client_app = HostStackApp(engine, appns_index=CLIENT_NS)

    # warm the engine's padded batch shapes (jit-per-shape)
    for shape in (8, 16, 32, 64):
        engine.check_connect([(CLIENT_NS, 6, 0, 0, LOOP, pport)] * shape)
        engine.check_accept([(6, LOOP, pport, LOOP, 40000)] * shape)

    BODY = b"x" * 64
    RESP = (b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n\r\n"
            % len(BODY)) + BODY
    RESP_LEN = len(RESP)
    REQ = b"GET / HTTP/1.1\r\nHost: b\r\n\r\n"
    stop = threading.Event()

    def recv_exact(sock, n):
        buf = b""
        while len(buf) < n:
            d = sock.recv(n - len(buf))
            if not d:
                return buf
            buf += d
        return buf

    def serve_backend(conn):
        try:
            while True:
                if not recv_exact(conn, len(REQ)):
                    return
                conn.sendall(RESP)
        except OSError:
            pass
        finally:
            conn.close()

    def serve_proxy(conn):
        """One upstream per downstream (Envoy's per-connection HTTP/1.1
        upstream), both keep-alive; the upstream connect is the third
        admission verdict of the chain."""
        ups = None
        try:
            ups = proxy_app.socket()
            ups.settimeout(10)
            ups.connect(("127.0.0.1", bport))
            while True:
                req = recv_exact(conn, len(REQ))
                if not req:
                    return
                ups.sendall(req)
                rsp = recv_exact(ups.sock, RESP_LEN)
                if not rsp:
                    return
                conn.sendall(rsp)
        except OSError:
            pass
        finally:
            if ups is not None:
                ups.close()
            conn.close()

    def acceptor(listener, handler):
        def run():
            while not stop.is_set():
                try:
                    wave = listener.accept_batch(max_n=64,
                                                 first_timeout=0.01)
                except OSError:
                    return
                for fconn, _peer in wave:
                    threading.Thread(target=handler, args=(fconn.sock,),
                                     daemon=True).start()
        t = threading.Thread(target=run, daemon=True)
        t.start()
        return t

    acceptor(bsrv, serve_backend)
    acceptor(psrv, serve_proxy)
    out = {"nginx_istio_rules": engine.num_rules}
    try:
        # --- RPS: 50 keep-alive chains (wrk-shaped) ---
        conns = [c for c in client_app.connect_batch(
            [("127.0.0.1", pport)] * 50) if c is not None]
        if len(conns) != 50:
            raise RuntimeError(f"chain admission failed: {len(conns)}/50")
        for c in conns:
            c.settimeout(10)
        reqs = 0
        deadline = time.perf_counter() + duration_s
        t0 = time.perf_counter()
        while time.perf_counter() < deadline:
            c = conns[reqs % 50]
            c.sendall(REQ)
            if len(recv_exact(c.sock, RESP_LEN)) != RESP_LEN:
                raise RuntimeError("chain closed mid-RPS")
            reqs += 1
        out["nginx_istio_rps"] = round(reqs / (time.perf_counter() - t0), 1)
        for c in conns:
            c.close()

        # --- CPS: full fresh chains (4 admission verdicts each) ---
        done = 0
        deadline = time.perf_counter() + duration_s
        t0 = time.perf_counter()
        while time.perf_counter() < deadline:
            wave = [c for c in client_app.connect_batch(
                [("127.0.0.1", pport)] * 16) if c is not None]
            for c in wave:
                c.settimeout(10)
                c.sendall(REQ)
                if len(recv_exact(c.sock, RESP_LEN)) == RESP_LEN:
                    done += 1
                c.close()
        out["nginx_istio_cps"] = round(done / (time.perf_counter() - t0), 1)
        return out
    finally:
        stop.set()
        psrv.close()
        bsrv.close()
        # let serve threads drain out of any in-flight jitted admission
        # check: a daemon thread killed inside an XLA call at
        # interpreter exit aborts the process (observed as "FATAL:
        # exception not rethrown" when this bench ran last)
        time.sleep(0.25)


def vcl_iperf_bench(engine, mb: int = 256, port: int = 15201) -> dict:
    """Bulk-transfer Gbps over loopback: bare kernel vs under the
    LD_PRELOAD session shim (admission served from ``engine``).

    The engine arrives with hoststack_bench's deny-alls installed in
    both scopes, so the iperf port needs explicit admits — which makes
    the shim's verdicts load-bearing, same as the RPS section."""
    import subprocess
    import tempfile

    from vpp_tpu.hoststack.admission import VclAdmissionServer
    from vpp_tpu.hoststack.preload import vcl_env
    from vpp_tpu.hoststack.session_rules import (
        RuleAction, RuleScope, SessionRule,
    )
    from vpp_tpu.hoststack.vcl import _ip_int

    LOOP = _ip_int("127.0.0.1")
    engine.apply(add=[
        SessionRule(scope=int(RuleScope.LOCAL), appns_index=1,
                    transport_proto=6, lcl_net=0, lcl_plen=0,
                    rmt_net=LOOP, rmt_plen=32, lcl_port=0, rmt_port=port,
                    action=int(RuleAction.ALLOW)),
        SessionRule(scope=int(RuleScope.GLOBAL), appns_index=-1,
                    transport_proto=6, lcl_net=LOOP, lcl_plen=32,
                    rmt_net=0, rmt_plen=0, lcl_port=port, rmt_port=0,
                    action=int(RuleAction.ALLOW)),
    ])

    total = mb << 20
    server_code = (
        "import socket, sys\n"
        "ls = socket.socket()\n"
        "ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)\n"
        f"ls.bind((\"127.0.0.1\", {port}))\n"
        "ls.listen(1)\n"
        "print(ls.getsockname()[1], flush=True)\n"
        "c, _ = ls.accept()\n"
        "buf = memoryview(bytearray(1 << 20))\n"
        "n = 0\n"
        "while True:\n"
        "    r = c.recv_into(buf)\n"
        "    if not r:\n"
        "        break\n"
        "    n += r\n"
        "print(n)\n"
    )
    client_code = (
        "import socket, sys, time\n"
        f"total = {total}\n"
        "c = socket.create_connection((\"127.0.0.1\", int(sys.argv[1])),"
        " timeout=30)\n"
        "chunk = b\"x\" * (1 << 20)\n"
        "t0 = time.perf_counter()\n"
        "sent = 0\n"
        "while sent < total:\n"
        "    c.sendall(chunk)\n"
        "    sent += len(chunk)\n"
        "c.close()\n"
        "print(time.perf_counter() - t0)\n"
    )

    def one(env) -> float:
        srv_p = subprocess.Popen([sys.executable, "-c", server_code],
                                 env=env, stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE, text=True)
        try:
            port = int(srv_p.stdout.readline())
            cli = subprocess.run([sys.executable, "-c", client_code,
                                  str(port)], env=env,
                                 capture_output=True, text=True,
                                 timeout=120)
            if cli.returncode != 0:
                raise RuntimeError(f"iperf client: {cli.stderr[-300:]}")
            dt = float(cli.stdout.strip())
            got = int(srv_p.stdout.readline())
            if got != total:
                raise RuntimeError(f"iperf short read {got}/{total}")
            return total * 8 / dt / 1e9
        finally:
            srv_p.kill()
            srv_p.wait(timeout=10)

    kernel_gbps = one(dict(os.environ))
    with tempfile.TemporaryDirectory() as td:
        sock = os.path.join(td, "vcl.sock")
        adm = VclAdmissionServer(engine, sock).start()
        try:
            vcl_gbps = one(vcl_env(sock, appns_index=1))
        finally:
            adm.stop()
    return {
        "iperf_kernel_gbps": round(kernel_gbps, 2),
        "iperf_vcl_ldpreload_gbps": round(vcl_gbps, 2),
    }


def io_daemon_bench(args, duration_s: float = 5.0) -> dict:
    """Real-packet throughput through the FULL node data path: kernel
    veth → AF_PACKET → IO daemon (recvmmsg batch rx) → rx ring →
    pipelined pump → device pipeline → tx ring → daemon (sendmmsg batch
    tx) → AF_PACKET → kernel veth. The reference's whole purpose is
    moving real packets (SURVEY §3.5); this is the number a deployed
    node actually sees. Skipped (empty dict) without CAP_NET_ADMIN."""
    import subprocess

    import jax as _jax

    def sh(*a):
        return subprocess.run(["ip", *a], capture_output=True, timeout=15)

    # capability check + fixture
    created = []
    for pair in (("vppbnA0", "vppbnA1"), ("vppbnB0", "vppbnB1")):
        sh("link", "del", pair[0])
        if sh("link", "add", pair[0], "type", "veth", "peer", "name",
              pair[1]).returncode != 0:
            for leg in created:  # don't leak a half-built fixture
                sh("link", "del", leg)
            return {}
        created.append(pair[0])
        for leg in pair:
            sh("link", "set", leg, "up")

    # everything from here runs under the cleanup block: a failing
    # import/compile/ring setup (busy TPU is a realistic one) must not
    # leak the veth pairs onto the host
    rings = daemon = pump = ppump = None
    try:
        from vpp_tpu.io.daemon import IODaemon
        from vpp_tpu.io.pump import DataplanePump
        from vpp_tpu.io.rings import IORingPair
        from vpp_tpu.io.transport import AfPacketTransport
        from vpp_tpu.pipeline.dataplane import Dataplane
        from vpp_tpu.pipeline.tables import DataplaneConfig
        from vpp_tpu.pipeline.vector import VEC, Disposition

        dp = Dataplane(DataplaneConfig())
        if_a = dp.add_pod_interface(("default", "a"))
        if_b = dp.add_pod_interface(("default", "b"))
        dp.builder.add_route("10.1.1.3/32", if_b, Disposition.LOCAL)
        dp.swap()

        rings = IORingPair(n_slots=256, snap=512)
        daemon = IODaemon(
            rings,
            {if_a: AfPacketTransport("vppbnA0"),
             if_b: AfPacketTransport("vppbnB0")},
            uplink_if=0,
        ).start()
        # the deployed ladder shape (cmd/config.py IOConfig defaults):
        # auto fetch workers + the adaptive chainer armed
        pump = DataplanePump(dp, rings, max_batch=16384, chain_k=4)
        pump.warm()
        pump.start()

        # warm-up barrier: one real packet through veth → daemon →
        # device → daemon before the measured window, so the window
        # never pays dispatch ramp + first fetch RTT. The warm frame
        # reaches vppbnB1 before the receiver binds — unaccounted by
        # design.
        warm_tx = AfPacketTransport("vppbnA1")
        warm_deadline = time.perf_counter() + 120
        while (pump.stats["frames"] == 0
               and time.perf_counter() < warm_deadline):
            warm_tx.send_frame(wire_udp(0))
            time.sleep(0.2)
        warm_tx.close()
        # drain to quiescence: warm frames still in the rx ring /
        # in-flight batches would otherwise reach vppbnB1 after the
        # receiver binds and count in 'got' but never in 'offered'
        stable_since = time.perf_counter()
        stable_count = pump.stats["frames"]
        while time.perf_counter() < warm_deadline:
            time.sleep(0.1)
            now, cnt = time.perf_counter(), pump.stats["frames"]
            if cnt != stable_count:
                stable_count, stable_since = cnt, now
            elif now - stable_since > 1.5:
                break
        # report window-only pump counters: warm-up traffic must not
        # mask "zero frames moved during the measured window"
        pump_base = dict(pump.stats)

        # sender/receiver as SUBPROCESSES: in-process Python threads
        # would fight the daemon+pump threads for the GIL and the
        # receiver would undercount by dropping at its own socket —
        # separate interpreters measure the daemon, not the harness.
        # (They import only the native codec + transports, no jax.)
        env = dict(os.environ)
        env["PYTHONPATH"] = (
            os.path.dirname(os.path.abspath(__file__))
            + os.pathsep + env.get("PYTHONPATH", "")
        )
        def make_sender(pace_pps: float | None) -> str:
            if pace_pps is None:
                loop = (
                    "while time.perf_counter() < deadline:\n"
                    "    k = codec.send_batch(t.batch_fd, payload, rows, "
                    "lens, VEC)\n"
                    "    sent += k\n"
                    "    if k < VEC:\n"
                    "        time.sleep(0.0005)\n"
                )
            else:
                # paced: BURST frames per interval, absolute schedule
                # (next_t += interval) so pacing error doesn't accumulate
                loop = (
                    "BURST = 64\n"
                    f"interval = BURST / {pace_pps}\n"
                    "next_t = t0\n"
                    "while True:\n"
                    "    now = time.perf_counter()\n"
                    "    if now >= deadline:\n"
                    "        break\n"
                    "    if now < next_t:\n"
                    "        time.sleep(min(next_t - now, 0.001))\n"
                    "        continue\n"
                    "    k = codec.send_batch(t.batch_fd, payload, rows, "
                    "lens, BURST)\n"
                    "    sent += k\n"
                    "    next_t += interval\n"
                )
            return (
                "import time\n"
                "import numpy as np\n"
                "from bench import wire_udp\n"
                "from vpp_tpu.io.transport import AfPacketTransport\n"
                "from vpp_tpu.native.pktio import PacketCodec\n"
                "VEC = 256\n"
                "codec = PacketCodec(snap=512)\n"
                "t = AfPacketTransport('vppbnA1')\n"
                "payload = np.zeros((VEC, 512), np.uint8)\n"
                "lens = np.zeros(VEC, np.uint32)\n"
                "for i in range(VEC):\n"
                "    f = wire_udp(i)\n"
                "    payload[i, :len(f)] = np.frombuffer(f, np.uint8)\n"
                "    lens[i] = len(f)\n"
                "rows = np.arange(VEC, dtype=np.uint32)\n"
                # the sender times its own loop: interpreter/numpy
                # startup and frame building must not dilute the window
                "t0 = time.perf_counter()\n"
                f"deadline = t0 + {duration_s}\n"
                "sent = 0\n"
                + loop +
                "print(sent, time.perf_counter() - t0)\n"
            )
        recv_code = (
            "import socket, time\n"
            "import numpy as np\n"
            "from vpp_tpu.io.transport import AfPacketTransport\n"
            "from vpp_tpu.native.pktio import PacketCodec\n"
            "codec = PacketCodec(snap=512)\n"
            "t = AfPacketTransport('vppbnB1')\n"
            "SO_RCVBUFFORCE = 33\n"
            "t.sock.setsockopt(socket.SOL_SOCKET, SO_RCVBUFFORCE,\n"
            "                  256 << 20)\n"  # past rmem_max (CAP_NET_ADMIN)
            "print('READY', flush=True)\n"
            "scratch = np.zeros((256, 512), np.uint8)\n"
            "lens = np.zeros(256, np.uint32)\n"
            f"deadline = time.perf_counter() + {duration_s + 10.0}\n"
            "got, idle_since = 0, None\n"
            "while time.perf_counter() < deadline:\n"
            "    n = codec.recv_batch(t.batch_fd, scratch, lens)\n"
            "    if n > 0:\n"
            "        got += n\n"
            "        idle_since = None\n"
            "    else:\n"
            "        now = time.perf_counter()\n"
            "        if idle_since is None:\n"
            "            idle_since = now\n"
            f"        elif got and now - idle_since > 1.5:\n"
            "            break\n"  # sender done, queue drained
            "        time.sleep(0.0002)\n"
            "print(got)\n"
        )
        def run_round(pace_pps: float | None):
            """One sender/receiver subprocess round; returns
            (offered, got, send_window_s)."""
            recv_proc = subprocess.Popen(
                [sys.executable, "-c", recv_code], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            # wait for the receiver's socket to exist before offering
            # load — frames forwarded to vppbnB1 before the bind are
            # unaccountable
            ready = recv_proc.stdout.readline()
            if "READY" not in ready:
                _, r_err = recv_proc.communicate(timeout=30)
                raise RuntimeError(
                    f"receiver failed to start: {r_err[-300:]}")
            send_proc = subprocess.Popen(
                [sys.executable, "-c", make_sender(pace_pps)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            s_out, s_err = send_proc.communicate(timeout=duration_s + 60)
            r_out, r_err = recv_proc.communicate(timeout=duration_s + 60)
            # a dead endpoint must surface as an ERROR, not as a
            # plausible 0.0 Mpps datum
            if send_proc.returncode != 0 or not s_out.strip():
                raise RuntimeError(f"sender failed: {s_err[-300:]}")
            if recv_proc.returncode != 0 or not r_out.strip():
                raise RuntimeError(f"receiver failed: {r_err[-300:]}")
            offered_s, window_s = s_out.split()
            return int(offered_s), int(r_out.strip()), float(window_s)

        def wait_quiesce(p) -> None:
            """Let in-flight traffic drain through pump ``p``, under a
            HARD cap — trickling background frames (e.g. kernel ND
            chatter) must not reset the wait forever."""
            q_deadline = time.perf_counter() + 20
            q_since, q_cnt = time.perf_counter(), p.stats["frames"]
            while time.perf_counter() < q_deadline:
                time.sleep(0.1)
                cnt = p.stats["frames"]
                if cnt != q_cnt:
                    q_cnt, q_since = cnt, time.perf_counter()
                elif time.perf_counter() - q_since > 1.5:
                    break

        offered, got, send_window = run_round(None)
        # snapshot NOW: the reported pump window counters must cover
        # exactly the saturation round they are named for, not the
        # quiesce drain + paced round that follow
        pump_sat = dict(pump.stats)

        # paced round: offer at ~60% of the measured saturation
        # DELIVERY rate — the deployment regime (goodput at a
        # sustainable load), vs the saturation row where sender-side
        # kernel drops dominate on a shared core (docs/IO_PATH.md).
        # A fresh flow set would re-miss the session cache, so reuse.
        paced = {}
        sat_pps = got / send_window
        if sat_pps > 0:
            try:
                wait_quiesce(pump)
                # the latency window must cover exactly this paced
                # round — saturation-round batches in the deque would
                # report queueing delay as paced latency
                pump.reset_latency()
                p_off, p_got, p_win = run_round(
                    max(sat_pps * 0.6, 5_000.0))
                paced = {
                    "io_daemon_paced_mpps": round(p_got / p_win / 1e6, 4),
                    "io_daemon_paced_offered_mpps": round(
                        p_off / p_win / 1e6, 4),
                    "io_daemon_paced_goodput_pct": round(
                        100.0 * p_got / p_off, 1) if p_off else 0.0,
                }
            except Exception as e:  # noqa: BLE001 — the paced round is
                # additive; its failure must not discard the measured
                # saturation numbers
                paced = {"io_daemon_paced_error":
                         f"{type(e).__name__}: {e}"}

        # persistent-mode round on the SAME deployed path (VERDICT r4
        # Next #2: experienced wire latency in both pump modes). The
        # resident loop is the latency-floor regime — one frame per
        # loop iteration — so pacing it at the DISPATCH ladder's rate
        # (the r5 methodology) asked it for throughput it
        # architecturally doesn't offer and booked the shortfall as
        # 61.7% goodput "loss". Measure ITS saturation first, then
        # pace at 60% of that: goodput at its own sustainable rate is
        # the deployment question (VERDICT r5 Next #2 done-condition).
        dlat = pump.latency_us()
        persistent = {}
        if sat_pps > 0:
            try:
                pump.stop()
                ppump = DataplanePump(dp, rings, mode="persistent")
                ppump.warm()
                ppump.start()
                wait_quiesce(ppump)
                pp_soff, pp_sgot, pp_swin = run_round(None)
                pp_sat_pps = pp_sgot / pp_swin
                wait_quiesce(ppump)
                ppump.reset_latency()  # warm/sat frames excluded
                pp_off, pp_got, pp_win = run_round(
                    max(pp_sat_pps * 0.6, 5_000.0))
                plat = ppump.latency_us()
                # drop-cause attribution (ISSUE 7 satellite): the r5
                # goodput pct hid WHERE loss happened — split it into
                # daemon rx-ring overflow vs pump tx stall vs shutdown
                # so a bad number is diagnosable from the JSON alone
                rwin = int(ppump.stats.get("ring_windows", 0))
                persistent = {
                    "io_daemon_persistent_sat_mpps": round(
                        pp_sat_pps / 1e6, 4),
                    "io_daemon_persistent_mpps": round(
                        pp_got / pp_win / 1e6, 4),
                    "io_daemon_persistent_goodput_pct": round(
                        100.0 * pp_got / max(1, pp_off), 1),
                    "io_daemon_persistent_drops_rx_full": int(
                        daemon.stats.get("drops_rx_full", 0)),
                    "io_daemon_persistent_drops_tx_stall": int(
                        ppump.stats.get("drops_tx_stall", 0)),
                    "io_daemon_persistent_drops_shutdown": int(
                        ppump.stats.get("drops_shutdown", 0)),
                    "io_daemon_persistent_ring_windows": rwin,
                    "io_daemon_persistent_callbacks_per_window": round(
                        int(ppump.stats.get("io_callbacks", 0))
                        / max(1, rwin), 4),
                }
                if plat["n"]:
                    persistent.update({
                        "io_daemon_persistent_pump_lat_p50_us": round(
                            plat["p50"], 1),
                        "io_daemon_persistent_pump_lat_p99_us": round(
                            plat["p99"], 1),
                    })
            except Exception as e:  # noqa: BLE001 — additive round
                persistent = {"io_daemon_persistent_error":
                              f"{type(e).__name__}: {e}"}

        # rate over the offered window (the receiver's post-drain of its
        # kernel queue belongs to that window's traffic)
        return {
            **paced,
            **persistent,
            # n == 0 means the paced round died after reset_latency():
            # omitting beats emitting a plausible-perfect 0.0 datum
            **({"io_daemon_pump_lat_p50_us": round(dlat["p50"], 1),
                "io_daemon_pump_lat_p99_us": round(dlat["p99"], 1)}
               if dlat["n"] else {}),
            "io_daemon_veth_mpps": round(got / send_window / 1e6, 4),
            # the acceptance-named alias of the veth saturation row
            "io_daemon_mpps": round(got / send_window / 1e6, 4),
            "io_daemon_offered_mpps": round(offered / send_window / 1e6, 4),
            # the overlap ladder's shape + activity in the window
            "io_daemon_fetch_workers": pump.workers,
            "io_daemon_max_inflight": pump.max_inflight,
            "io_daemon_chain_k": pump.chain_k,
            "io_daemon_chain_batches":
                pump_sat["chain_batches"] - pump_base["chain_batches"],
            "io_daemon_inflight_peak": pump_sat["inflight_peak"],
            # diagnosability: what the pump actually moved during the
            # measured window, warm-up excluded (a zero delivered count
            # with nonzero pump frames points at the tx side; zero pump
            # frames points at rx/dispatch)
            "io_daemon_pump_frames":
                pump_sat["frames"] - pump_base["frames"],
            "io_daemon_pump_batches":
                pump_sat["batches"] - pump_base["batches"],
            # per-stage pump time attribution (cumulative seconds in
            # the window): which leg of ring->device->ring bounds the
            # wire path (VERDICT r3 Weak #3 diagnosability)
            "io_daemon_t_pack_s": round(
                pump_sat["t_pack"] - pump_base["t_pack"], 3),
            "io_daemon_t_dispatch_s": round(
                pump_sat["t_dispatch"] - pump_base["t_dispatch"], 3),
            # fetch split (io/pump.py): t_fetch is the serial result
            # COPY; t_fetch_wait is waiting for results to become
            # ready — overlapped across the in-flight window, i.e.
            # hidden time, reported so the overlap is observable
            "io_daemon_t_fetch_s": round(
                pump_sat["t_fetch"] - pump_base["t_fetch"], 3),
            "io_daemon_t_fetch_wait_s": round(
                pump_sat["t_fetch_wait"] - pump_base["t_fetch_wait"], 3),
            "io_daemon_t_write_s": round(
                pump_sat["t_write"] - pump_base["t_write"], 3),
        }
    finally:
        if pump is not None:
            pump.stop()
        if ppump is not None:
            ppump.stop()
        if daemon is not None:
            daemon.stop()
            for t in daemon.transports.values():
                t.close()
        if rings is not None:
            rings.close()
        for leg in ("vppbnA0", "vppbnB0"):
            sh("link", "del", leg)


def fleet_bench(args, frame_pkts: int = 1024, iters: int = 8) -> dict:
    """Gateway fleet: elastic scale-out + live rebalance (ISSUE 18).

    Scale-out ladder — N in {1, 2, 4} identical sym-hash instances
    behind one FleetSteering tier, the SAME offered load per rung.
    The deployment model is one instance per host, so each instance's
    packed-step throughput is measured SEQUENTIALLY (they never share
    this harness's cores inside a sample) and the rung aggregates as
    parallel capacity: ``offered / (steer + max(per-instance))``. The
    steering tier's partition cost is charged as a serial prefix — the
    rung only scales if steering stays cheap relative to the step.
    Acceptance: fleet_scaleout_ratio (per-doubling geometric mean)
    >= 1.8. CPU-harness caveat: the sequential-measure/sum framing is
    what makes the rung meaningful on one host; on a real multi-host
    deployment the same keys measure true aggregate.

    Live rebalance — a 2-instance fleet takes a 3rd member under
    continuous FleetPump load; the newcomer's rendezvous-won ranges
    migrate live (fence → drain → adopt → commit → release). Keys
    prove the tentpole bar: EXACT conservation (zero unattributed
    loss), bounded dispatch p99 across the move, and fastpath
    hit-rate >= 0.9 on the migrated flows within a bounded number of
    post-move windows.
    """
    import threading

    import jax as _jax

    from vpp_tpu.fleet.hashring import assign_ranges
    from vpp_tpu.fleet.membership import FleetMembership
    from vpp_tpu.fleet.steering import FleetSteering
    from vpp_tpu.io.fleet import FleetPump
    from vpp_tpu.ir.rule import Action, ContivRule, Protocol
    from vpp_tpu.kvstore.store import KVStore
    from vpp_tpu.pipeline.dataplane import (
        Dataplane,
        pack_packet_columns,
    )
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import Disposition

    shrink = _jax.default_backend() == "cpu" and not args.cpu_full
    if shrink:
        frame_pkts, iters = 512, 4
    n_frames = 32 if shrink else 64
    sess_slots = (1 << 16) if shrink else (1 << 18)
    # many ranges per instance smooth the rendezvous spread — a
    # 4-member rung owns ~16 ranges each, so per-host load imbalance
    # stays small and the ladder measures steering + step cost, not
    # assignment variance
    n_ranges = 64

    def mk_dp():
        cfg = DataplaneConfig(
            max_tables=2, max_rules=16, max_global_rules=16,
            max_ifaces=8, fib_slots=16, sess_slots=sess_slots,
            sess_ways=4, nat_mappings=2, nat_backends=2,
            sess_sweep_stride=0, sess_hash="sym")
        dp = Dataplane(cfg)
        dp.add_uplink()
        dp.add_pod_interface(("default", "web"))
        dp.builder.add_route("10.1.1.0/24", 2, Disposition.LOCAL)
        dp.builder.set_global_table([
            ContivRule(action=Action.PERMIT, protocol=Protocol.TCP),
            ContivRule(action=Action.DENY)])
        dp.swap()
        return dp

    pod_ip = np.uint32((10 << 24) | (1 << 16) | (1 << 8) | 2)

    def mk_frames(n_fr, width, reply=False):
        """Packed [5, width] frames of distinct TCP flows; ``reply``
        reverses direction (same canonical buckets under sym hash)."""
        out = []
        for f in range(n_fr):
            flow = f * width + np.arange(width)
            src = (np.uint32((10 << 24) | (9 << 16))
                   + (flow % 65536).astype(np.uint32))
            sport = (1024 + flow % 40000).astype(np.int32)
            n = width
            cols = {
                "src_ip": np.full(n, pod_ip) if reply else src,
                "dst_ip": src if reply else np.full(n, pod_ip),
                "proto": np.full(n, 6, np.int32),
                "sport": np.full(n, 80, np.int32) if reply else sport,
                "dport": sport if reply else np.full(n, 80, np.int32),
                "ttl": np.full(n, 64, np.int32),
                "pkt_len": np.full(n, 64, np.int32),
                "rx_if": np.full(n, 2 if reply else 1, np.int32),
                "flags": np.ones(n, np.int32),
            }
            flat = np.zeros((5, n), np.int32)
            pack_packet_columns(flat.view(np.uint32), cols, n)
            out.append(flat)
        return out

    out: dict = {}
    fr = mk_frames(n_frames, frame_pkts)
    offered = n_frames * frame_pkts
    out["fleet_scaleout_pkts"] = offered
    rungs = (1, 2, 4)
    fleets = {}
    try:
        for n_inst in rungs:
            names = [f"gw{i}" for i in range(n_inst)]
            dps = {nm: mk_dp() for nm in names}
            st = FleetSteering(dps, n_ranges=n_ranges)
            # warm/compile once (instances share one geometry → one
            # cached packed step) before any timed sample
            for dp in dps.values():
                _jax.block_until_ready(
                    dp.process_packed(fr[0], commit=False))
            parts = [st.partition(f)[0] for f in fr]
            plan = []
            for nm in names:
                share = [np.ascontiguousarray(f[:, idx])
                         for f, groups in zip(fr, parts)
                         for idx in (groups.get(nm),)
                         if idx is not None and idx.size]
                cols = np.concatenate(share, axis=1)
                npk = cols.shape[1]
                pad = (-npk) % frame_pkts
                if pad:
                    cols = np.concatenate(
                        [cols, np.zeros((5, pad), np.int32)],
                        axis=1)
                inst_frames = [cols[:, i:i + frame_pkts]
                               for i in range(0, cols.shape[1],
                                              frame_pkts)]
                # equal-DURATION samples: scale iterations so every
                # sample moves the same packet total regardless of
                # share size (a quarter-share loop is otherwise so
                # short it fits inside one host-scheduler throttling
                # window and reads 30-40% slow)
                it = max(1, round(offered * iters / npk))
                plan.append((dps[nm], nm, npk, it, inst_frames))
            fleets[n_inst] = (st, plan)

        # INTERLEAVED best-of-3 over all rungs: the harness's
        # sustained rate drifts on ~minute timescales (burst credits,
        # frequency scaling), so measuring rung 1 minutes before rung
        # 4 folds host drift straight into the scaling ratio; a
        # round-robin pass hits every rung inside each drift window
        # and best-of picks each instance's sustained floor
        steer_best = {n: float("inf") for n in rungs}
        proc_best: dict = {}
        for _ in range(3):
            for n_inst in rungs:
                st, plan = fleets[n_inst]
                t0 = time.perf_counter()
                for f in fr:
                    st.partition(f)
                steer_best[n_inst] = min(
                    steer_best[n_inst], time.perf_counter() - t0)
                for dp, nm, npk, it, inst_frames in plan:
                    t0 = time.perf_counter()
                    res = None
                    for _ in range(it):
                        for flat in inst_frames:
                            res = dp.process_packed(flat,
                                                    commit=True)
                    _jax.block_until_ready(res)
                    _jax.block_until_ready(dp.tables.sess_valid)
                    dt = time.perf_counter() - t0
                    key = (n_inst, nm)
                    proc_best[key] = min(
                        proc_best.get(key, float("inf")), dt)

        mpps = {}
        for n_inst in rungs:
            st, plan = fleets[n_inst]
            # padded tail slots are processed but not credited — the
            # per-host rate only counts real packets; hosts run in
            # parallel (one instance per host) so their rates SUM,
            # and the dispatch tier's serial partition rate caps the
            # aggregate — the rung only scales while steering stays
            # off the critical path
            tput = [npk * it / proc_best[(n_inst, nm)]
                    for _, nm, npk, it, _f in plan]
            steer_rate = offered / steer_best[n_inst]
            mpps[n_inst] = min(sum(tput), steer_rate) / 1e6
            out[f"fleet_scaleout_mpps_{n_inst}"] = round(
                mpps[n_inst], 3)
        out["fleet_steer_ns_pkt"] = round(
            steer_best[4] / offered * 1e9, 1)
    finally:
        for st, _plan in fleets.values():
            st.close()
    out["fleet_scaleout_ratio"] = round(
        (mpps[4] / mpps[1]) ** 0.5, 2)

    # --- live rebalance under load -----------------------------------
    width = 256
    n_flows = 2048 if shrink else 8192
    fwd = mk_frames(n_flows // width, width)
    rev = mk_frames(n_flows // width, width, reply=True)
    names = ["gw0", "gw1", "gw2"]
    dps = {nm: mk_dp() for nm in names}
    st = FleetSteering(
        dps, membership=FleetMembership(KVStore(), name="bench"),
        n_ranges=n_ranges)
    pump = FleetPump(st, frame_width=width, queue_slots=256)

    def drain(timeout=60.0):
        pump.flush()
        t0 = time.perf_counter()
        while pump.pending() and time.perf_counter() - t0 < timeout:
            time.sleep(0.001)

    seen = {"hits": 0, "deliv": 0}

    def window(frames_list):
        lats = []
        for f in frames_list:
            t0 = time.perf_counter()
            pump.submit(f)
            lats.append(time.perf_counter() - t0)
        drain()
        snap = pump.stats_snapshot()
        hits = sum(a.get("sess_hits", 0)
                   for a in snap["aux"].values())
        deliv = sum(snap["delivered"].values())
        dh = hits - seen["hits"]
        dd = deliv - seen["deliv"]
        seen["hits"], seen["deliv"] = hits, deliv
        return lats, (dh / dd if dd else 0.0)

    try:
        # shrink the fleet to two members, then establish every flow
        st.rebalance(target=assign_ranges(["gw0", "gw1"], n_ranges))
        pump.start()
        for f in fwd:
            pump.submit(f)
        drain()
        # prime the per-window delta baseline PAST the establishment
        # phase (inserts, not hits) so window hit rates measure only
        # reply traffic
        snap0 = pump.stats_snapshot()
        seen["hits"] = sum(a.get("sess_hits", 0)
                           for a in snap0["aux"].values())
        seen["deliv"] = sum(snap0["delivered"].values())
        base_lats, base_hit = window(rev)
        out["fleet_rebalance_hit_rate_base"] = round(base_hit, 3)

        # the newcomer joins: default target re-runs rendezvous over
        # all three instances; its won ranges migrate live while
        # reply windows keep flowing through the pump
        ss0 = st.stats_snapshot()
        mover = threading.Thread(target=st.rebalance, daemon=True)
        move_lats: list = []
        mover.start()
        while mover.is_alive():
            lats, _ = window(rev)
            move_lats.extend(lats)
        mover.join()

        recovery = -1
        max_w = 10
        for w in range(1, max_w + 1):
            _, hit = window(rev)
            if hit >= 0.9:
                recovery = w
                break
        out["fleet_rebalance_hit_rate_final"] = round(hit, 3)
        out["fleet_rebalance_recovery_windows"] = recovery
        pump.stop()
        cons = pump.conservation()
        attributed = (cons["delivered"] + cons["fenced_drops"]
                      + cons["no_owner_drops"] + cons["queue_drops"]
                      + cons["pending"])
        out["fleet_rebalance_offered"] = cons["offered"]
        out["fleet_rebalance_delivered"] = cons["delivered"]
        out["fleet_rebalance_fenced_drops"] = cons["fenced_drops"]
        out["fleet_rebalance_queue_drops"] = cons["queue_drops"]
        out["fleet_rebalance_conservation_exact"] = int(
            cons["offered"] == attributed and cons["pending"] == 0)
        ss = st.stats_snapshot()
        out["fleet_rebalance_ranges_moved"] = (
            ss["migrated_ranges"] - ss0["migrated_ranges"])
        out["fleet_rebalance_sessions_moved"] = (
            ss["migrated_sessions"] - ss0["migrated_sessions"])
        out["fleet_rebalance_p99_ms_base"] = round(
            float(np.percentile(np.array(base_lats) * 1e3, 99)), 3)
        if move_lats:
            out["fleet_rebalance_p99_ms_move"] = round(
                float(np.percentile(np.array(move_lats) * 1e3, 99)), 3)
    finally:
        try:
            pump.stop()
        except Exception:  # noqa: BLE001 — already stopped
            pass
        st.close()
    return out


def overlay_bench(args, iters: int = 12, batch: int = 2048) -> dict:
    """Device-resident VXLAN overlay + svc NAT44 planes (ISSUE 19
    tentpole): three captures.

      * **encap overhead** — the deployed chain compiled overlay off
        vs vxlan over IDENTICAL east-west traffic at the headline rule
        count; the vxlan variant additionally runs the decap
        validator, the per-packet outer-header math and the outer-FIB
        walk INSIDE the one jitted program, so the delta IS the
        always-paid overlay cost (``overlay_encap_overhead_pct``,
        acceptance: <= 15).
      * **east-west round** — pod-to-pod across a 2-instance gateway
        fleet: VXLAN frames addressed to the anycast VTEP are spread
        by the steering tier (outer entropy sport — exactly how
        underlay ECMP spreads them), decapped on whichever instance
        owns the flow, delivered locally or re-encapped toward the
        destination node. Per-tenant VNI isolation: an unknown VNI
        fails CLOSED (drop_overlay) on every instance, conservation
        exact.
      * **backend churn** — a rolling service-backend replacement at
        svc scale ships ONLY the svc group's few-KB scatter blob
        (``svc_churn_bytes``; every non-svc device array carries over
        by identity) and keeps surviving backends' hash ways
        (``svc_sticky_kept_pct`` — only the replaced backend's flows
        move, with zero unattributed loss).

    CPU-harness caveat: overhead pct compares two compilations of the
    same chain on the same backend, so the RATIO is meaningful even
    when the absolute step cost is CPU-bound (the fleet_bench
    framing); on TPU the same keys price the real deployment.
    """
    import jax
    import jax.numpy as jnp

    from vpp_tpu.fleet.steering import FleetSteering
    from vpp_tpu.ir.rule import Action, ContivRule, Protocol
    from vpp_tpu.ops.vxlan import OUTER_TTL, VXLAN_PORT, ENCAP_OVERHEAD
    from vpp_tpu.pipeline.dataplane import Dataplane, pack_packet_columns
    from vpp_tpu.pipeline.graph import make_pipeline_step
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import (
        Disposition,
        FLAG_VALID,
        PacketVector,
        ip4,
    )

    shrink = jax.default_backend() == "cpu" and not args.cpu_full
    if shrink:
        iters = max(iters // 2, 4)
    out: dict = {"overlay_batch": batch, "overlay_rules": args.rules}

    # --- the overlay + svc gateway under test (parts 1 and 3) ---
    config = DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=args.rules + 1,
        max_ifaces=16, fib_slots=64, sess_slots=1 << 14,
        nat_mappings=4, nat_backends=4, overlay="vxlan",
        svc_vips=64, svc_backend_ways=8,
    )
    dp = Dataplane(config)
    uplink = dp.add_uplink()
    pod_if = dp.add_pod_interface(("default", "server"))
    dp.set_vtep(ip4("192.168.16.1"))
    dp.builder.add_route("10.1.1.0/24", pod_if, Disposition.LOCAL)
    # svc backends live behind the pod interface
    dp.builder.add_route("10.200.0.0/16", pod_if, Disposition.LOCAL)
    # 16 remote pod /24s, each behind a peer VTEP (inner FIB), plus the
    # VTEP underlay /24 the OUTER header resolves through — the second
    # FIB walk the vxlan variant pays every step
    for x in range(16):
        dp.builder.add_route(
            f"10.2.{x}.0/24", uplink, Disposition.REMOTE,
            next_hop=ip4(f"192.168.16.{2 + x % 8}"), node_id=2 + x)
    dp.builder.add_route("192.168.16.0/24", uplink, Disposition.REMOTE)
    rules = build_rules(args.rules)
    # VIP traffic (dport 80) rides the same table as the east-west mix
    rules.insert(0, ContivRule(action=Action.PERMIT,
                               protocol=Protocol.TCP, dest_port=80))
    dp.builder.set_global_table(rules)
    # 48 service VIPs x 4 backends: the svc planes at deployment scale
    # (64-row capacity), so the churn round exercises the incremental
    # blob path (the w-ladder needs blocks smaller than the VIP axis)
    vips = {}
    for v in range(48):
        key = (ip4(f"10.96.{v // 250}.{2 + v % 250}"), 80, 6)
        backends = [(ip4(f"10.200.{v}.10") + j, 80, 1) for j in range(4)]
        dp.builder.set_service(*key, backends)
        vips[v] = (key, backends)
    dp.swap()
    out["svc_full_upload_bytes"] = int(dp.builder.svc_upload["bytes"])

    # --- part 1: the always-paid overlay stage cost -------------------
    # East-west transit shaped on the rule grid (src block <-> dport
    # pairing of build_rules) so the batch actually forwards: permitted
    # frames take a REMOTE next_hop route and the vxlan variant
    # re-encaps them toward the peer VTEP on-device.
    rng = np.random.default_rng(19)
    ridx = rng.integers(0, max(args.rules - 1, 1), batch)
    ridx = ridx + (ridx % 6 == 5)  # step off the interleaved DENY rows
    block = ridx % 1000
    src = ((172 << 24) | ((16 + block // 256) << 16)
           | ((block % 256) << 8)
           | rng.integers(1, 255, batch)).astype(np.uint32)
    dst = ((10 << 24) | (2 << 16) | ((ridx % 16) << 8)
           | rng.integers(2, 250, batch)).astype(np.uint32)
    pkts = PacketVector(
        src_ip=jnp.asarray(src),
        dst_ip=jnp.asarray(dst),
        proto=jnp.full((batch,), 6, jnp.int32),
        sport=jnp.asarray(
            rng.integers(1024, 65535, batch).astype(np.int32)),
        dport=jnp.asarray(
            (8000 + (ridx // 1000) % 20).astype(np.int32)),
        ttl=jnp.full((batch,), 64, jnp.int32),
        pkt_len=jnp.full((batch,), 512, jnp.int32),
        rx_if=jnp.full((batch,), uplink, jnp.int32),
        flags=jnp.full((batch,), FLAG_VALID, jnp.int32),
    )
    impl, skip = dp.classifier_impl, dp._skip_local
    step_off = jax.jit(make_pipeline_step(impl, skip,
                                          fib_impl=dp.fib_impl))
    step_ovl = jax.jit(make_pipeline_step(impl, skip,
                                          fib_impl=dp.fib_impl,
                                          overlay="vxlan"))
    tables = dp.tables
    no_frames = jnp.full((batch,), -1, jnp.int32)  # plain-IP sidecar

    def med_us(step, *extra):
        jax.block_until_ready(step(tables, pkts, jnp.int32(2),
                                   *extra).disp)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(step(tables, pkts, jnp.int32(2),
                                       *extra).disp)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) * 1e6

    t_off = med_us(step_off)
    t_ovl = med_us(step_ovl, pkts, no_frames)
    probe = step_ovl(tables, pkts, jnp.int32(2), pkts, no_frames)
    out["overlay_encap_pkts"] = int(probe.stats.ovl_encap)
    out["overlay_off_us"] = round(t_off, 1)
    out["overlay_on_us"] = round(t_ovl, 1)
    out["overlay_stage_ns_pkt"] = round(
        max(t_ovl - t_off, 0.0) / batch * 1e3, 2)
    out["overlay_encap_overhead_pct"] = round(
        100.0 * (t_ovl - t_off) / max(t_off, 1e-9), 2)

    # --- part 3: rolling backend replacement (zero-reship churn) ------
    # Flow fan toward one VIP; probe() observes the hash-way pick
    # without committing sessions, so stickiness below is the svc
    # plane's sticky fill — not session pinning.
    n_flows = 512
    vkey, vbackends = vips[7]
    frng = np.random.default_rng(23)
    vip_pkts = PacketVector(
        src_ip=jnp.asarray(
            (ip4("172.16.0.0")
             + frng.integers(1, 255, n_flows)).astype(np.uint32)),
        dst_ip=jnp.full((n_flows,), vkey[0], jnp.uint32),
        proto=jnp.full((n_flows,), 6, jnp.int32),
        sport=jnp.asarray(
            (1024 + np.arange(n_flows) * 13 % 50000).astype(np.int32)),
        dport=jnp.full((n_flows,), 80, jnp.int32),
        ttl=jnp.full((n_flows,), 64, jnp.int32),
        pkt_len=jnp.full((n_flows,), 128, jnp.int32),
        rx_if=jnp.full((n_flows,), uplink, jnp.int32),
        flags=jnp.full((n_flows,), FLAG_VALID, jnp.int32),
    )
    r0 = dp.probe(vip_pkts, now=3)
    picks0 = np.asarray(r0.pkts.dst_ip)
    ok0 = np.asarray(r0.disp) != int(Disposition.DROP)
    pins = (dp.tables.glb_src_net, dp.tables.acl_src_net,
            dp.tables.fib_prefix, dp.tables.tnt_vni)
    # roll ONE backend of ONE vip — the Deployment rolling-update beat
    replaced = vbackends[3]
    new_bk = (ip4("10.200.99.99"), 80, 1)
    with dp.commit_lock:
        dp.builder.set_service(*vkey, vbackends[:3] + [new_bk])
        dp.swap()
    up = dp.builder.svc_upload
    out["svc_churn_bytes"] = int(up["bytes"])
    out["svc_churn_blob_bytes"] = int(up["blob_bytes"])
    out["svc_churn_fields"] = len(up["fields"])
    out["svc_churn_ms"] = round(float(up["ms"]), 3)
    out["svc_churn_zero_reship"] = int(all(
        a is b for a, b in zip(pins, (
            dp.tables.glb_src_net, dp.tables.acl_src_net,
            dp.tables.fib_prefix, dp.tables.tnt_vni))))
    r1 = dp.probe(vip_pkts, now=4)
    picks1 = np.asarray(r1.pkts.dst_ip)
    ok1 = np.asarray(r1.disp) != int(Disposition.DROP)
    survivor = ok0 & (picks0 != np.uint32(replaced[0]))
    moved = ok0 & (picks0 == np.uint32(replaced[0]))
    out["svc_churn_flows"] = int(ok0.sum())
    out["svc_churn_loss"] = int(ok0.sum() - ok1.sum())
    out["svc_sticky_kept_pct"] = round(
        100.0 * float((picks1[survivor] == picks0[survivor]).mean())
        if survivor.any() else 100.0, 2)
    out["svc_moved_flows"] = int(moved.sum())
    out["svc_moved_to_new_pct"] = round(
        100.0 * float((picks1[moved] == np.uint32(new_bk[0])).mean())
        if moved.any() else 100.0, 2)

    # --- part 2: pod-to-pod across the fleet, per-tenant VNIs ---------
    def mk_gw():
        cfg = DataplaneConfig(
            max_tables=2, max_rules=16, max_global_rules=8,
            max_ifaces=8, fib_slots=32, sess_slots=1 << 12,
            sess_ways=4, sess_hash="sym", nat_mappings=1,
            nat_backends=1, tenancy="on", tenancy_tenants=4,
            overlay="vxlan")
        gw = Dataplane(cfg)
        gup = gw.add_uplink()
        gpod = gw.add_pod_interface(("default", "east"))
        gw.set_vtep(ip4("192.168.32.1"))  # anycast gateway VTEP
        gw.builder.set_tenant(1, prefixes=["10.61.0.0/16"], vni=100)
        gw.builder.set_tenant(2, prefixes=["10.62.0.0/16"], vni=200)
        for t in (61, 62):
            gw.builder.add_route(f"10.{t}.1.0/24", gpod,
                                 Disposition.LOCAL)
            gw.builder.add_route(
                f"10.{t}.2.0/24", gup, Disposition.REMOTE,
                next_hop=ip4("192.168.32.9"), node_id=3)
        gw.builder.add_route("192.168.32.0/24", gup,
                             Disposition.REMOTE)
        gw.builder.set_global_table([
            ContivRule(action=Action.PERMIT, protocol=Protocol.TCP),
            ContivRule(action=Action.DENY)])
        gw.swap()
        return gw, gup

    n2 = 512
    lanes = np.arange(n2)
    tnt = 1 + (lanes % 2)
    bad = (lanes % 8) == 7
    to_local = (lanes // 2) % 2 == 0
    inner_src = ((10 << 24) | ((60 + tnt) << 16) | (9 << 8)
                 | (1 + lanes % 250)).astype(np.uint32)
    inner_dst = ((10 << 24) | ((60 + tnt) << 16)
                 | (np.where(to_local, 1, 2) << 8)
                 | (2 + lanes % 250)).astype(np.uint32)
    vni = np.where(bad, 999, np.where(tnt == 1, 100, 200)).astype(
        np.int32)
    outer_cols = {
        "src_ip": np.full(n2, ip4("192.168.32.50"), np.uint32),
        "dst_ip": np.full(n2, ip4("192.168.32.1"), np.uint32),
        "proto": np.full(n2, 17, np.int32),
        "sport": (49152 + lanes % 16384).astype(np.int32),
        "dport": np.full(n2, VXLAN_PORT, np.int32),
        "ttl": np.full(n2, OUTER_TTL, np.int32),
        "pkt_len": np.full(n2, 128 + ENCAP_OVERHEAD, np.int32),
        "rx_if": np.ones(n2, np.int32),
        "flags": np.full(n2, FLAG_VALID, np.int32),
    }
    flat = np.zeros((5, n2), np.int32)
    pack_packet_columns(flat.view(np.uint32), outer_cols, n2)

    gws = {"gw-a": mk_gw(), "gw-b": mk_gw()}
    st = FleetSteering({nm: g for nm, (g, _) in gws.items()})
    try:
        groups, sdrops = st.partition(flat)
        delivered = reencapped = decapped = bad_dropped = 0
        bad_offered = int(bad.sum())
        spread = {}
        for nm, idx in groups.items():
            gw, gup = gws[nm]
            k = idx.size
            spread[nm] = k
            sel = np.concatenate(
                [idx, np.zeros(n2 - k, np.int64)]).astype(np.int64)
            live = np.arange(n2) < k
            outer_pv = PacketVector(
                src_ip=jnp.asarray(outer_cols["src_ip"][sel]),
                dst_ip=jnp.asarray(outer_cols["dst_ip"][sel]),
                proto=jnp.asarray(outer_cols["proto"][sel]),
                sport=jnp.asarray(outer_cols["sport"][sel]),
                dport=jnp.asarray(outer_cols["dport"][sel]),
                ttl=jnp.asarray(outer_cols["ttl"][sel]),
                pkt_len=jnp.asarray(outer_cols["pkt_len"][sel]),
                rx_if=jnp.full((n2,), 1, jnp.int32),
                flags=jnp.asarray(
                    np.where(live, FLAG_VALID, 0).astype(np.int32)),
            )
            inner_pv = PacketVector(
                src_ip=jnp.asarray(inner_src[sel]),
                dst_ip=jnp.asarray(inner_dst[sel]),
                proto=jnp.full((n2,), 6, jnp.int32),
                sport=jnp.asarray(
                    (1024 + sel % 40000).astype(np.int32)),
                dport=jnp.full((n2,), 80, jnp.int32),
                ttl=jnp.full((n2,), 64, jnp.int32),
                pkt_len=jnp.full((n2,), 128, jnp.int32),
                rx_if=jnp.full((n2,), 1, jnp.int32),
                flags=jnp.asarray(
                    np.where(live, FLAG_VALID, 0).astype(np.int32)),
            )
            vni_pv = np.where(live, vni[sel], -1).astype(np.int32)
            r = gw.process(outer_pv, now=5, ovl_inner=inner_pv,
                           ovl_vni=vni_pv)
            disp = np.asarray(r.disp)[:k]
            delivered += int((disp == int(Disposition.LOCAL)).sum())
            reencapped += int(r.stats.ovl_encap)
            decapped += int(r.stats.ovl_decap)
            bad_dropped += int(r.stats.drop_overlay)
        n_good = n2 - bad_offered - sdrops["fenced"] - \
            sdrops["no_owner"]
        out["overlay_eastwest_frames"] = n2
        out["overlay_eastwest_instances"] = len(gws)
        out["overlay_eastwest_spread_min_pct"] = round(
            100.0 * min(spread.values(), default=0) / n2, 1)
        out["overlay_eastwest_decapped"] = decapped
        out["overlay_eastwest_delivered"] = delivered
        out["overlay_eastwest_reencapped"] = reencapped
        out["overlay_eastwest_delivered_pct"] = round(
            100.0 * (delivered + reencapped) / max(n_good, 1), 1)
        out["overlay_eastwest_bad_vni"] = bad_offered
        out["overlay_eastwest_bad_dropped"] = bad_dropped
        out["overlay_eastwest_isolated"] = int(
            bad_dropped == bad_offered)
        out["overlay_eastwest_conservation_exact"] = int(
            delivered + reencapped + bad_dropped
            + sdrops["fenced"] + sdrops["no_owner"] == n2)
    finally:
        st.close()
    return out


def _autotune_profile():
    """The committed tuned/<backend>.json knobs, if the repo carries a
    profile for this backend (None otherwise) — so a bench round and
    the config a deployment would boot with land in one JSON line."""
    try:
        import jax as _jax

        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tuned", f"{_jax.default_backend()}.json")
        if not os.path.exists(path):
            return None
        with open(path) as f:
            prof = json.load(f)
        return {"path": os.path.relpath(path, os.getcwd()),
                "knobs": prof.get("knobs"),
                "floor_us": prof.get("floor_us")}
    except Exception as e:  # noqa: BLE001 — additive, never fatal
        return {"error": f"{type(e).__name__}: {e}"}


# sidecar bookkeeping keys that are not measured sections


def _run():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rules", type=int, default=10240)
    ap.add_argument("--packets", type=int, default=65536,
                    help="packets per pipeline step (throughput run)")
    ap.add_argument("--backends", type=int, default=100)
    ap.add_argument("--iters", type=int, default=50,
                    help="throughput iterations")
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--latency-frame", type=int, default=256,
                    help="frame size for the added-latency measurement")
    ap.add_argument("--cpu", action="store_true",
                    help="debug: run on the CPU backend; the output "
                         "names the cpu platform and is no chip number")
    ap.add_argument("--cpu-full", action="store_true", dest="cpu_full",
                    help="with --cpu: run full-size fleet/overlay "
                         "workloads (slow; default shrinks them)")
    ap.add_argument("--no-subbench", action="store_true",
                    help="skip the secondary BASELINE configs (#1/#3/#4)")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.cpu else "tpu"):
        raise SystemExit(
            f"bench.py: found platform {platform!r}, needs a TPU "
            "(pass --cpu for a CPU debug run)")
    from vpp_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp

    from vpp_tpu.pipeline.graph import make_pipeline_step

    # --- priority capture (VERDICT r5 Next #1): the sections that have
    # never been measured on real hardware run FIRST — sess_election_*,
    # commit_ms_*, the ring-to-ring wire path in both pump modes, and
    # the deployed io-daemon rows — BEFORE the multi-minute headline
    # compile. Each is individually guarded: a failure records its
    # error key, the run continues, and the exit status is non-zero.
    pri = {}
    _jc = _jit_compiles_now()
    _tb = _transfer_bytes_now()
    try:
        pri.update(session_election_bench(args))
    except Exception as e:  # noqa: BLE001 — priority sections are
        # individually additive
        pri["sess_election_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["sess_election_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["sess_election_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # set-associative session table (ISSUE 6): old-vs-new insert
        # medians + the 10M-resident scale rows (admission ksps,
        # resident millions) — acceptance: sess_insert_speedup_x >= 3
        pri.update(session_scale_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["session_scale_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["session_scale_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["session_scale_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # crash-consistent snapshot at the scale config (ISSUE 8):
        # chunked-drain cost + the concurrent per-step stall —
        # acceptance: snapshot_step_stall_pct < 10
        pri.update(snapshot_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["snapshot_bench_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["snapshot_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["snapshot_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        pri.update(commit_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["commit_bench_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["commit_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["commit_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # classifier shoot-out (ISSUE 4): dense vs MXU vs BV at 1,024
        # and the headline rule count — re-validates the auto default
        pri.update(acl_classifier_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["acl_classifier_bench_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["acl_classifier_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["acl_classifier_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # million-route LPM FIB (ISSUE 15): 1M-route build, LPM vs
        # dense lookup ns/pkt (+ the dense-at-1M extrapolation), one
        # /24 flap's bounded commit, ECMP member spread — acceptance:
        # lpm <= 2x dense-at-native, >= 10x dense-extrapolated-to-1M,
        # churn ships only the touched length plane
        pri.update(fib_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["fib_bench_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["fib_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["fib_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # pallas kernel rungs (ISSUE 16): fused vs reference ns/pkt +
        # bit-exactness for the three gather-bound hot ops — native on
        # TPU, interpret-mode semantics pricing elsewhere
        pri.update(pallas_kernel_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["pallas_kernel_bench_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["pallas_kernel_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["pallas_kernel_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # tentpole capture: the two-tier fast path's measured win at
        # the headline rule count (acceptance: >= 3x on all-established)
        pri.update(fastpath_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["fastpath_bench_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["fastpath_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["fastpath_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # per-packet ML stage (ISSUE 10): marginal in-step cost of the
        # int8 MLP + the zero-re-ship model-swap check (acceptance:
        # ml_headline_overhead_pct < 10, ml_swap_zero_reship == 1)
        pri.update(ml_stage_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["ml_stage_bench_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["ml_stage_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["ml_stage_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # device telemetry plane (ISSUE 11): in-step histogram/sketch
        # overhead + the on-device load-vs-tail sweep + sketch
        # fidelity (acceptance: telemetry_overhead_pct < 5,
        # flow_topk_recall >= 0.9)
        pri.update(latency_telemetry_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["latency_telemetry_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["latency_telemetry_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["latency_telemetry_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # gateway fleet (ISSUE 18): the scale-out ladder (1→2→4
        # instances, acceptance fleet_scaleout_ratio >= 1.8 per
        # doubling) + live rebalance under pump load (acceptance:
        # conservation EXACT, hit-rate recovery >= 0.9)
        pri.update(fleet_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["fleet_bench_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["fleet_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["fleet_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    try:
        # device-resident VXLAN overlay + svc NAT44 planes (ISSUE 19):
        # the always-paid overlay stage cost at the headline rule
        # count (acceptance: overlay_encap_overhead_pct <= 15), the
        # pod-to-pod cross-instance round over the steering tier with
        # per-tenant VNI isolation, and the rolling backend
        # replacement's svc-only blob (svc_churn_bytes — a few KB,
        # every non-svc plane identity-pinned)
        pri.update(overlay_bench(args))
    except Exception as e:  # noqa: BLE001
        pri["overlay_bench_error"] = f"{type(e).__name__}: {e}"
    _jc_now = _jit_compiles_now()
    pri["overlay_jit_compiles"] = _jc_now - _jc
    _jc = _jc_now
    _tb_now = _transfer_bytes_now()
    pri["overlay_transfer_bytes"] = _tb_now - _tb
    _tb = _tb_now
    if not args.no_subbench:
        try:
            pri.update(io_ring_bench(args))
        except Exception as e:  # noqa: BLE001
            pri["io_ring_bench_error"] = f"{type(e).__name__}: {e}"
        _jc_now = _jit_compiles_now()
        pri["io_ring_jit_compiles"] = _jc_now - _jc
        _jc = _jc_now
        _tb_now = _transfer_bytes_now()
        pri["io_ring_transfer_bytes"] = _tb_now - _tb
        _tb = _tb_now
        try:
            # reflex-plane latency governor (ISSUE 13): the priority
            # ladder at 50/80/95/120% of sat x {ungoverned, governed}
            # + the square-wave burst scenario (acceptance: governed
            # priority p99 <= 2x the lone-frame floor,
            # latency_slo_goodput_ratio >= 0.9, io_callbacks == 0,
            # zero new step variants)
            pri.update(latency_slo_bench(args))
        except Exception as e:  # noqa: BLE001
            pri["latency_slo_bench_error"] = f"{type(e).__name__}: {e}"
        _jc_now = _jit_compiles_now()
        pri["latency_slo_jit_compiles"] = _jc_now - _jc
        _jc = _jc_now
        _tb_now = _transfer_bytes_now()
        pri["latency_slo_transfer_bytes"] = _tb_now - _tb
        _tb = _tb_now
        try:
            # multi-tenant isolation (ISSUE 14): 4 tenants on the
            # wire path, tenant 4 at 4x quota with a square-wave
            # burst (acceptance: well-behaved goodput >= 0.9x solo,
            # p99 <= 2x solo, overage fully attributed
            # tenant_quota/overload, conservation exact)
            pri.update(tenant_isolation_bench(args))
        except Exception as e:  # noqa: BLE001
            pri["tenant_isolation_bench_error"] = \
                f"{type(e).__name__}: {e}"
        _jc_now = _jit_compiles_now()
        pri["tenant_isolation_jit_compiles"] = _jc_now - _jc
        _jc = _jc_now
        _tb_now = _transfer_bytes_now()
        pri["tenant_isolation_transfer_bytes"] = _tb_now - _tb
        _tb = _tb_now
        try:
            pri.update(io_daemon_bench(args))
        except Exception as e:  # noqa: BLE001 — optional, env-dependent
            pri["io_daemon_bench_error"] = f"{type(e).__name__}: {e}"
        _jc_now = _jit_compiles_now()
        pri["io_daemon_jit_compiles"] = _jc_now - _jc
        _jc = _jc_now
        _tb_now = _transfer_bytes_now()
        pri["io_daemon_transfer_bytes"] = _tb_now - _tb
        _tb = _tb_now

    dp, uplink = build_dataplane(args.rules, args.backends)
    # headline runs whatever the deployed dataplane selected (the
    # classifier: auto ladder — BV at the 10k regime, re-validated by
    # the acl_classifier_* shoot-out above — AND the fib_impl ladder,
    # dense at the headline's node-scale FIB; fib_bench above carries
    # the million-route LPM rows)
    step_fn = make_pipeline_step(dp.classifier_impl, dp._skip_local,
                                 fib_impl=dp.fib_impl)
    step = jax.jit(step_fn, donate_argnums=(0,))

    # --- throughput: K chained steps, sessions threaded through ---
    pkts = build_traffic(args.packets, uplink)
    tables = dp.tables
    for i in range(args.warmup):
        res = step(tables, pkts, jnp.int32(i + 1))
        tables = res.tables
    jax.block_until_ready(tables)

    t0 = time.perf_counter()
    for i in range(args.iters):
        res = step(tables, pkts, jnp.int32(100 + i))
        tables = res.tables
    jax.block_until_ready(res)
    dt = time.perf_counter() - t0
    mpps = args.packets * args.iters / dt / 1e6

    # --- added latency: single small-frame step, p50/p99 ---
    def pack_frame(pv, n):
        """Latency-section staging: one packed [5, n] int32 frame from
        a PacketVector (shared by the chained and persistent levers —
        they must measure identical traffic)."""
        from vpp_tpu.pipeline.dataplane import pack_packet_columns

        cols = {
            f: np.asarray(getattr(pv, f))
            for f in ("src_ip", "dst_ip", "proto", "sport", "dport",
                      "ttl", "pkt_len", "rx_if", "flags")
        }
        flat = np.zeros((5, n), np.int32)
        pack_packet_columns(flat.view(np.uint32), cols, n)
        return flat

    frame = build_traffic(args.latency_frame, uplink, seed=11)
    lat = []
    for i in range(args.warmup):
        out = step(tables, frame, jnp.int32(i))
        jax.block_until_ready(out.disp)
        tables = out.tables
    for i in range(200):
        t0 = time.perf_counter()
        out = step(tables, frame, jnp.int32(1000 + i))
        jax.block_until_ready(out.disp)
        lat.append(time.perf_counter() - t0)
        tables = out.tables
    lat_us = np.array(lat) * 1e6

    # steady-state (pipelined) per-frame latency: dispatch K frames
    # back-to-back without host sync — the per-frame cost once dispatch
    # overlaps execution, the deployment regime of a streaming data plane
    K = 64
    t0 = time.perf_counter()
    for i in range(K):
        out = step(tables, frame, jnp.int32(2000 + i))
        tables = out.tables
    jax.block_until_ready(out.disp)
    pipelined_us = (time.perf_counter() - t0) / K * 1e6

    # chained quantum (VERDICT r3 Next #4 lever): K packed frames run
    # inside ONE device program (lax.scan) with ONE dispatch + ONE
    # sync, vs K separate dispatches above. Amortizes the per-step
    # host round trip; measured per frame.
    KC = 16
    chain_dp, chain_up = build_dataplane(args.rules, args.backends)
    cframe = build_traffic(args.latency_frame, chain_up, seed=12)
    one = pack_frame(cframe, args.latency_frame)
    flats = np.broadcast_to(
        one, (KC, 5, args.latency_frame)).copy()
    jax.block_until_ready(
        chain_dp.process_packed_chain(flats.copy(), now=1)
    )  # compile
    chain_lat = []
    for i in range(20):
        t0 = time.perf_counter()
        jax.block_until_ready(
            chain_dp.process_packed_chain(flats.copy(), now=10 + i)
        )
        chain_lat.append((time.perf_counter() - t0) / KC * 1e6)
    chained_us = float(np.percentile(np.array(chain_lat), 50))

    # persistent device-ring path (docs/LATENCY.md round-7 lever):
    # frames ride device-resident descriptor-ring windows — a lone
    # frame ships in a 1-slot window, so this ping-pong measures the
    # single-window exchange quantum (zero io_callbacks). Latency-
    # floor regime; additive and best-effort.
    persistent_us = None
    pump_p = None
    try:
        from vpp_tpu.pipeline.persistent import PersistentPump

        pdp, pup = build_dataplane(args.rules, args.backends)
        pflat = pack_frame(build_traffic(args.latency_frame, pup,
                                         seed=13), args.latency_frame)
        pump_p = PersistentPump(pdp.tables, batch=args.latency_frame,
                                classifier=pdp.classifier_impl,
                                skip_local=pdp._skip_local)
        pump_p.start()
        pump_p.submit(pflat, now=1)          # warm (traces the loop)
        pump_p.result(timeout=600)
        lat_p = []
        for i in range(50):
            t0 = time.perf_counter()
            pump_p.submit(pflat, now=2 + i)
            pump_p.result(timeout=120)
            lat_p.append(time.perf_counter() - t0)
        persistent_us = round(
            float(np.percentile(np.array(lat_p) * 1e6, 50)), 1)
    except Exception as e:  # noqa: BLE001 — prototype lever, optional
        persistent_us = f"error: {type(e).__name__}: {e}"
    finally:
        # the resident program must NOT outlive this section: on a
        # single-execution-stream device it would block everything
        # after it (it sits in host_fetch waiting for frames)
        if pump_p is not None:
            try:
                pump_p.stop()
            except Exception:  # noqa: BLE001 — already recorded above
                pass

    subs = {} if args.no_subbench else sub_benches(args)
    subs.update(pri)  # priority-capture sections into the final details
    if not args.no_subbench:
        try:
            subs.update(hoststack_bench(args))
        except Exception as e:  # noqa: BLE001 — optional, env-dependent
            subs["hoststack_bench_error"] = f"{type(e).__name__}: {e}"
        try:
            subs.update(proxy_chain_bench(args))
        except Exception as e:  # noqa: BLE001 — optional, env-dependent
            subs["nginx_istio_error"] = f"{type(e).__name__}: {e}"
    # the honest experienced figure: ring-to-ring wire-path latency at
    # a paced (non-saturating) offered load, NOT pipelined-throughput/N
    # (VERDICT r2 Weak #2); the wire bench fills it in when it ran
    if "io_wire_lat_p99_us" in subs:
        subs["added_latency_p99_us_experienced"] = subs["io_wire_lat_p99_us"]

    result = {
        "metric": "acl_nat_pipeline_mpps_10k_rules",
        "value": round(mpps, 3),
        "unit": "Mpps",
        "vs_baseline": round(mpps / BASELINE_MPPS, 4),
        "details": {
            "rules": args.rules,
            "packets_per_step": args.packets,
            "nat_backends": args.backends,
            "frame_latency_p50_us": round(float(np.percentile(lat_us, 50)), 1),
            "frame_latency_p99_us": round(float(np.percentile(lat_us, 99)), 1),
            "frame_latency_pipelined_us": round(pipelined_us, 1),
            # K frames inside ONE device program, one
            # dispatch+sync (lax.scan chain) — the bounded-sync
            # quantum, per frame (docs/LATENCY.md lever #4)
            "frame_latency_chained_us": round(chained_us, 1),
            # resident while_loop + io_callback refills: zero
            # per-frame dispatch (docs/LATENCY.md lever #5)
            "frame_latency_persistent_us": persistent_us,
            # throughput at the DEPLOYED frame size (VPP's 256-
            # packet frames), not the 65536-packet bench steps —
            # the honest companion to the batch-inflated headline
            "pipeline_mpps_at_frame": round(
                args.latency_frame / pipelined_us, 3
            ),
            "per_packet_added_latency_us": round(
                pipelined_us / args.latency_frame, 3
            ),
            "latency_frame": args.latency_frame,
            # runtime jit-compile guard roll-up: per-section
            # *_jit_compiles deltas ride in via **subs; this is
            # the whole-run total (flat across rounds unless a
            # recompile regression landed)
            "jit_compiles_total": _jit_compiles_now(),
            "device_transfer_bytes_total": _transfer_bytes_now(),
            # committed autotuner profile for this backend
            # (tools/autotune.py; ISSUE 16) — the knobs a
            # deployment loading tuned/<backend>.json would
            # run with, alongside the numbers measured here
            "autotune_profile": _autotune_profile(),
            "backend": jax.default_backend(),
            "device": {
                "platform": jax.devices()[0].platform,
                "kind": jax.devices()[0].device_kind,
                "count": len(jax.devices()),
            },
            # wire-path numbers are host-CPU-bound too: the
            # sender/daemon/pump/receiver share the host cores
            "host_cores": os.cpu_count(),
            **subs,
        },
    }
    print(json.dumps(result))
    errors = _section_errors(result["details"])
    if errors:
        raise SystemExit(f"bench.py: sections raised: {errors}")


def _section_errors(details: dict) -> list:
    """Keys of the sections that raised (recorded as ``*_error`` keys
    or as an ``error: ...`` string in place of a number)."""
    bad = []
    for k, v in details.items():
        if k.endswith("_error") or (isinstance(v, str)
                                    and v.startswith("error:")):
            bad.append(k)
        elif isinstance(v, dict) and "error" in v:
            bad.append(k)
    return sorted(bad)


if __name__ == "__main__":
    _run()
