"""Multi-tenant gateway mode (ISSUE 14; vpp_tpu/tenancy/).

Four layers:

* **device kernels** — tenant-id derivation (first-match-wins,
  symmetric under src/dst swap) and the per-tenant token bucket pinned
  against an INDEPENDENT NumPy oracle over seeded multi-window traffic
  (refill clamp, burst cap, in-batch arrival-rank determinism,
  rate=0 unlimited).
* **pipeline differentials** — quota drops attributed DROP_TENANT with
  exact conservation and no session install; tenancy-on-unconfigured
  bit-exact vs tenancy-off (the default staging is the identity);
  tenant-sliced session capacity where a flooded slice fails/evicts
  only WITHIN its owning tenant (never cross-tenant — structural);
  replies landing in the same slice (the symmetric-key contract);
  per-tenant ML mode/threshold overrides against ONE staged model with
  zero weight re-ship; the tenant upload group's independence from
  rule churn; and the shard-composition differential (tenant-sliced
  bucket indices under the 2-way mesh ownership split reproduce the
  standalone lookup bit-exactly — the PARTITION_RULES contract).
* **host scheduling** — TenantScheduler WFQ units (proportional
  service, idle-rebase anti-banking, hog-first shedding, ring-fault
  requeue) + TenantClassifier units, then the REAL pump: a saturating
  tenant's backlog cannot starve a later-arriving light tenant
  (weighted-fair dequeue), and the device token-bucket drops surface
  as drops_tenant_quota with the per-tenant planes agreeing exactly.
* **wiring** — config validation refusals at YAML load, `show
  tenants`, the vpp_tpu_tenant_* families, the one-new-step-form +
  zero-io_callback contract.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from wire import make_frame

from vpp_tpu.ir.rule import Action, ContivRule, Protocol
from vpp_tpu.io import DataplanePump, IORingPair
from vpp_tpu.native.pktio import PacketCodec
from vpp_tpu.pipeline.dataplane import Dataplane
from vpp_tpu.pipeline.graph import DROP_TENANT
from vpp_tpu.pipeline.tables import (
    SESSION_FIELDS,
    DataplaneConfig,
)
from vpp_tpu.pipeline.vector import (
    VEC,
    Disposition,
    ip4,
    make_packet_vector,
)
from vpp_tpu.tenancy.derive import tenant_ids, tenant_limit
from vpp_tpu.tenancy.sched import (
    TenantClassifier,
    TenantScheduler,
    tenant_entries_from_config,
    validate_tenancy_config,
)
from vpp_tpu.testing import faults

# tenant address plan: tenant 1 owns 10.50/16, tenant 2 owns 10.60/16,
# everything else (10.1.1.0/24 pods) is the default tenant 0
T1_NET = "10.50.0.0/16"
T2_NET = "10.60.0.0/16"


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.install(None)
    yield
    faults.install(None)


def build_dp(tenants=(), sess_slots=256, **over):
    """Tenancy-on dataplane: pod route for 10.1.1.0/24, default-route
    uplink, permit-TCP-80 + permit-UDP + deny global table, the given
    tenant registry staged before the first swap."""
    cfg = DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=32, max_ifaces=8,
        fib_slots=16, sess_slots=sess_slots, nat_mappings=2,
        nat_backends=2, tenancy="on", sess_sweep_stride=0, **over,
    )
    dp = Dataplane(cfg)
    up = dp.add_uplink()
    pod = dp.add_pod_interface(("default", "web"))
    dp.builder.add_route("10.1.1.0/24", pod, Disposition.LOCAL)
    dp.builder.add_route("0.0.0.0/0", up, Disposition.REMOTE, node_id=1)
    dp.builder.set_global_table([
        ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                   dest_port=80),
        ContivRule(action=Action.PERMIT, protocol=Protocol.UDP),
        ContivRule(action=Action.DENY),
    ])
    for e in tenants:
        kw = {k: v for k, v in e.items() if k != "id"}
        dp.builder.set_tenant(e["id"], **kw)
    dp.swap()
    return dp, up, pod


def tenant_traffic(up, tid_nets, n=None, seed=0, dport=80, proto=6):
    """One packet per (net, i) pair: src inside the tenant's net,
    dst a pod address — distinct flows per call via the seed."""
    rng = np.random.default_rng(seed)
    pkts = []
    for net, count in tid_nets:
        base = net.split("/")[0].rsplit(".", 2)[0]
        for i in range(count):
            pkts.append(dict(
                src=f"{base}.{rng.integers(0, 250)}.{rng.integers(1, 250)}",
                dst=f"10.1.1.{2 + (i % 200)}",
                proto=proto, sport=int(rng.integers(1024, 65000)),
                dport=dport, rx_if=up))
    return make_packet_vector(pkts, n=n or max(16, len(pkts)))


# --------------------------------------------------------------------
# derivation + token bucket vs the NumPy oracle
# --------------------------------------------------------------------


class TestDerivation:
    def test_derivation_multi_prefix_and_default(self):
        """Disjoint prefix ownership (cross-tenant overlap is refused
        at validation — the device's first-match and the host
        classifier's max only agree on disjoint maps): a tenant may
        hold several prefixes, including SAME-tenant nesting, and
        unmatched addresses derive the default tenant 0."""
        dp, up, _pod = build_dp(tenants=[
            # same-tenant nesting is harmless: either slot derives 2
            {"id": 2, "prefixes": ["10.50.7.0/24", T1_NET]},
            {"id": 3, "prefixes": [T2_NET]},
        ])
        pv = make_packet_vector([
            {"src": "10.50.7.9", "dst": "10.1.1.2", "proto": 6,
             "sport": 1, "dport": 80, "rx_if": up},   # nested -> 2
            {"src": "10.50.8.9", "dst": "10.1.1.2", "proto": 6,
             "sport": 2, "dport": 80, "rx_if": up},   # broad -> 2
            {"src": "10.60.0.9", "dst": "10.1.1.2", "proto": 6,
             "sport": 3, "dport": 80, "rx_if": up},   # -> 3
            {"src": "172.16.0.1", "dst": "10.1.1.2", "proto": 6,
             "sport": 4, "dport": 80, "rx_if": up},   # unmatched -> 0
        ], n=8)
        tid = np.asarray(tenant_ids(dp.tables, pv))
        assert tid[0] == 2 and tid[1] == 2 and tid[2] == 3 \
            and tid[3] == 0

    def test_symmetric_under_src_dst_swap(self):
        """key_tenant(a, b) == key_tenant(b, a) — the property that
        makes a forward flow's insert key and the reply's lookup key
        land in the same tenant slice."""
        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET]},
            {"id": 3, "prefixes": [T2_NET]},
        ])
        rng = np.random.default_rng(7)
        fwd = make_packet_vector([
            dict(src=f"10.{rng.choice([50, 60, 1])}.{rng.integers(0, 250)}"
                     f".{rng.integers(1, 250)}",
                 dst=f"10.{rng.choice([50, 60, 1])}.{rng.integers(0, 250)}"
                     f".{rng.integers(1, 250)}",
                 proto=6, sport=100 + i, dport=80, rx_if=up)
            for i in range(24)
        ], n=24)
        rev = make_packet_vector([
            dict(src=".".join(str((int(np.asarray(fwd.dst_ip)[i]) >> s)
                                  & 255) for s in (24, 16, 8, 0)),
                 dst=".".join(str((int(np.asarray(fwd.src_ip)[i]) >> s)
                                  & 255) for s in (24, 16, 8, 0)),
                 proto=6, sport=80, dport=100 + i, rx_if=up)
            for i in range(24)
        ], n=24)
        assert np.array_equal(np.asarray(tenant_ids(dp.tables, fwd)),
                              np.asarray(tenant_ids(dp.tables, rev)))


def bucket_oracle(rate, burst, tokens, tok_time, tids, alive, now):
    """Independent sequential re-implementation of tenancy/derive.py
    tenant_limit: per-packet, in packet order, each tenant consumes
    from its refilled bucket."""
    T = len(rate)
    dt = np.clip(now - tok_time, 0, 1 << 14)
    tok = np.minimum(burst, tokens + rate * dt).astype(np.int64)
    dropped = np.zeros(len(tids), bool)
    for p in range(len(tids)):
        if not alive[p]:
            continue
        t = tids[p]
        if rate[t] <= 0:
            continue
        if tok[t] > 0:
            tok[t] -= 1
        else:
            dropped[p] = True
    tok_after = np.where(rate > 0, np.clip(tok, 0, burst), burst)
    return dropped, tok_after.astype(np.int32), \
        np.full(T, now, np.int32)


class TestTokenBucketOracle:
    def test_multi_window_differential(self):
        """Seeded mixed traffic over 3 tenants x 6 windows with
        varying inter-window gaps (including a clamp-sized idle gap):
        dropped mask and bucket levels bit-equal to the sequential
        oracle every window."""
        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 3, "burst": 8},
            {"id": 2, "prefixes": [T2_NET], "rate": 1, "burst": 2},
            # tenant 3: registered but unlimited (rate 0)
            {"id": 3, "prefixes": ["10.70.0.0/16"], "rate": 0},
        ])
        tables = dp.tables
        rng = np.random.default_rng(11)
        now = 5
        for w, gap in enumerate((0, 1, 2, 7, 40000, 1)):
            now += gap
            pv = tenant_traffic(
                up, [(T1_NET, int(rng.integers(2, 12))),
                     (T2_NET, int(rng.integers(1, 6))),
                     ("10.70.0.0/16", 3),
                     ("172.16.0.0/16", 2)],
                n=32, seed=100 + w)
            alive = np.asarray(pv.valid)
            tids = np.asarray(tenant_ids(tables, pv))
            want_drop, want_tok, want_time = bucket_oracle(
                np.asarray(tables.tnt_rate),
                np.asarray(tables.tnt_burst),
                np.asarray(tables.tnt_tokens),
                np.asarray(tables.tnt_tok_time),
                tids, alive, now)
            tables, dropped = tenant_limit(
                tables, jnp.asarray(tids), jnp.asarray(alive),
                jnp.int32(now))
            assert np.array_equal(np.asarray(dropped), want_drop), \
                f"window {w}: dropped mask diverged"
            assert np.array_equal(np.asarray(tables.tnt_tokens),
                                  want_tok), f"window {w}: levels"
            assert np.array_equal(np.asarray(tables.tnt_tok_time),
                                  want_time)
        # the schedule really exercised both outcomes
        assert int(np.asarray(tables.tnt_tokens)[2]) >= 0

    def test_refill_no_int32_overflow_at_bounds(self):
        """rate=2^16 with burst=2^30 (both at the validator's
        inclusive bounds) and clamp-sized idle gaps: the naive
        ``tokens + rate*dt`` reaches exactly 2^31 and wraps negative —
        the headroom-capped refill must keep a full bucket at burst
        and keep admitting in-quota traffic."""
        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 1 << 16,
             "burst": 1 << 30},
        ])
        tables = dp.tables
        none = jnp.zeros(16, jnp.int32), jnp.zeros(16, bool)
        # prime: one empty round at a clamp-sized gap fills the bucket
        # to burst (rate*dt alone == 2^30)
        tables, _ = tenant_limit(tables, none[0], none[1],
                                 jnp.int32(1 << 14))
        assert int(np.asarray(tables.tnt_tokens)[1]) == 1 << 30
        # second clamp-sized idle gap with the bucket FULL: the naive
        # sum is 2^31 (negative in int32) and would drop everything
        pv = tenant_traffic(up, [(T1_NET, 8)], n=16, seed=3)
        tids = jnp.asarray(np.asarray(tenant_ids(tables, pv)))
        alive = jnp.asarray(np.asarray(pv.valid))
        tables, dropped = tenant_limit(tables, tids, alive,
                                       jnp.int32(2 << 14))
        assert not np.asarray(dropped).any(), \
            "int32 refill overflow dropped in-quota traffic"
        assert int(np.asarray(tables.tnt_tokens)[1]) == (1 << 30) - 8


# --------------------------------------------------------------------
# pipeline differentials
# --------------------------------------------------------------------


class TestQuotaDrops:
    def test_attributed_conserved_and_no_session(self):
        """Over-quota packets: DROP_TENANT attribution, StepStats
        conservation (rx counts them, tx excludes them), and NO
        session install for a dropped packet."""
        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 1, "burst": 4},
        ])
        pv = tenant_traffic(up, [(T1_NET, 10), ("172.16.0.0/16", 3)],
                            n=16, seed=1)
        # now=100 lets the bucket refill to burst (rate 1/tick from
        # the zero init at tick 0)
        res = dp.process(pv, now=100)
        limited = int(res.stats.tnt_limited)
        assert limited == 6  # burst 4 admits 4 of 10; 3 default free
        cause = np.asarray(res.drop_cause)
        assert (cause == DROP_TENANT).sum() == limited
        # conservation: rx counts the dropped packets as received
        assert int(res.stats.rx) == 13
        assert int(res.stats.tx) + int(res.stats.drop_acl) \
            + limited == 13
        # dropped packets installed no session: only the 7 forwarded
        # flows are resident
        assert int(np.asarray(dp.tables.sess_valid).sum()) == 7
        snap = dp.tenant_snapshot()
        assert snap is not None
        assert int(snap["rl_drops"][1]) == limited
        assert int(snap["rx"][1]) == 10
        assert int(snap["tx"][1]) + limited == 10

    def test_unconfigured_tenancy_is_bit_exact_identity(self):
        """tenancy: on with NO tenants registered must forward
        bit-identically to tenancy: off — same verdicts, same session
        cells (the default staging hashes into the same buckets)."""
        dp_on, up, _ = build_dp()
        cfg_off = DataplaneConfig(
            max_tables=2, max_rules=16, max_global_rules=32,
            max_ifaces=8, fib_slots=16, sess_slots=256, nat_mappings=2,
            nat_backends=2, tenancy="off", sess_sweep_stride=0)
        dp_off = Dataplane(cfg_off)
        up2 = dp_off.add_uplink()
        pod2 = dp_off.add_pod_interface(("default", "web"))
        dp_off.builder.add_route("10.1.1.0/24", pod2, Disposition.LOCAL)
        dp_off.builder.add_route("0.0.0.0/0", up2, Disposition.REMOTE,
                                 node_id=1)
        dp_off.builder.set_global_table([
            ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                       dest_port=80),
            ContivRule(action=Action.PERMIT, protocol=Protocol.UDP),
            ContivRule(action=Action.DENY),
        ])
        dp_off.swap()
        assert up == up2
        for step, seed in ((1, 3), (2, 3), (3, 4)):  # repeat = refresh
            pv = tenant_traffic(up, [(T1_NET, 6), (T2_NET, 4),
                                     ("172.16.0.0/16", 4)],
                                n=16, seed=seed)
            ra = dp_on.process(pv, now=step)
            rb = dp_off.process(pv, now=step)
            for f in ("disp", "tx_if", "drop_cause", "established"):
                assert np.array_equal(np.asarray(getattr(ra, f)),
                                      np.asarray(getattr(rb, f))), f
            for f in SESSION_FIELDS:
                assert np.array_equal(
                    np.asarray(getattr(ra.tables, f)),
                    np.asarray(getattr(rb.tables, f))), \
                    f"{f} diverged — default staging is not identity"


class TestSlicedCapacity:
    def _sliced_pair(self):
        # 256 slots / 4 ways = 64 buckets; tenant 1+2 sliced 4 buckets
        # (16 slots) each
        return build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "sess_buckets": 4},
            {"id": 2, "prefixes": [T2_NET], "sess_buckets": 4},
        ])

    def test_flood_never_evicts_other_tenant(self):
        """Fill tenant 2 with 8 flows, then flood tenant 1 with 64
        distinct flows into its 16-slot slice: tenant 1 over-fills
        (insert failures counted against IT), tenant 2's sessions all
        survive — structurally untouchable by the flood."""
        dp, up, _pod = self._sliced_pair()
        r0 = dp.process(
            tenant_traffic(up, [(T2_NET, 8)], n=16, seed=5), now=1)
        assert int(r0.stats.tx) == 8
        snap = dp.tenant_snapshot()
        t2_live = int(snap["occupancy"][2])
        # 8 flows over 16 slice slots: a same-bucket overflow is
        # possible but most must land
        assert t2_live >= 6
        # the flood: 64 distinct UDP flows in one batch
        r1 = dp.process(
            tenant_traffic(up, [(T1_NET, 64)], n=64, seed=6,
                           dport=5000, proto=17), now=2)
        snap = dp.tenant_snapshot()
        assert int(snap["occupancy"][1]) <= 16  # capped at the slice
        assert int(snap["occupancy"][2]) == t2_live  # UNTOUCHED
        # over-filling a 16-slot slice with 64 same-batch flows MUST
        # fail some inserts, attributed to tenant 1
        assert int(r1.stats.tnt_qfail) > 0
        assert int(snap["quota_fails"][1]) == int(r1.stats.tnt_qfail)
        assert int(snap["quota_fails"][2]) == 0

    def test_unsliced_flood_never_evicts_sliced_tenant(self):
        """The REVERSE direction of the no-eviction guarantee: default
        (unmatched → tenant 0, unsliced) flood traffic hashes only
        into the residual bottom region — slices allocate from the top
        of the table, so an unsliced flood is structurally unable to
        touch a sliced tenant's residents."""
        dp, up, _pod = self._sliced_pair()
        r0 = dp.process(
            tenant_traffic(up, [(T2_NET, 8)], n=16, seed=5), now=1)
        assert int(r0.stats.tx) == 8
        snap = dp.tenant_snapshot()
        t2_live = int(snap["occupancy"][2])
        assert t2_live >= 6
        # the flood arrives from an UNREGISTERED range: 64 distinct
        # UDP flows derive tenant 0 and contend only with each other
        dp.process(
            tenant_traffic(up, [("172.16.0.0/16", 64)], n=64, seed=9,
                           dport=5000, proto=17), now=2)
        snap = dp.tenant_snapshot()
        assert int(snap["occupancy"][2]) == t2_live, \
            "unsliced flood evicted a sliced tenant's sessions"
        assert int(snap["quota_fails"][2]) == 0

    def test_reply_lands_in_same_slice_established(self):
        """The symmetric-key contract end-to-end: forward flows from a
        SLICED tenant install sessions; their replies (reversed
        endpoints) hit established — the reverse lookup hashed into
        the same slice."""
        dp, up, pod = self._sliced_pair()
        fwd = tenant_traffic(up, [(T1_NET, 6)], n=16, seed=8)
        r0 = dp.process(fwd, now=1)
        assert int(r0.stats.tx) == 6
        reply = make_packet_vector([
            dict(src=".".join(str((int(np.asarray(fwd.dst_ip)[i]) >> s)
                                  & 255) for s in (24, 16, 8, 0)),
                 dst=".".join(str((int(np.asarray(fwd.src_ip)[i]) >> s)
                                  & 255) for s in (24, 16, 8, 0)),
                 proto=6, sport=80,
                 dport=int(np.asarray(fwd.sport)[i]), rx_if=pod)
            for i in range(6)
        ], n=16)
        r1 = dp.process(reply, now=2)
        est = np.asarray(r1.established)
        assert est[:6].all(), "reply missed its own tenant slice"


class TestTenantMl:
    def _ml_dp(self, tenants):
        from vpp_tpu.ml.train import train_and_pack

        model, _ = train_and_pack(kind="mlp", hidden=8, seed=7,
                                  action="drop")
        cfg = DataplaneConfig(
            max_tables=2, max_rules=16, max_global_rules=32,
            max_ifaces=8, fib_slots=16, sess_slots=256, nat_mappings=2,
            nat_backends=2, tenancy="on", sess_sweep_stride=0,
            ml_stage="enforce", ml_hidden=8)
        dp = Dataplane(cfg)
        up = dp.add_uplink()
        pod = dp.add_pod_interface(("default", "web"))
        dp.builder.add_route("10.1.1.0/24", pod, Disposition.LOCAL)
        dp.builder.add_route("0.0.0.0/0", up, Disposition.REMOTE,
                             node_id=1)
        dp.builder.set_global_table([ContivRule(action=Action.PERMIT)])
        model.flag_thresh = -(1 << 30)  # flag EVERYTHING (inherit)
        dp.builder.set_ml_model(model)
        for e in tenants:
            kw = {k: v for k, v in e.items() if k != "id"}
            dp.builder.set_tenant(e["id"], **kw)
        dp.swap()
        return dp, up

    def test_per_tenant_modes_against_one_model(self):
        """One staged flag-everything drop model; tenant 1 ml off,
        tenant 2 score-only, tenant 3 a never-flag threshold override,
        default inherits enforce: per-packet outcomes follow the
        TENANT, not the global stage."""
        dp, up = self._ml_dp([
            {"id": 1, "prefixes": [T1_NET], "ml_mode": "off"},
            {"id": 2, "prefixes": [T2_NET], "ml_mode": "score"},
            {"id": 3, "prefixes": ["10.70.0.0/16"],
             "ml_thresh": (1 << 31) - 1},
        ])
        pv = tenant_traffic(
            up, [(T1_NET, 4), (T2_NET, 4), ("10.70.0.0/16", 4),
                 ("172.16.0.0/16", 4)], n=16, seed=9)
        res = dp.process(pv, now=1)
        tid = np.asarray(tenant_ids(dp.tables, pv))
        disp = np.asarray(res.disp)
        cause = np.asarray(res.drop_cause)
        from vpp_tpu.pipeline.graph import DROP_ML
        from vpp_tpu.pipeline.vector import Disposition as D

        fwd = disp == int(D.LOCAL)
        # tenant 1 (ml off) + tenant 3 (thresh max): all forwarded
        assert fwd[tid == 1].all()
        assert fwd[tid == 3].all()
        # tenant 2 (score): flagged but never dropped
        assert fwd[tid == 2].all()
        # default tenant inherits enforce: all ml-dropped
        assert (cause[(tid == 0) & np.asarray(pv.valid)]
                == DROP_ML).all()
        assert int(res.stats.ml_drops) == 4

    def test_threshold_flip_reships_zero_weight_planes(self):
        dp, up = self._ml_dp([
            {"id": 1, "prefixes": [T1_NET]},
        ])
        w1 = dp.tables.glb_ml_w1
        before_pfx = dp.tables.tnt_pfx_net
        with dp.commit_lock:
            dp.builder.set_tenant_ml(1, ml_mode="score",
                                     ml_thresh=123)
            dp.swap()
        assert dp.tables.glb_ml_w1 is w1, \
            "tenant ML flip re-shipped the model planes"
        assert int(np.asarray(dp.tables.glb_ml_tnt_thresh)[1]) == 123
        assert dp.tables.tnt_pfx_net is not before_pfx


class TestUploadGroups:
    def test_tenant_group_independent_of_rule_churn(self):
        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 5, "burst": 10},
        ])
        pfx = dp.tables.tnt_pfx_net
        rate = dp.tables.tnt_rate
        rules_before = dp.tables.glb_src_net
        # rule churn: tenant planes ride by identity
        with dp.commit_lock:
            dp.builder.set_global_table([
                ContivRule(action=Action.DENY, protocol=Protocol.TCP,
                           dest_port=2222),
                ContivRule(action=Action.PERMIT)])
            dp.swap()
        assert dp.tables.tnt_pfx_net is pfx
        assert dp.tables.tnt_rate is rate
        # tenant churn: rule planes ride by identity
        rules_now = dp.tables.glb_src_net
        assert rules_now is not rules_before
        with dp.commit_lock:
            dp.builder.set_tenant(2, prefixes=[T2_NET], rate=1,
                                  burst=2)
            dp.swap()
        assert dp.tables.glb_src_net is rules_now
        assert dp.tables.tnt_rate is not rate

    def test_bucket_state_carries_across_swaps(self):
        """Token-bucket levels and accounting planes ride epoch swaps
        by reference — a rule churn must not refill buckets or zero
        counters."""
        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 1, "burst": 4},
        ])
        dp.process(tenant_traffic(up, [(T1_NET, 10)], n=16, seed=12),
                   now=1)
        rl_before = int(np.asarray(dp.tables.tnt_rl_c)[1])
        tok_before = int(np.asarray(dp.tables.tnt_tokens)[1])
        assert rl_before > 0
        with dp.commit_lock:
            dp.builder.set_global_table([
                ContivRule(action=Action.PERMIT)])
            dp.swap()
        assert int(np.asarray(dp.tables.tnt_rl_c)[1]) == rl_before
        assert int(np.asarray(dp.tables.tnt_tokens)[1]) == tok_before


class TestShardComposition:
    @pytest.mark.slow  # ~17 s: mesh-sliced tenant lookup compile; per-tenant correctness stays fast, mesh slicing anchored by test_partition 2-way
    def test_sliced_lookup_2way_mesh_bitexact(self):
        """The PARTITION_RULES contract (ISSUE 14): tenant slices
        address GLOBAL bucket units, so the mesh's blocked bucket
        ownership composes unchanged — a 2-way shard_map reverse
        lookup over a tenant-SLICED table reproduces the standalone
        lookup bit-exactly (hits AND matched slots)."""
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from vpp_tpu.ops.session import session_lookup_reverse_idx
        from vpp_tpu.parallel.partition import RULE_AXIS, ShardCtx

        dp, up, pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "sess_buckets": 8},
            {"id": 2, "prefixes": [T2_NET], "sess_buckets": 8},
        ], sess_slots=512)  # 128 buckets
        fwd = tenant_traffic(up, [(T1_NET, 10), (T2_NET, 6),
                                  ("172.16.0.0/16", 4)], n=32, seed=13)
        dp.process(fwd, now=1)
        reply = make_packet_vector([
            dict(src=".".join(str((int(np.asarray(fwd.dst_ip)[i]) >> s)
                                  & 255) for s in (24, 16, 8, 0)),
                 dst=".".join(str((int(np.asarray(fwd.src_ip)[i]) >> s)
                                  & 255) for s in (24, 16, 8, 0)),
                 proto=6, sport=80,
                 dport=int(np.asarray(fwd.sport)[i]), rx_if=pod)
            for i in range(20)
        ], n=32)
        t = dp.tables
        solo_hit, solo_idx = session_lookup_reverse_idx(
            t, reply, jnp.int32(2), tnt=True)
        solo_hit = np.asarray(solo_hit)
        assert solo_hit.sum() >= 16  # the differential has signal
        shards = 2
        mesh = Mesh(np.array(jax.devices("cpu")[:shards]), (RULE_AXIS,))
        ctx = ShardCtx(RULE_AXIS, shards)
        # the session bucket grids shard along the bucket axis; every
        # other field — the tenant planes included — replicates, the
        # PARTITION_RULES placement
        grid = {"sess_valid", "sess_src", "sess_dst", "sess_ports",
                "sess_proto", "sess_time"}
        nb = t.sess_valid.shape[0]
        assert nb % shards == 0
        tbl_specs = type(t)(**{
            f: (P(RULE_AXIS) if f in grid else P())
            for f in t._fields})

        def kernel(tbl, pv):
            return session_lookup_reverse_idx(
                tbl, pv, jnp.int32(2), shard=ctx, tnt=True)

        with mesh:
            sharded = jax.shard_map(
                kernel, mesh=mesh,
                in_specs=(tbl_specs, P()),
                out_specs=(P(), P()),
            )
            mesh_hit, mesh_idx = sharded(t, reply)
        mesh_hit = np.asarray(mesh_hit)
        assert np.array_equal(mesh_hit, solo_hit)
        # matched slots agree wherever found: the mesh returns the
        # GLOBAL flat slot (shard-local recombined), identical to the
        # standalone index
        assert np.array_equal(np.asarray(mesh_idx)[solo_hit],
                              np.asarray(solo_idx)[solo_hit])


# --------------------------------------------------------------------
# host-side scheduling units
# --------------------------------------------------------------------


class TestShardRefusal:
    def test_mesh_refuses_tenancy_on(self):
        """The cluster step does not compile the tenant stage (yet):
        an enforcement feature must refuse loudly on the mesh, never
        silently skip quotas (the explicit-bv-refusal convention)."""
        from vpp_tpu.parallel.cluster import ClusterDataplane
        from vpp_tpu.parallel.mesh import cluster_mesh
        from vpp_tpu.parallel.multihost import MultiHostCluster

        cfg = DataplaneConfig(
            max_tables=2, max_rules=16, max_global_rules=32,
            max_ifaces=8, fib_slots=16, sess_slots=256, nat_mappings=2,
            nat_backends=2, tenancy="on", sess_sweep_stride=0)
        with pytest.raises(ValueError, match="tenancy"):
            ClusterDataplane(cluster_mesh(1, 1), cfg)
        with pytest.raises(ValueError, match="tenancy"):
            MultiHostCluster(1, cfg)


class TestScheduler:
    def test_wfq_proportional_service(self):
        s = TenantScheduler({1: 3, 2: 1})
        for i in range(12):
            s.push(1, 100 + i, 4)
            s.push(2, 200 + i, 4)
        served = {1: 0, 2: 0}
        for _ in range(16):
            t = s.pick()
            s.pop(t, 4)
            served[t] += 1
        # weight 3:1 -> tenant 1 gets ~3x the service
        assert served[1] == 12 and served[2] == 4

    def test_idle_rebase_prevents_banked_burst(self):
        s = TenantScheduler({1: 1, 2: 1})
        for i in range(8):
            s.push(1, i, 4)
        for _ in range(8):
            s.pop(s.pick(), 4)  # tenant 1 accrues vtime 32
        s.push(1, 100, 4)
        s.push(2, 200, 4)  # tenant 2 returns from idle
        # without the rebase tenant 2 would monopolize until its
        # vtime catches up from 0; WITH it, service alternates
        order = []
        for _ in range(2):
            t = s.pick()
            s.pop(t, 4)
            order.append(t)
        assert set(order) == {1, 2}

    def test_shed_pick_names_the_hog(self):
        s = TenantScheduler({1: 1, 2: 4})
        for i in range(4):
            s.push(1, i, 16)       # backlog 64 / weight 1 = 64
        for i in range(8):
            s.push(2, 100 + i, 16)  # backlog 128 / weight 4 = 32
        assert s.shed_pick() == 1  # most backlog PER UNIT WEIGHT
        s.pop(1, 1 << 30)
        assert s.shed_pick() == 2

    def test_requeue_front_restores_order_and_vtime(self):
        s = TenantScheduler({1: 1})
        for i in range(3):
            s.push(1, i, 4)
        frames = s.pop(1, 8)  # rids 0, 1
        assert [r for r, _ in frames] == [0, 1]
        v_after = s._vtime[1]
        assert v_after == 8.0
        s.requeue_front(1, frames)
        assert s._vtime[1] == 0.0
        assert [r for r, _ in s.pop(1, 1 << 30)] == [0, 1, 2]

    def test_pop_takes_at_least_one_frame(self):
        s = TenantScheduler()
        s.push(5, 0, 64)
        assert [r for r, _ in s.pop(5, 4)] == [0]  # oversize but first


class TestClassifier:
    def test_prefix_vni_and_frame(self):
        cls = TenantClassifier(tenant_entries_from_config([
            {"id": 1, "prefixes": [T1_NET], "weight": 3, "vni": 700},
            {"id": 2, "prefixes": [T2_NET]},
        ]))
        src = np.asarray([int(ip4("10.50.1.1")), int(ip4("1.1.1.1")),
                          int(ip4("1.1.1.1"))], np.uint32)
        dst = np.asarray([int(ip4("2.2.2.2")), int(ip4("10.60.0.9")),
                          int(ip4("3.3.3.3"))], np.uint32)
        assert cls.packet_tenants(src, dst).tolist() == [1, 2, 0]
        assert cls.tenant_of_vni(700) == 1
        assert cls.tenant_of_vni(999) == 0
        assert cls.weight(1) == 3 and cls.weight(2) == 1


# --------------------------------------------------------------------
# validation refusals
# --------------------------------------------------------------------


class TestValidation:
    def _cfg(self, **over):
        return DataplaneConfig(
            max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=4,
            fib_slots=16, sess_slots=256, nat_mappings=2,
            nat_backends=2, tenancy="on", **over)

    @pytest.mark.parametrize("entries,frag", [
        ([{"id": 1}, {"id": 1}], "duplicate"),
        ([{"id": 99}], "outside"),
        ([{"id": 1, "prefixes": ["not-a-net"]}], ""),
        ([{"id": 1, "prefixes": ["fd00::/8"]}], "IPv4"),
        ([{"id": 1, "rate": (1 << 16) + 1}], "rate"),
        ([{"id": 1, "rate": 5}], "burst"),
        ([{"id": 1, "sess_buckets": 3}], "power of two"),
        ([{"id": 1, "sess_buckets": 128}], "exceeds"),
        ([{"id": 1, "sess_buckets": 32}, {"id": 2, "sess_buckets": 64}],
         "oversubscribed"),
        # cross-tenant overlapping prefixes: device first-match vs
        # host max would bill the same packet to different tenants
        ([{"id": 1, "prefixes": ["10.0.0.0/8"]},
          {"id": 2, "prefixes": ["10.60.0.0/16"]}], "overlap"),
        # slices fill the whole table while the implicit default
        # tenant 0 (unsliced) still needs residual bucket range
        ([{"id": 1, "sess_buckets": 64}], "residual"),
        ([{"id": 1, "weight": 0}], "weight"),
        ([{"id": 1, "ml_mode": "bogus"}], "ml_mode"),
        ([{"id": 1, "nonsense_key": 1}], "unknown"),
        ([{"name": "anonymous"}], "missing"),
    ])
    def test_refusals(self, entries, frag):
        with pytest.raises(ValueError) as ei:
            validate_tenancy_config(self._cfg(), entries)
        assert frag.lower() in str(ei.value).lower()

    def test_full_slicing_allowed_when_tenant0_sliced(self):
        """Slicing the WHOLE table is legal iff no unsliced tenant
        remains — i.e. the default tenant 0 registered its own
        slice."""
        entries = validate_tenancy_config(self._cfg(), [
            {"id": 0, "sess_buckets": 32},
            {"id": 1, "prefixes": [T1_NET], "sess_buckets": 32},
        ])
        assert len(entries) == 2

    def test_prefix_map_overflow_refused_at_load(self):
        """A prefix list larger than the device map fails AT CONFIG
        VALIDATION (load / set_tenant pre-staging), not as a
        _restage_tenants crash after the registry mutated."""
        with pytest.raises(ValueError, match="slots"):
            validate_tenancy_config(
                self._cfg(tenancy_prefixes=2),
                [{"id": 1, "prefixes": ["10.50.0.0/16", "10.51.0.0/16",
                                        "10.52.0.0/16"]}])

    def test_set_tenant_requires_knob(self):
        dp = Dataplane(DataplaneConfig(
            max_tables=2, max_rules=8, max_global_rules=8,
            max_ifaces=4, fib_slots=16, sess_slots=256, nat_mappings=2,
            nat_backends=2))
        with pytest.raises(ValueError, match="tenancy"):
            dp.builder.set_tenant(1, prefixes=[T1_NET])

    def test_agent_config_refuses_tenants_with_knob_off(self):
        from vpp_tpu.cmd.config import AgentConfig

        with pytest.raises(ValueError, match="tenancy"):
            AgentConfig.from_dict({
                "node_name": "n1",
                "tenants": [{"id": 1, "prefixes": [T1_NET]}],
            })
        # and loads cleanly with it on
        cfg = AgentConfig.from_dict({
            "node_name": "n1",
            "dataplane": {"tenancy": "on"},
            "tenants": [{"id": 1, "prefixes": [T1_NET], "weight": 2}],
        })
        assert cfg.tenants[0]["weight"] == 2

    def test_tenant_quantum_knob_validated_and_applied(self):
        from vpp_tpu.cmd.config import AgentConfig

        with pytest.raises(ValueError, match="io_tenant_quantum"):
            AgentConfig.from_dict({
                "node_name": "n1",
                "io": {"io_tenant_quantum": -1},
            })
        # the pump caps a WFQ take at the quantum (the tenant
        # latency/throughput dial)
        dp, a, _b = _pump_dp()
        cls = TenantClassifier(tenant_entries_from_config(
            [{"id": 1, "prefixes": [T1_NET]}]))
        rings = IORingPair(n_slots=16)
        pump = DataplanePump(dp, rings, mode="dispatch",
                             tenants=cls, tenant_quantum=8)
        try:
            assert pump.tenant_quantum == 8
            with pump._held_lock:
                for rid in range(3):
                    pump._tnt_sched.push(1, rid, 4)
            # a take pops at most the quantum (2 x 4-pkt frames)
            with pump._held_lock:
                frames = pump._tnt_sched.pop(1, min(
                    pump.max_batch, pump.tenant_quantum))
            assert [r for r, _ in frames] == [0, 1]
        finally:
            rings.close()

    def test_oversubscription_refused_before_staging_mutates(self):
        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "sess_buckets": 32},
        ])
        before = dict(dp.builder.tnt)
        with pytest.raises(ValueError, match="oversubscribed"):
            dp.builder.set_tenant(2, prefixes=[T2_NET],
                                  sess_buckets=64)
        for k, v in dp.builder.tnt.items():
            assert np.array_equal(v, before[k]), k
        assert 2 not in dp.builder.tenants


# --------------------------------------------------------------------
# pump integration: WFQ no-starvation + device quota drops
# --------------------------------------------------------------------


def _pump_dp():
    dp = Dataplane(DataplaneConfig(sess_slots=256, sess_sweep_stride=0))
    a = dp.add_pod_interface(("default", "a"))
    b = dp.add_pod_interface(("default", "b"))
    dp.builder.add_route("10.50.0.0/16", b, Disposition.LOCAL)
    dp.builder.add_route("10.60.0.0/16", b, Disposition.LOCAL)
    dp.builder.add_route("10.1.1.0/24", a, Disposition.LOCAL)
    dp.swap()
    return dp, a, b


def _push_tenant_frames(rings, codec, scratch, rx_if, src, n_frames,
                        per, tag0, seq0, seqs):
    pushed = 0
    for k in range(n_frames):
        frames = [make_frame(src, "10.1.1.2", proto=17,
                             sport=tag0 + k * 64 + j, dport=3000)
                  for j in range(per)]
        cols, n = codec.parse(frames, rx_if, scratch)
        cols["meta"][:n] = seq0 + k
        assert rings.rx.push(cols, n, payload=scratch)
        seqs.append(seq0 + k)
        pushed += n
    return pushed


class TestPumpWfq:
    def test_heavy_tenant_cannot_starve_light_tenant(self):
        """A saturating tenant-1 backlog sits FIRST in ring order;
        tenant 2 (weight 4) is queued behind all 48 of its frames —
        both pushed BEFORE the pump starts, so the scenario carries no
        wall-clock race at all (a sleep-based "arrives later" phase
        stretches unboundedly on a loaded single-core box; arrival-
        order fairness in TIME is TestScheduler's idle-rebase unit).
        FIFO would serve the whole tenant-1 backlog before tenant 2;
        weighted-fair dequeue must interleave tenant 2 within a few
        quanta. (Egress stays ring-ordered by the tx writer's
        done-prefix, so the observable fairness signal is service
        ORDER: the pump's monotone per-tenant ``last_admit_seq``,
        read after the full drain — poll-free.) Conservation exact,
        lane accounting populated."""
        dp, a, _b = _pump_dp()
        cls = TenantClassifier(tenant_entries_from_config([
            {"id": 1, "prefixes": [T1_NET], "weight": 1},
            {"id": 2, "prefixes": [T2_NET], "weight": 4},
        ]))
        rings = IORingPair(n_slots=128)
        pump = DataplanePump(dp, rings, mode="dispatch",
                             max_batch=VEC, max_inflight=1,
                             fetch_delay=0.06, tenants=cls)
        codec = PacketCodec()
        scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
        t1_seqs, t2_seqs = [], []
        offered = _push_tenant_frames(
            rings, codec, scratch, a, "10.50.1.1", 48, 16, 10000, 0,
            t1_seqs)
        offered += _push_tenant_frames(
            rings, codec, scratch, a, "10.60.1.1", 4, 4, 20000,
            100, t2_seqs)
        pump.start()
        try:
            drained = 0
            deadline = time.monotonic() + 180.0
            while drained < 52 and time.monotonic() < deadline:
                g = rings.tx.peek()
                if g is None:
                    time.sleep(0.005)
                    continue
                drained += 1
                rings.tx.release()
            assert drained == 52, "tx drain timed out"
            assert pump.stop(join_timeout=60.0)
            s = pump.stats
            assert s["pkts"] == offered
            tio = pump.tenant_io_snapshot()
            # service-order proof off the monotone admission sequence:
            # frames OTHER tenants were admitted before tenant 2
            # finished = tenant 2's last_admit_seq minus its own 4
            # frames. WFQ (weight 4 vs 1) serves tenant 2 within the
            # first few quanta even though all 48 tenant-1 frames sit
            # ahead of it in ring order; FIFO would put every one of
            # them first (seq 52). >10 frames still queued at tenant
            # 2's completion <=> at most 37 went before it.
            t1_before_t2_done = tio["io"][2]["last_admit_seq"] - 4
            assert t1_before_t2_done <= 37, (
                "light tenant waited out the heavy backlog (FIFO?): "
                f"{t1_before_t2_done} tenant-1 frames admitted before "
                "tenant 2 finished")
            assert tio["io"][1]["last_admit_seq"] \
                > tio["io"][2]["last_admit_seq"]
            assert tio["io"][1]["pkts"] == 48 * 16
            assert tio["io"][2]["pkts"] == 16
            assert tio["io"][1]["shed_pkts"] == 0  # no governor
            assert tio["weights"] == {1: 1, 2: 4}
        finally:
            pump.stop(join_timeout=30.0)
            rings.close()

    def test_priority_express_not_gated_by_tenant_scan_stall(self):
        """Tenants AND a PriorityFilter together: the scan frontier's
        tenant-lane stall (taken+done >= hold_cap) must NOT delay
        reflex classification — a priority frame behind a saturating
        bulk backlog takes service within a few WFQ quanta (the
        ISSUE 13 bounded-queueing contract), observable poll-free via
        the priority_admit_bulk_seq order signal."""
        from vpp_tpu.io.governor import PriorityFilter

        dp, a, _b = _pump_dp()
        cls = TenantClassifier(tenant_entries_from_config([
            {"id": 1, "prefixes": [T1_NET], "weight": 1},
        ]))
        rings = IORingPair(n_slots=16)  # hold_cap 12 < the backlog
        pump = DataplanePump(dp, rings, mode="dispatch",
                             max_batch=VEC, max_inflight=1,
                             fetch_delay=0.05, tenants=cls,
                             tenant_quantum=4,
                             priority=PriorityFilter(ports=(9999,)))
        codec = PacketCodec()
        scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
        offered = 0
        seqs = []
        offered += _push_tenant_frames(
            rings, codec, scratch, a, "10.50.1.1", 14, 4, 10000, 0,
            seqs)
        # the reflex frame sits BEHIND the whole bulk backlog
        frames = [make_frame("10.50.9.9", "10.1.1.2", proto=17,
                             sport=5, dport=9999)]
        cols, n = codec.parse(frames, a, scratch)
        cols["meta"][:n] = 999
        assert rings.rx.push(cols, n, payload=scratch)
        offered += n
        pump.start()
        try:
            drained = 0
            deadline = time.monotonic() + 120.0
            while drained < 15 and time.monotonic() < deadline:
                g = rings.tx.peek()
                if g is None:
                    time.sleep(0.005)
                    continue
                drained += 1
                rings.tx.release()
            assert drained == 15, "tx drain timed out"
            assert pump.stop(join_timeout=30.0)
            s = pump.stats
            assert s["pkts"] == offered
            assert s["priority_frames"] == 1
            # the frontier never stalls on bulk occupancy with a
            # priority filter attached: the reflex frame classifies on
            # the FIRST scan pass and the express take outranks every
            # bulk lane, so it observes 0 bulk admissions (measured;
            # the reverted stall reads 5 — classification waits out
            # hold_cap releases)
            assert s["priority_admit_bulk_seq"] <= 2, \
                s["priority_admit_bulk_seq"]
        finally:
            pump.stop(join_timeout=30.0)
            rings.close()

    @pytest.mark.slow  # ~10 s: pump + WFQ bring-up; quota-drop conservation stays fast in TestQuotaDrops
    def test_device_quota_drops_surface_in_pump_stats(self):
        """Dispatch pump over a tenancy-on dataplane with a
        rate-limited tenant: the aux rider's DROP_TENANT count lands
        in stats['drops_tenant_quota'] and agrees EXACTLY with the
        device per-tenant plane."""
        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 1, "burst": 8},
        ])
        cls = TenantClassifier(tenant_entries_from_config([
            {"id": 1, "prefixes": [T1_NET]},
        ]))
        rings = IORingPair(n_slots=64)
        pump = DataplanePump(dp, rings, mode="dispatch",
                             max_batch=VEC, tenants=cls)
        codec = PacketCodec()
        scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
        offered = 0
        for k in range(4):
            frames = [make_frame("10.50.2.3", "10.1.1.2", proto=17,
                                 sport=40000 + k * 64 + j, dport=53)
                      for j in range(16)]
            cols, n = codec.parse(frames, up, scratch)
            assert rings.rx.push(cols, n, payload=scratch)
            offered += n
        pump.start()
        try:
            deadline = time.monotonic() + 120.0
            while pump.stats["pkts"] < offered \
                    and time.monotonic() < deadline:
                while rings.tx.peek() is not None:
                    rings.tx.release()
                time.sleep(0.01)
            while rings.tx.peek() is not None:
                rings.tx.release()
            assert pump.stop(join_timeout=60.0)
            s = pump.stats
            assert s["pkts"] == offered
            assert s["drops_tenant_quota"] > 0  # 64 pkts vs burst 8
            snap = dp.tenant_snapshot()
            assert int(snap["rl_drops"][1]) == s["drops_tenant_quota"]
            assert int(snap["rx"][1]) == offered
            assert int(snap["tx"][1]) + int(snap["rl_drops"][1]) \
                == offered
        finally:
            pump.stop(join_timeout=30.0)
            rings.close()


# --------------------------------------------------------------------
# wiring: step-form contract, CLI, collector
# --------------------------------------------------------------------


class TestStepFormContract:
    @pytest.mark.jit_budget(4)
    def test_one_new_form_and_zero_io_callbacks(self):
        """The ISSUE 14 acceptance pair: tenancy adds exactly ONE
        step-form dimension value (the `_tenancy` label suffix on the
        same process-wide cache) and the persistent ring path stays
        io_callback-free with the stage compiled in."""
        from vpp_tpu.pipeline.dataplane import _JIT_STEPS, _step_label

        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 2, "burst": 4},
        ])
        before = set(_JIT_STEPS)
        dp.process(tenant_traffic(up, [(T1_NET, 4)], n=16, seed=20),
                   now=1)
        new = set(_JIT_STEPS) - before
        assert all(k[-1] == "on" for k in new), \
            f"non-tenancy variants appeared: {new}"
        assert "_tenancy" in _step_label(
            "dense", False, False, "plain", 0, tnt_mode="on")
        # ring path: the window program with tenancy on makes ZERO
        # host callbacks
        from vpp_tpu.pipeline.persistent import PersistentPump

        pp = PersistentPump(dp.tables, batch=VEC, fastpath=False,
                            tnt_mode="on").start()
        try:
            pv = tenant_traffic(up, [(T1_NET, 8)], n=VEC, seed=21)
            cols = {f: np.asarray(getattr(pv, f))
                    for f in ("src_ip", "dst_ip", "proto", "sport",
                              "dport", "ttl", "pkt_len", "rx_if",
                              "flags")}
            from vpp_tpu.pipeline.dataplane import (
                pack_packet_columns,
                packed_input_zeros,
            )

            flat = packed_input_zeros(VEC)
            pack_packet_columns(flat.view(np.uint32), cols, VEC)
            pp.submit(flat, now=2)
            out, aux = pp.result_ex(timeout=60.0)
            assert out is not None
            assert pp.stats_snapshot()["io_callbacks"] == 0
            # the tenancy aux rows rode the ring fetch
            from vpp_tpu.pipeline.dataplane import PACKED_AUX_SCHEMA

            rl_row = PACKED_AUX_SCHEMA.index("tnt_limited")
            assert np.asarray(aux)[rl_row] >= 0
        finally:
            pp.stop()

    def test_packed_aux_carries_tenancy_rows(self):
        from vpp_tpu.pipeline.dataplane import (
            PACKED_AUX_ROWS,
            PACKED_AUX_SCHEMA,
            pack_packet_columns,
            packed_input_zeros,
        )

        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 1, "burst": 2},
        ])
        pv = tenant_traffic(up, [(T1_NET, 8)], n=16, seed=22)
        flat = packed_input_zeros(16)
        cols = {f: np.asarray(getattr(pv, f))
                for f in ("src_ip", "dst_ip", "proto", "sport",
                          "dport", "ttl", "pkt_len", "rx_if", "flags")}
        pack_packet_columns(flat.view(np.uint32), cols, 16)
        _out, aux = dp.process_packed(flat, now=3, with_aux=True)
        aux_h = np.asarray(aux)
        assert aux_h.shape == (PACKED_AUX_ROWS,) \
            == (len(PACKED_AUX_SCHEMA),)
        assert aux_h[PACKED_AUX_SCHEMA.index("tnt_limited")] == 6
        assert aux_h[PACKED_AUX_SCHEMA.index("tnt_qfail")] == 0


class TestObservability:
    def test_show_tenants_and_collector_families(self):
        from vpp_tpu.cli import DebugCLI
        from vpp_tpu.stats.collector import StatsCollector

        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "name": "gold", "prefixes": [T1_NET], "rate": 2,
             "burst": 4, "sess_buckets": 4, "weight": 3},
        ])
        res = dp.process(
            tenant_traffic(up, [(T1_NET, 8)], n=16, seed=23), now=100)
        cli = DebugCLI(dp)
        out = cli.run("show tenants")
        assert "tenant 1 (gold)" in out
        assert "rate 2/tick" in out
        assert "rl-drops 4" in out
        # the default tenant renders even with a non-empty registry:
        # unmatched traffic lands there and must stay observable
        assert "tenant 0" in out
        coll = StatsCollector(dp)
        coll.update(res.stats)  # the pump's per-frame ingestion path
        coll.publish()
        text = "\n".join(line for _p, fam in coll.registry.families()
                         for line in fam.render())
        assert 'vpp_tpu_tenant_goodput_packets{tenant="1"} 4' in text
        assert 'vpp_tpu_tenant_rl_dropped_packets{tenant="1"} 4' in text
        assert 'vpp_tpu_tenant_weight{tenant="1"} 3' in text
        assert 'vpp_tpu_tenant_rx_packets{tenant="0"}' in text
        assert "vpp_tpu_node_tenant_limited_packets 4" in text

    def test_trace_renders_tenant_quota_drop(self):
        """PacketTracer attributes DROP_TENANT to its own error-drop
        leaf right after ip4-input (the token bucket runs BEFORE
        session/ML/NAT/ACL) — never a fabricated forwarding path."""
        from vpp_tpu.trace.tracer import PacketTracer

        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 1, "burst": 2},
        ])
        tracer = PacketTracer()
        dp.tracer = tracer
        tracer.add(8)
        res = dp.process(
            tenant_traffic(up, [(T1_NET, 6)], n=8, seed=30), now=100)
        assert int(res.stats.tnt_limited) == 4  # burst 2 admits 2
        entries = tracer.entries()
        dropped = [e for e in entries
                   if e.drop_cause == "tenant-quota"]
        passed = [e for e in entries if e.drop_cause == "none"]
        assert len(dropped) == 4 and passed
        for e in dropped:
            assert e.path == ("ip4-input", "tenant-limit",
                              "error-drop (tenant-quota)")
        for e in passed:
            assert "error-drop (tenant-quota)" not in e.path

    def test_deleted_tenant_labelsets_removed(self):
        """A cleared tenant's per-tenant series must disappear from
        the next publish (the vpp_tpu_build_info stale-labelset
        discipline) — not export frozen ghost values forever."""
        from vpp_tpu.stats.collector import StatsCollector

        dp, up, _pod = build_dp(tenants=[
            {"id": 1, "prefixes": [T1_NET], "rate": 2, "burst": 4},
        ])
        dp.process(tenant_traffic(up, [(T1_NET, 8)], n=16, seed=24),
                   now=100)
        coll = StatsCollector(dp)
        coll.publish()

        def render():
            return "\n".join(line
                             for _p, fam in coll.registry.families()
                             for line in fam.render())

        assert 'vpp_tpu_tenant_rx_packets{tenant="1"}' in render()
        dp.builder.clear_tenants()
        dp.swap()
        coll.publish()
        text = render()
        assert 'vpp_tpu_tenant_rx_packets{tenant="1"}' not in text
        assert 'vpp_tpu_tenant_rx_packets{tenant="0"}' in text

    def test_show_tenants_off_dataplane(self):
        from vpp_tpu.cli import DebugCLI

        dp = Dataplane(DataplaneConfig(
            max_tables=2, max_rules=8, max_global_rules=8,
            max_ifaces=4, fib_slots=16, sess_slots=256, nat_mappings=2,
            nat_backends=2))
        assert "tenancy: off" in DebugCLI(dp).run("show tenants")
