"""Differential suite for the million-route LPM FIB (ISSUE 15).

Pins ops/lpm.py (per-length binary-search planes), the shared ECMP
resolver (ops/fib.py), the per-length incremental churn path
(pipeline/tables.py) and the fib_impl selection ladder against an
INDEPENDENT NumPy per-packet oracle — reimplemented here from the spec
(longest match, lowest slot on ties, the session hash family for the
member pick), never by calling the device kernels.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from vpp_tpu.ops.fib import fib_lookup_dense, ip4_lookup
from vpp_tpu.ops.lpm import LPM_PAD, fib_lookup_lpm, lpm_field
from vpp_tpu.pipeline.dataplane import Dataplane
from vpp_tpu.pipeline.tables import DataplaneConfig, TableBuilder
from vpp_tpu.pipeline.vector import (
    FLAG_VALID,
    Disposition,
    PacketVector,
    ip4,
)

M32 = (1 << 32) - 1


def _mask_of(plen: int) -> int:
    return (M32 ^ ((1 << (32 - plen)) - 1)) if plen else 0


def np_flow_mix(src, dst, sport, dport, proto):
    """Independent reimplementation of the session 5-tuple hash family
    (the ECMP member-pick contract, docs/ROUTING.md) — uint32 wrap
    semantics spelled out by hand."""
    src = np.asarray(src, np.uint64)
    dst = np.asarray(dst, np.uint64)
    ports = ((np.asarray(sport, np.uint64) << 16)
             | (np.asarray(dport, np.uint64) & 0xFFFF)) & M32
    proto = np.asarray(proto, np.uint64)
    h = (src * 0x9E3779B1) & M32
    h ^= (dst * 0x85EBCA77) & M32
    h ^= (ports * 0xC2B2AE3D) & M32
    h ^= (proto * 0x27D4EB2F) & M32
    h ^= h >> 15
    h = (h * 0x2545F491) & M32
    h ^= h >> 13
    return h.astype(np.uint32)


class NumpyLpmOracle:
    """Per-packet longest-prefix-match + ECMP resolve over a staged
    TableBuilder, straight from the route arrays."""

    def __init__(self, b: TableBuilder):
        self.plen = np.asarray(b.fib_plen).copy()
        self.pfx = np.asarray(b.fib_prefix).copy()
        self.mask = np.asarray(b.fib_mask).copy()
        self.tx_if = np.asarray(b.fib_tx_if).copy()
        self.disp = np.asarray(b.fib_disp).copy()
        self.nh = np.asarray(b.fib_next_hop).copy()
        self.node = np.asarray(b.fib_node_id).copy()
        self.snat = np.asarray(b.fib_snat).copy()
        self.grp = np.asarray(b.fib_grp).copy()
        self.grp_nh = np.asarray(b.fib_grp_nh).copy()
        self.grp_tx = np.asarray(b.fib_grp_tx_if).copy()
        self.grp_node = np.asarray(b.fib_grp_node).copy()
        self.grp_n = np.asarray(b.fib_grp_n).copy()

    def lookup_one(self, src, dst, sport, dport, proto):
        best_slot, best_len = -1, -1
        for s in range(len(self.plen)):
            L = int(self.plen[s])
            if L < 0:
                continue
            if (dst & _mask_of(L)) == int(self.pfx[s]) and L > best_len:
                best_slot, best_len = s, L
        if best_slot < 0:
            return dict(matched=False, tx_if=-1,
                        disp=int(Disposition.DROP), next_hop=0,
                        node_id=-1, snat=False, grp=-1, way=0)
        s = best_slot
        g = int(self.grp[s])
        ways = self.grp_nh.shape[1]
        if g >= 0:
            if int(self.grp_n[g]) == 0:
                # empty group fails closed as a no-route miss
                return dict(matched=False, tx_if=-1,
                            disp=int(Disposition.DROP), next_hop=0,
                            node_id=-1, snat=False, grp=-1, way=0)
            w = int(np_flow_mix(src, dst, sport, dport, proto)) \
                & (ways - 1)
            return dict(matched=True, tx_if=int(self.grp_tx[g, w]),
                        disp=int(self.disp[s]),
                        next_hop=int(self.grp_nh[g, w]),
                        node_id=int(self.grp_node[g, w]),
                        snat=bool(self.snat[s]), grp=g, way=w)
        return dict(matched=True, tx_if=int(self.tx_if[s]),
                    disp=int(self.disp[s]), next_hop=int(self.nh[s]),
                    node_id=int(self.node[s]), snat=bool(self.snat[s]),
                    grp=-1, way=0)

    def lookup(self, pkts: PacketVector):
        src = np.asarray(pkts.src_ip)
        dst = np.asarray(pkts.dst_ip)
        sp = np.asarray(pkts.sport)
        dp_ = np.asarray(pkts.dport)
        pr = np.asarray(pkts.proto)
        rows = [self.lookup_one(int(src[i]), int(dst[i]), int(sp[i]),
                                int(dp_[i]), int(pr[i]))
                for i in range(len(dst))]
        return {k: np.asarray([r[k] for r in rows]) for k in rows[0]}


def assert_fib_equal(res, oracle_out):
    np.testing.assert_array_equal(np.asarray(res.matched),
                                  oracle_out["matched"])
    np.testing.assert_array_equal(np.asarray(res.tx_if),
                                  oracle_out["tx_if"])
    np.testing.assert_array_equal(np.asarray(res.disp),
                                  oracle_out["disp"])
    np.testing.assert_array_equal(
        np.asarray(res.next_hop).astype(np.int64),
        oracle_out["next_hop"].astype(np.int64))
    np.testing.assert_array_equal(np.asarray(res.node_id),
                                  oracle_out["node_id"])
    np.testing.assert_array_equal(np.asarray(res.snat),
                                  oracle_out["snat"])
    np.testing.assert_array_equal(np.asarray(res.grp),
                                  oracle_out["grp"])


# every prefix length this suite stages (restricting the populated-
# length tuple keeps the compiled LPM kernels at ~14 unrolled lengths
# instead of 33 — pure tier-1 compile-time budget, zero semantics)
_TEST_PLENS = (0, 8, 10, 12, 16, 18, 20, 22, 23, 24, 28, 30, 31, 32)


def _cfg(fib_slots=256, **kw):
    kw.setdefault("fib_lpm_plen_caps",
                  tuple(fib_slots if L in _TEST_PLENS else 0
                        for L in range(33)))
    # the two-tier dispatcher doubles every compiled program and this
    # suite never exercises session-hit traffic — plain chain only
    # (budget; the fastpath x LPM interplay rides the shared fib_fn,
    # already pinned by the step factory's composition)
    kw.setdefault("fastpath", False)
    return DataplaneConfig(
        max_tables=2, max_rules=8, max_global_rules=8, max_ifaces=16,
        fib_slots=fib_slots, sess_slots=64, nat_mappings=2,
        nat_backends=4, **kw)


# weighted length mix shaped like a BGP feed tail
_LENGTHS = [0, 8, 10, 12, 16, 18, 20, 22, 23, 24, 28, 30, 32]
_WEIGHTS = [1, 1, 1, 2, 4, 3, 4, 6, 5, 20, 2, 1, 4]


def _random_table(seed: int, n_routes: int, fib_slots: int,
                  ecmp_groups: int = 0) -> TableBuilder:
    rng = np.random.default_rng(seed)
    b = TableBuilder(_cfg(fib_slots=fib_slots, fib_impl="lpm",
                          fib_ecmp_groups=ecmp_groups,
                          fib_ecmp_ways=4))
    if ecmp_groups:
        for g in range(ecmp_groups):
            members = [(int(rng.integers(1, M32)),
                        int(rng.integers(0, 8)),
                        int(rng.integers(-1, 3)))
                       for _ in range(int(rng.integers(1, 5)))]
            b.set_nh_group(g, members)
    p = np.asarray(_WEIGHTS, float) / sum(_WEIGHTS)
    for i in range(n_routes):
        L = int(rng.choice(_LENGTHS, p=p))
        addr = int(rng.integers(0, 1 << 32)) & _mask_of(L)
        disp = int(rng.choice([int(Disposition.LOCAL),
                               int(Disposition.REMOTE),
                               int(Disposition.HOST),
                               int(Disposition.DROP)],
                              p=[0.4, 0.4, 0.1, 0.1]))
        group = (int(rng.integers(0, ecmp_groups))
                 if ecmp_groups and rng.random() < 0.25 else None)
        b.add_route(f"{addr >> 24 & 255}.{addr >> 16 & 255}."
                    f"{addr >> 8 & 255}.{addr & 255}/{L}",
                    tx_if=int(rng.integers(0, 8)),
                    disposition=Disposition(disp),
                    next_hop=int(rng.integers(0, 1 << 32)),
                    node_id=int(rng.integers(-1, 4)),
                    snat=bool(rng.random() < 0.2),
                    slot=i, group=group)
    return b


def _probe_traffic(b: TableBuilder, rng, n_pkts: int) -> PacketVector:
    """Half the packets aim INSIDE staged prefixes (guaranteed hits,
    overlapping covers exercised), half are uniform random."""
    live = np.nonzero(np.asarray(b.fib_plen) >= 0)[0]
    dst = rng.integers(0, 1 << 32, n_pkts).astype(np.uint32)
    take = rng.random(n_pkts) < 0.5
    picks = rng.choice(live, n_pkts)
    inside = (np.asarray(b.fib_prefix)[picks]
              | (dst & ~np.asarray(b.fib_mask)[picks])).astype(np.uint32)
    dst = np.where(take, inside, dst)
    return PacketVector(
        src_ip=jnp.asarray(rng.integers(0, 1 << 32, n_pkts)
                           .astype(np.uint32)),
        dst_ip=jnp.asarray(dst),
        proto=jnp.asarray(rng.choice([1, 6, 17], n_pkts)
                          .astype(np.int32)),
        sport=jnp.asarray(rng.integers(0, 65536, n_pkts)
                          .astype(np.int32)),
        dport=jnp.asarray(rng.integers(0, 65536, n_pkts)
                          .astype(np.int32)),
        ttl=jnp.full((n_pkts,), 64, jnp.int32),
        pkt_len=jnp.full((n_pkts,), 256, jnp.int32),
        rx_if=jnp.zeros((n_pkts,), jnp.int32),
        flags=jnp.full((n_pkts,), FLAG_VALID, jnp.int32),
    )


@pytest.mark.parametrize("seed,n_routes,fib_slots",
                         [(3, 40, 64), (7, 200, 256), (11, 900, 1024)])
def test_lpm_matches_oracle_and_dense(seed, n_routes, fib_slots):
    """Seeded random tables at multiple scales: the LPM lookup, the
    dense lookup and the NumPy oracle agree bit-exactly on every
    FibResult field (ECMP member picks included)."""
    b = _random_table(seed, n_routes, fib_slots, ecmp_groups=4)
    t = b.to_device()
    rng = np.random.default_rng(seed + 1)
    pkts = _probe_traffic(b, rng, 512)
    oracle = NumpyLpmOracle(b).lookup(pkts)
    assert_fib_equal(fib_lookup_lpm(t, pkts), oracle)
    assert_fib_equal(fib_lookup_dense(t, pkts), oracle)


def test_full_hint_bucket_finds_every_prefix(monkeypatch):
    """A stride-hint bucket holding as many prefixes as it can (every
    /24 under one top-6-bit prefix): the bounded bisection still lands
    on each one. One step short, the last candidate of a full bucket
    fell through to the default route."""
    monkeypatch.setenv("VPPT_LPM_HINT_MIN", "64")
    b = TableBuilder(_cfg(fib_slots=128, fib_impl="lpm",
                          fib_lpm_plen_caps=(1,) + (0,) * 23 + (64,)))
    b.add_route("0.0.0.0/0", 1, Disposition.REMOTE, node_id=-1, slot=0)
    for i in range(64):
        b.add_route(f"10.0.{i}.0/24", 2, Disposition.REMOTE,
                    node_id=i, slot=1 + i)
    t = b.to_device()
    assert t.fib_lpm_hint.shape[0] > 0  # the hint layer is engaged
    n = 64 * 4
    dst = np.array([(10 << 24) | (i // 4 << 8) | (i % 4 * 60 + 1)
                    for i in range(n)], np.uint32)
    pkts = PacketVector(
        src_ip=jnp.zeros((n,), jnp.uint32), dst_ip=jnp.asarray(dst),
        proto=jnp.full((n,), 6, jnp.int32),
        sport=jnp.zeros((n,), jnp.int32), dport=jnp.zeros((n,), jnp.int32),
        ttl=jnp.full((n,), 64, jnp.int32),
        pkt_len=jnp.full((n,), 64, jnp.int32),
        rx_if=jnp.zeros((n,), jnp.int32),
        flags=jnp.full((n,), FLAG_VALID, jnp.int32))
    oracle = NumpyLpmOracle(b).lookup(pkts)
    assert (oracle["node_id"] == np.arange(n) // 4).all()
    assert_fib_equal(fib_lookup_lpm(t, pkts), oracle)


def test_default_host_and_overlapping_covers():
    """/0 default + nested /8 /16 /24 /32 covers of one address:
    longest populated length wins at every nesting step, and deleting
    the middle cover re-resolves to the next one down."""
    b = TableBuilder(_cfg(fib_impl="lpm"))
    b.add_route("0.0.0.0/0", 1, Disposition.REMOTE, node_id=1)
    b.add_route("10.0.0.0/8", 2, Disposition.REMOTE)
    b.add_route("10.1.0.0/16", 3, Disposition.REMOTE)
    b.add_route("10.1.1.0/24", 4, Disposition.LOCAL)
    b.add_route("10.1.1.7/32", 5, Disposition.LOCAL)
    b.add_route("255.255.255.255/32", 6, Disposition.HOST)

    def tx(dst):
        t = b.to_device()
        pk = PacketVector(
            src_ip=jnp.asarray(np.uint32([ip4("1.2.3.4")])),
            dst_ip=jnp.asarray(np.uint32([ip4(dst)])),
            proto=jnp.asarray(np.int32([6])),
            sport=jnp.asarray(np.int32([4000])),
            dport=jnp.asarray(np.int32([80])),
            ttl=jnp.asarray(np.int32([64])),
            pkt_len=jnp.asarray(np.int32([64])),
            rx_if=jnp.asarray(np.int32([0])),
            flags=jnp.asarray(np.int32([FLAG_VALID])),
        )
        return int(np.asarray(fib_lookup_lpm(t, pk).tx_if)[0])

    assert tx("10.1.1.7") == 5
    assert tx("10.1.1.9") == 4
    assert tx("10.1.9.9") == 3
    assert tx("10.9.9.9") == 2
    assert tx("9.9.9.9") == 1
    assert tx("255.255.255.255") == 6   # the pad-value address, live
    assert b.del_route("10.1.1.0/24")
    assert tx("10.1.1.9") == 3          # next cover down
    assert b.del_route("255.255.255.255/32")
    assert tx("255.255.255.255") == 1   # falls to the default


def test_duplicate_prefix_keeps_lowest_slot():
    """Two slots staging the same (prefix, length): both impls must
    resolve the LOWER slot (the dense argmax tie-break)."""
    b = TableBuilder(_cfg(fib_impl="lpm"))
    b.add_route("10.1.1.0/24", 3, Disposition.LOCAL, slot=2)
    b.add_route("10.1.1.0/24", 7, Disposition.LOCAL, slot=9)
    t = b.to_device()
    dst = jnp.asarray(np.uint32([ip4("10.1.1.5")]))
    assert int(np.asarray(ip4_lookup(t, dst).tx_if)[0]) == 3


def test_ecmp_stickiness_under_member_churn():
    """Flow→member assignment: adding a member only remaps flows whose
    way was reassigned; removing one never remaps flows on surviving
    members (the sticky way-fill contract of set_nh_group)."""
    b = TableBuilder(_cfg(fib_impl="lpm", fib_ecmp_groups=2,
                          fib_ecmp_ways=8))
    A, B, C = (ip4("1.0.0.1"), 1, -1), (ip4("1.0.0.2"), 2, -1), \
        (ip4("1.0.0.3"), 3, -1)
    b.set_nh_group(0, [A, B])
    b.add_route("10.0.0.0/8", 1, Disposition.REMOTE, group=0)
    rng = np.random.default_rng(5)
    pkts = _probe_traffic(b, rng, 256)

    hit0 = np.asarray(
        fib_lookup_lpm(b.to_device(), pkts).matched)

    def members(bld):
        res = fib_lookup_lpm(bld.to_device(), pkts)
        return np.asarray(res.next_hop)[hit0].copy(), \
            np.asarray(res.way)[hit0].copy()

    nh1, way1 = members(b)
    assert set(int(x) for x in np.unique(nh1)) == {A[0], B[0]}
    # spread: both members serve a nontrivial share of the hashed flows
    assert min((nh1 == A[0]).sum(), (nh1 == B[0]).sum()) > 16
    assign1 = list(b.nh_groups[0]["assign"])
    b.set_nh_group(0, [A, B, C])
    assign2 = list(b.nh_groups[0]["assign"])
    nh2, way2 = members(b)
    np.testing.assert_array_equal(way1, way2)  # hash never moves
    for w in range(8):
        if assign2[w] == assign1[w]:
            same = way1 == w
            np.testing.assert_array_equal(nh1[same], nh2[same])
    # removing B: flows on A/C ways keep their member exactly
    b.set_nh_group(0, [A, C])
    assign3 = list(b.nh_groups[0]["assign"])
    nh3, _ = members(b)
    for w in range(8):
        if assign3[w] == assign2[w]:
            same = way2 == w
            np.testing.assert_array_equal(nh2[same], nh3[same])
    assert B[0] not in set(np.unique(nh3))


def test_bulk_loader_validates_group_range():
    """add_routes_np enforces the same ECMP-group range checks as
    add_route — an out-of-range id would be clipped on-device onto a
    REAL group and silently forward via its members."""
    b = TableBuilder(_cfg(fib_impl="lpm", fib_ecmp_groups=4))
    nets = np.array([ip4("10.0.0.0")], np.uint32)
    plens = np.array([8], np.int32)
    with pytest.raises(ValueError, match="0..3"):
        b.add_routes_np(nets, plens, tx_if=1,
                        disp=int(Disposition.REMOTE), group=7)
    b2 = TableBuilder(_cfg(fib_impl="lpm"))
    with pytest.raises(ValueError, match="fib_ecmp_groups"):
        b2.add_routes_np(nets, plens, tx_if=1,
                         disp=int(Disposition.REMOTE), group=0)


def test_empty_group_fails_closed():
    """A route pointing at an unconfigured/deleted group resolves as a
    no-route miss, never a zero next-hop forward."""
    b = TableBuilder(_cfg(fib_impl="lpm", fib_ecmp_groups=2))
    b.set_nh_group(1, [(ip4("1.0.0.1"), 1, -1)])
    b.add_route("10.0.0.0/8", 1, Disposition.REMOTE, group=1)
    rng = np.random.default_rng(9)
    pkts = _probe_traffic(b, rng, 64)
    t = b.to_device()
    assert bool(np.asarray(fib_lookup_lpm(t, pkts).matched).any())
    assert b.del_nh_group(1)
    t = b.to_device()
    res = fib_lookup_lpm(t, pkts)
    in_grp = (np.asarray(pkts.dst_ip) >> 24) == 10
    assert not np.asarray(res.matched)[in_grp].any()
    assert_fib_equal(res, NumpyLpmOracle(b).lookup(pkts))


class TestIncrementalChurn:
    def test_flap_reships_only_touched_length_plane(self):
        """A /24 flap re-ships fib_lpm_p24 + the count vector + a
        bounded slot blob; every other length plane (and the ECMP
        tables) keeps device-array identity."""
        b = _random_table(21, 600, 2048)
        t1 = b.to_device()
        # flap one /24: withdraw + re-announce
        slot = int(np.nonzero(np.asarray(b.fib_plen) == 24)[0][0])
        pfx = int(b.fib_prefix[slot])
        pfx_s = (f"{pfx >> 24 & 255}.{pfx >> 16 & 255}."
                 f"{pfx >> 8 & 255}.{pfx & 255}/24")
        assert b.del_route(pfx_s)
        b.add_route(pfx_s, 5, Disposition.REMOTE, slot=slot)
        t2 = b.to_device(sessions=t1)
        up = b.fib_upload
        # the touched plane + count vector (+ the hint rows when the
        # plane is big enough to carry them) — and NOTHING else
        assert "fib_lpm_p24" in up["fields"]
        assert set(up["fields"]) <= {"fib_lpm_p24", "fib_lpm_cnt",
                                     "fib_lpm_hint"}
        assert up["blob_bytes"] > 0     # per-slot rows went as a blob
        assert up["blob_bytes"] < 64 * 1024
        for length in range(33):
            if length == 24:
                continue
            assert getattr(t2, lpm_field(length)) \
                is getattr(t1, lpm_field(length)), length
        assert t2.fib_grp_nh is t1.fib_grp_nh
        # the churned table still matches the oracle
        rng = np.random.default_rng(22)
        pkts = _probe_traffic(b, rng, 256)
        assert_fib_equal(fib_lookup_lpm(t2, pkts),
                         NumpyLpmOracle(b).lookup(pkts))

    def test_noop_commit_ships_nothing(self):
        b = _random_table(23, 100, 256)
        t1 = b.to_device()
        t2 = b.to_device(sessions=t1)
        for length in range(33):
            assert getattr(t2, lpm_field(length)) \
                is getattr(t1, lpm_field(length))
        assert t2.fib_prefix is t1.fib_prefix
        assert t2.fib_grp is t1.fib_grp

    def test_churn_parity_vs_scratch(self):
        """After a sequence of adds/deletes/group churn, the
        incremental planes equal a scratch rebuild bit-for-bit."""
        b = _random_table(31, 200, 512, ecmp_groups=2)
        b.to_device()
        rng = np.random.default_rng(32)
        for _ in range(30):
            if rng.random() < 0.4:
                live = np.nonzero(np.asarray(b.fib_plen) >= 0)[0]
                s = int(rng.choice(live))
                L = int(b.fib_plen[s])
                pfx = int(b.fib_prefix[s])
                b.del_route(f"{pfx >> 24 & 255}.{pfx >> 16 & 255}."
                            f"{pfx >> 8 & 255}.{pfx & 255}/{L}")
            else:
                L = int(rng.choice(_LENGTHS))
                addr = int(rng.integers(0, 1 << 32)) & _mask_of(L)
                free = np.nonzero(np.asarray(b.fib_plen) < 0)[0]
                b.add_route(
                    f"{addr >> 24 & 255}.{addr >> 16 & 255}."
                    f"{addr >> 8 & 255}.{addr & 255}/{L}",
                    int(rng.integers(0, 8)), Disposition.LOCAL,
                    slot=int(free[0]))
        b._restage_lpm()
        scratch = TableBuilder(b.config)
        for arr in ("fib_prefix", "fib_mask", "fib_plen", "fib_tx_if",
                    "fib_disp", "fib_next_hop", "fib_node_id",
                    "fib_snat", "fib_grp"):
            getattr(scratch, arr)[...] = getattr(b, arr)
        for g, e in b.nh_groups.items():
            scratch.set_nh_group(g, e["members"])
        scratch._lpm_dirty_lens = set(range(33))
        scratch._restage_lpm()
        for length in range(33):
            np.testing.assert_array_equal(
                b.lpm_planes[lpm_field(length)],
                scratch.lpm_planes[lpm_field(length)], str(length))
        np.testing.assert_array_equal(b.lpm_cnt, scratch.lpm_cnt)

    def test_state_snapshot_restore_roundtrip(self):
        """Builder rollback (the txn path) restores routes, planes and
        groups; the next to_device serves pre-mutation lookups."""
        b = _random_table(41, 80, 128, ecmp_groups=2)
        rng = np.random.default_rng(42)
        pkts = _probe_traffic(b, rng, 128)
        before = NumpyLpmOracle(b).lookup(pkts)
        snap = b.state_snapshot()
        b.add_route("77.0.0.0/8", 7, Disposition.LOCAL)
        b.set_nh_group(0, [(ip4("9.9.9.9"), 1, -1)])
        assert b.del_route("77.0.0.0/8") or True
        b.state_restore(snap)
        t = b.to_device()
        assert_fib_equal(fib_lookup_lpm(t, pkts), before)
        assert_fib_equal(fib_lookup_dense(t, pkts), before)


def test_plane_overflow_regates_to_dense():
    """A length over its configured cap makes lpm_ok() false and the
    auto ladder falls back to dense — loudly visible, never a wrong
    lookup."""
    caps = [0] * 25
    caps[24] = 2
    caps[0] = 1
    dp = Dataplane(_cfg(fib_impl="auto", fib_lpm_min_routes=1,
                        fib_lpm_plen_caps=tuple(caps)))
    dp.builder.add_route("10.1.1.0/24", 1, Disposition.LOCAL)
    dp.builder.add_route("10.1.2.0/24", 1, Disposition.LOCAL)
    dp.swap()
    assert dp.fib_impl == "lpm"
    dp.builder.add_route("10.1.3.0/24", 1, Disposition.LOCAL)
    dp.swap()
    assert not dp.builder.lpm_ok()
    assert dp.fib_impl == "dense"
    # a length with cap 0 is not served either
    dp.builder.add_route("10.0.0.0/8", 1, Disposition.REMOTE)
    dp.swap()
    assert dp.fib_impl == "dense"


def test_mem_cap_disables_lpm():
    """auto honors fib_lpm_mem_mb: a cap below the plane bytes keeps
    the builder off LPM entirely (zero-width placeholders)."""
    dp = Dataplane(_cfg(fib_slots=4096, fib_impl="auto",
                        fib_lpm_mem_mb=0))
    assert not dp.builder.lpm_enabled
    assert dp.tables.fib_lpm_p24.shape[1] == 0
    dp.builder.add_route("10.1.1.0/24", 1, Disposition.LOCAL)
    dp.swap()
    assert dp.fib_impl == "dense"
    # the route histogram must not depend on LPM staging: dense-only
    # configs still report their per-length counts
    snap = dp.fib_snapshot()
    assert snap["by_length"] == {24: 1} and snap["routes"] == 1


@pytest.mark.jit_budget(4)
def test_auto_regates_at_swap_with_bounded_compiles():
    """fib_impl auto flips dense→lpm at the route threshold across
    epoch swaps; the flip costs exactly the two step programs (one per
    rung) and churn AFTER the flip compiles nothing new — the
    zero-new-step-form contract (only the fib_impl key)."""
    from vpp_tpu.cli import DebugCLI
    from vpp_tpu.stats.collector import StatsCollector

    dp = Dataplane(_cfg(fib_impl="auto", fib_lpm_min_routes=8))
    up = dp.add_uplink()
    dp.builder.add_route("10.1.1.0/24", up, Disposition.LOCAL)
    dp.swap()
    assert dp.fib_impl == "dense"
    pkts = _probe_traffic(dp.builder, np.random.default_rng(2), 64)
    pkts = pkts._replace(rx_if=jnp.full(pkts.rx_if.shape, up,
                                        jnp.int32))
    dp.process(pkts)
    for i in range(10):
        dp.builder.add_route(f"10.{i + 2}.0.0/16", up,
                             Disposition.LOCAL)
    dp.swap()
    assert dp.fib_impl == "lpm"
    dp.process(pkts)
    coll = StatsCollector(dp)
    coll.publish()
    page = coll.registry.render("/stats")
    assert 'vpp_tpu_fib_impl{impl="lpm"} 1' in page
    assert 'vpp_tpu_fib_impl{impl="dense"} 0' in page
    assert "impl lpm" in DebugCLI(dp).run("show fib")
    # churn at the same rung: swap + process retraces nothing (the
    # jit_budget marker enforces the ceiling at test end)
    dp.builder.add_route("10.99.0.0/16", up, Disposition.LOCAL)
    dp.swap()
    assert dp.fib_impl == "lpm"
    dp.process(pkts)


def test_end_to_end_lpm_equals_dense_dataplane():
    """Full fused-pipeline differential: identical config except the
    fib_impl knob must produce identical dispositions, drop causes and
    counters over mixed traffic (the classifier-knob test's twin)."""
    rng = np.random.default_rng(51)
    rows = []
    for i in range(96):
        rows.append({"src": f"172.16.{i % 8}.{1 + i % 250}",
                     "dst": rng.choice(
                         ["10.1.1.2", "10.1.2.9", "10.9.1.1",
                          "8.8.8.8", "10.1.1.255"]),
                     "proto": 6, "sport": 1024 + i,
                     "dport": int(rng.choice([80, 443, 8080]))})
    out = {}
    for knob in ("dense", "lpm"):
        dp = Dataplane(_cfg(fib_impl=knob, fib_ecmp_groups=2,
                            fib_ecmp_ways=4))
        up = dp.add_uplink()
        dp.builder.set_nh_group(0, [(ip4("192.168.0.2"), up, 1),
                                    (ip4("192.168.0.3"), up, 2)])
        dp.builder.add_route("10.1.1.0/24", up, Disposition.LOCAL)
        dp.builder.add_route("10.1.0.0/16", up, Disposition.REMOTE,
                             node_id=1)
        dp.builder.add_route("10.0.0.0/8", up, Disposition.REMOTE,
                             group=0)
        dp.builder.add_route("0.0.0.0/0", up, Disposition.DROP)
        dp.swap()
        if knob == "lpm":
            assert dp.fib_impl == "lpm"
        from vpp_tpu.pipeline.vector import make_packet_vector

        pkts = make_packet_vector(
            [dict(r, rx_if=up) for r in rows], n=len(rows))
        res = dp.process(pkts)
        out[knob] = (np.asarray(res.disp), np.asarray(res.drop_cause),
                     np.asarray(res.tx_if), np.asarray(res.next_hop),
                     int(res.stats.tx), int(res.stats.drop_no_route))
    for a, bb in zip(out["dense"], out["lpm"]):
        np.testing.assert_array_equal(a, bb)


def test_ecmp_accounting_plane_and_family():
    """Forwarded ECMP packets land in the carried [G, W] accounting
    plane (exact conservation vs StepStats.tx on a pure-ECMP batch)
    and render on the labelled vpp_tpu_fib_ecmp_packets family."""
    from vpp_tpu.stats.collector import StatsCollector

    dp = Dataplane(_cfg(fib_impl="lpm", fib_ecmp_groups=2,
                        fib_ecmp_ways=4))
    up = dp.add_uplink()
    dp.builder.set_nh_group(0, [(ip4("192.168.0.2"), up, 1),
                                (ip4("192.168.0.3"), up, 2)])
    dp.builder.add_route("10.0.0.0/8", up, Disposition.REMOTE, group=0)
    dp.swap()
    from vpp_tpu.pipeline.vector import make_packet_vector

    rng = np.random.default_rng(61)
    pkts = make_packet_vector(
        [{"src": f"172.16.0.{1 + i % 250}", "dst": f"10.2.3.{i % 250}",
          "proto": 17, "sport": int(rng.integers(1024, 65000)),
          "dport": 53, "rx_if": up} for i in range(64)], n=64)
    res = dp.process(pkts)
    fwd = int(res.stats.tx)
    assert fwd == 64
    plane = np.asarray(dp.tables.fib_ecmp_c)
    assert int(plane.sum()) == fwd
    assert int(plane[0].sum()) == fwd
    snap = dp.fib_snapshot()
    assert int(snap["ecmp_c"].sum()) == fwd
    coll = StatsCollector(dp)
    coll.publish()
    page = coll.registry.render("/stats")
    # BOTH members render as their own series (full identity labels —
    # the two members here share nothing, but members differing only
    # in node_id must not collapse either)
    assert 'member="192.168.0.2:if' in page
    assert 'member="192.168.0.3:if' in page
    # swap carries the plane by reference (state, like telemetry)
    before = dp.tables.fib_ecmp_c
    dp.builder.add_route("10.7.0.0/16", up, Disposition.LOCAL)
    dp.swap()
    assert dp.tables.fib_ecmp_c is before


def test_show_fib_summary_filter_and_scale_guard():
    """`show fib` leads with the summary header; big tables render no
    per-slot rows without a prefix filter; the filter matches with one
    vectorized pass (covering + covered routes)."""
    from vpp_tpu.cli import DebugCLI

    b_dp = Dataplane(_cfg(fib_slots=1024, fib_impl="lpm"))
    cli = DebugCLI(b_dp)
    for i in range(600):
        b_dp.builder.add_route(f"10.{i // 250}.{i % 250}.0/24", 1,
                               Disposition.LOCAL, slot=i)
    b_dp.builder.add_route("0.0.0.0/0", 2, Disposition.REMOTE,
                           slot=1000)
    b_dp.swap()
    out = cli.run("show fib")
    assert "impl lpm" in out and "routes 601" in out
    assert "/24:600" in out
    assert "prefix filter" in out          # too big to list
    assert "10.1.17.0/24" not in out
    filt = cli.run("show fib 10.1.17.0/24")
    assert "10.1.17.0/24" in filt
    assert "0.0.0.0/0" in filt             # the covering default shows
    assert "10.1.18.0/24" not in filt
    assert "bad prefix filter" in cli.run("show fib bogus")


def test_pad_address_and_planes_inert():
    """The 255.255.255.255 pad value is still a servable address, and
    pad rows past each plane's live count never match (the lint
    invariant, exercised through the kernel)."""
    b = TableBuilder(_cfg(fib_impl="lpm"))
    b.add_route("255.255.255.254/31", 4, Disposition.LOCAL)
    t = b.to_device()
    assert int(b.lpm_cnt[31]) == 1
    plane = b.lpm_planes[lpm_field(31)]
    assert (plane[0, 1:] == LPM_PAD).all()
    dst = jnp.asarray(np.uint32([ip4("255.255.255.255"),
                                 ip4("255.255.255.253")]))
    res = ip4_lookup(t, dst)
    assert bool(np.asarray(res.matched)[0])
    assert not bool(np.asarray(res.matched)[1])


def test_tables_lint_lpm_invariants():
    """tools/lint.py --tables runs the LPM structure pass from tier-1
    (strict sort, pad inertness, group membership)."""
    import importlib.util
    import sys
    from pathlib import Path

    tools = Path(__file__).resolve().parents[1] / "tools"
    if str(tools) not in sys.path:
        sys.path.insert(0, str(tools))
    spec = importlib.util.spec_from_file_location(
        "vppt_lint", tools / "lint.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from analysis.registries import _lpm_plane_problems

    assert _lpm_plane_problems() == []
