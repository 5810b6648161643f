"""Nodes whose policy lives in per-interface local tables.

- The local BV classify's per-packet binary search over the flattened
  boundary arrays is bit-exact with the search it replaced (each
  packet's whole boundary row gathered, then a vmapped searchsorted),
  kept here as the oracle, and the local Pallas first-set kernel
  (interpret mode) with both.
- Kubernetes egress semantics end to end: random namespaces, egress
  policies (ipBlocks with excepts, ports or none, no peers) and
  unisolated pods go through PolicyProcessor -> PolicyConfigurator ->
  TpuRenderer -> Dataplane, and every local rung (dense, bv, pallas in
  interpret mode) gives the verdicts a direct evaluation of the
  NetworkPolicy objects gives.
- ``classifier: auto`` engages BV (pallas on a TPU) from the largest
  staged local table, and the memory cap counts the local planes.
- The ``local_table_pkts`` counter: packets whose rx interface has a
  local table, counted per dispatch and folded into the pump's stats.
"""

import ipaddress
import random
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vpp_tpu.ir.rule import PodID
from vpp_tpu.ksr import model as m
from vpp_tpu.ops.acl import AclVerdict, acl_classify_local, acl_unmatched_default
from vpp_tpu.ops.acl_bv import (
    BV_ENC_MISS,
    _first_set_bit,
    _local_rows,
    _local_verdict,
    acl_classify_local_bv,
    acl_local_bv_first_set,
    bv_config_bytes,
    bv_enabled_for,
    bv_first_set,
    bv_global_bytes,
)
from vpp_tpu.parallel.partition import select_impl
from vpp_tpu.pipeline.dataplane import (
    Dataplane,
    local_table_pkts,
    pack_packet_columns,
)
from vpp_tpu.pipeline.tables import DataplaneConfig, InterfaceType, TableBuilder
from vpp_tpu.pipeline.vector import VEC, Disposition, PacketVector, make_packet_vector
from vpp_tpu.policy import PolicyCache, PolicyConfigurator, PolicyProcessor
from vpp_tpu.renderer.tpu import TpuRenderer

from test_acl_bv import random_packets, random_rules


def vmapped_local_bv(tables, pkts):
    """The earlier local BV classify: every packet gathers its table's
    whole [I] boundary rows, then a vmapped searchsorted."""
    tid = tables.if_local_table[pkts.rx_if]
    has_table = tid >= 0
    t = jnp.maximum(tid, 0)
    nb = tables.acl_bv_nbnd[t]

    def seg(bnd_rows, vals, n):
        i = jax.vmap(lambda b, v: jnp.searchsorted(b, v, side="right"))(
            bnd_rows, vals).astype(jnp.int32) - 1
        return jnp.clip(i, 0, n - 1)

    si = seg(tables.acl_bv_bnd_src[t], pkts.src_ip, nb[:, 0])
    di = seg(tables.acl_bv_bnd_dst[t], pkts.dst_ip, nb[:, 1])
    pi = seg(tables.acl_bv_bnd_sport[t], pkts.sport, nb[:, 2])
    qi = seg(tables.acl_bv_bnd_dport[t], pkts.dport, nb[:, 3])
    pr = jnp.clip(pkts.proto, 0, tables.acl_bv_proto.shape[1] - 1)
    words = (tables.acl_bv_src[t, si] & tables.acl_bv_dst[t, di]
             & tables.acl_bv_sport[t, pi] & tables.acl_bv_dport[t, qi]
             & tables.acl_bv_proto[t, pr])
    matched, rule = _first_set_bit(words)
    safe = jnp.where(matched, rule, 0)
    act = tables.acl_action[t, safe]
    permit = jnp.where(matched, act == 1,
                       acl_unmatched_default(pkts, tables.acl_nrules[t]))
    return AclVerdict(permit=jnp.where(has_table, permit, True),
                      rule_idx=jnp.where(has_table & matched, rule, -1))


def local_pallas_interpret(tables, pkts):
    """The pallas rung's local classify with its kernel in interpret
    mode (on a TPU the same composition runs compiled)."""
    t, has_table, rows = _local_rows(tables, pkts)
    enc = acl_local_bv_first_set(*rows, interpret=True)
    matched = enc != BV_ENC_MISS
    return _local_verdict(tables, pkts, t, has_table, matched,
                          jnp.where(matched, enc, -1))


def _local_tables(rng, n_tables, max_rules=64):
    b = TableBuilder(DataplaneConfig(
        max_tables=n_tables + 1, max_rules=max_rules, max_global_rules=32,
        max_ifaces=16, fib_slots=16, sess_slots=64, nat_mappings=2,
        nat_backends=4, classifier="bv"))
    b.set_interface(1, InterfaceType.UPLINK, apply_global=True)
    rules = []
    for t in range(n_tables):
        b.set_interface(2 + t, InterfaceType.POD, local_table=t)
        r = random_rules(rng, int(rng.integers(1, max_rules)))
        rules += r
        b.set_local_table(t, r)
    # a pod without a table and an empty staged slot
    b.set_interface(2 + n_tables, InterfaceType.POD, local_table=-1)
    b.set_interface(3 + n_tables, InterfaceType.POD, local_table=n_tables)
    return b, rules


def _edge_packets(t, rng, n, max_if):
    """Packets whose fields sit ON the staged boundaries (and one past
    them), at 0 and at the pad-equal maxima."""
    pts = {}
    for dim, col in (("src", "acl_bv_bnd_src"), ("dst", "acl_bv_bnd_dst"),
                     ("sport", "acl_bv_bnd_sport"),
                     ("dport", "acl_bv_bnd_dport")):
        b = np.asarray(getattr(t, col)).astype(np.int64).ravel()
        hi = (1 << 32) - 1 if dim in ("src", "dst") else 65535
        vals = np.concatenate([b, b - 1, b + 1, [0, hi]])
        pts[dim] = vals[(vals >= 0) & (vals <= hi)]
    pick = {d: rng.choice(v, n) for d, v in pts.items()}
    return PacketVector(
        src_ip=jnp.asarray(pick["src"].astype(np.uint32)),
        dst_ip=jnp.asarray(pick["dst"].astype(np.uint32)),
        proto=jnp.asarray(rng.choice([1, 6, 17, 255], n).astype(np.int32)),
        sport=jnp.asarray(pick["sport"].astype(np.int32)),
        dport=jnp.asarray(pick["dport"].astype(np.int32)),
        ttl=jnp.full((n,), 64, jnp.int32),
        pkt_len=jnp.full((n,), 100, jnp.int32),
        rx_if=jnp.asarray(rng.integers(0, max_if, n).astype(np.int32)),
        flags=jnp.ones((n,), jnp.int32),
    )


def _assert_same(got, want):
    np.testing.assert_array_equal(np.asarray(got.permit),
                                  np.asarray(want.permit))
    np.testing.assert_array_equal(np.asarray(got.rule_idx),
                                  np.asarray(want.rule_idx))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_local_search_bitexact_with_row_gather(seed):
    rng = np.random.default_rng(seed)
    b, rules = _local_tables(rng, 4)
    t = b.to_device()
    for pkts in (random_packets(rng, 384, rules, max_if=9),
                 _edge_packets(t, rng, 384, 9)):
        want = vmapped_local_bv(t, pkts)
        _assert_same(acl_classify_local_bv(t, pkts), want)
        _assert_same(acl_classify_local(t, pkts), want)


@pytest.mark.parametrize("seed", [3, 4])
def test_local_pallas_kernel_interpret_matches_bv(seed):
    rng = np.random.default_rng(seed)
    b, rules = _local_tables(rng, 3, max_rules=96)
    t = b.to_device()
    pkts = random_packets(rng, 300, rules, max_if=8)
    _assert_same(local_pallas_interpret(t, pkts), acl_classify_local_bv(t, pkts))
    # the local kernel is the global kernel's math under its own name
    rows = [jnp.asarray(rng.integers(0, 1 << 32, (40, 7), dtype=np.uint32))
            for _ in range(5)]
    np.testing.assert_array_equal(
        np.asarray(acl_local_bv_first_set(*rows, interpret=True)),
        np.asarray(bv_first_set(*rows, interpret=True)))


# --- Kubernetes egress semantics, through the policy path ------------------

PORTS = (("TCP", 80), ("TCP", 443), ("UDP", 53), ("TCP", 8080))
REMOTE = "10.9.0.0/16"


def _random_block(rng):
    base = rng.choice(("10.1.1.0", "10.9.1.0", "10.9.2.0"))
    plen = rng.choice((24, 26, 27))
    net = ipaddress.ip_network(f"{base}/{plen}")
    excepts = set()
    for _ in range(rng.randint(0, 3)):
        sub = list(net.subnets(new_prefix=min(32, plen + rng.choice((2, 3, 4)))))
        excepts.add(str(rng.choice(sub)))
    return m.IPBlock(cidr=str(net), except_cidrs=sorted(excepts))


def k8s_egress_allowed(policies, ns, dst, proto, port):
    """Direct evaluation of the egress NetworkPolicies selecting a pod
    of namespace ``ns`` (every policy here selects all its pods)."""
    applying = [p for p in policies if p.namespace == ns]
    if not applying:
        return True
    addr = ipaddress.ip_address(dst)
    for pol in applying:
        for rule in pol.egress_rules:
            port_ok = not rule.ports or any(
                pp.port == port and pp.protocol == proto for pp in rule.ports)
            peer_ok = not rule.peers or any(
                addr in ipaddress.ip_network(pe.ip_block.cidr)
                and not any(addr in ipaddress.ip_network(e)
                            for e in pe.ip_block.except_cidrs)
                for pe in rule.peers)
            if port_ok and peer_ok:
                return True
    return False


def _egress_node(seed, classifier):
    rng = random.Random(seed)
    namespaces = [f"ns{j}" for j in range(3)] + ["open"]
    dp = Dataplane(DataplaneConfig(
        max_tables=8, max_rules=256, max_global_rules=32, max_ifaces=16,
        fib_slots=32, sess_slots=256, classifier=classifier,
        classifier_bv_min_rules=1))
    up = dp.add_uplink()
    cache = PolicyCache()
    configurator = PolicyConfigurator(cache)
    configurator.register_renderer(TpuRenderer(dp))
    PolicyProcessor(cache, configurator)
    pods = []
    for k in range(8):
        ns = namespaces[k % len(namespaces)]
        pid = PodID(ns, f"p{k}")
        ip = f"10.1.1.{10 + 16 * k}"
        idx = dp.add_pod_interface(pid)
        dp.builder.add_route(f"{ip}/32", idx, Disposition.LOCAL)
        pods.append((pid, ip, idx))
    dp.builder.add_route("0.0.0.0/0", up, Disposition.REMOTE)
    dp.swap()
    policies = []
    for j, ns in enumerate(namespaces[:3]):
        policies.append(m.Policy(
            name=f"eg{j}", namespace=ns, pods=m.LabelSelector(),
            policy_type=m.POLICY_EGRESS,
            egress_rules=[
                m.PolicyRule(
                    ports=[m.PolicyPort(protocol=p, port=n) for p, n in
                           rng.sample(PORTS, rng.randint(1, 2))]
                    if rng.random() < 0.75 else [],
                    peers=[m.PolicyPeer(ip_block=_random_block(rng))
                           for _ in range(rng.randint(1, 3))]
                    if rng.random() < 0.85 else [])
                for _ in range(rng.randint(1, 2))]))
    cache.resync(
        [m.Pod(name=pid.name, namespace=pid.namespace, ip_address=ip)
         for pid, ip, _ in pods],
        policies,
        [m.Namespace(name=ns) for ns in namespaces])
    return dp, pods, policies, rng


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_egress_policies_match_k8s_semantics(seed):
    dp, pods, policies, rng = _egress_node(seed, "bv")
    assert dp.classifier_impl == "bv"
    # isolated namespaces got tables, the open one none
    assert {dp.builder.if_local_table[i] >= 0 for pid, _, i in pods
            if pid.namespace == "open"} == {False}
    probes, want = [], []
    remote = list(ipaddress.ip_network(REMOTE).hosts())
    for pid, _ip, idx in pods:
        for _ in range(24):
            if rng.random() < 0.4:
                dst = rng.choice([ip for p, ip, _ in pods if p != pid])
            else:
                dst = str(rng.choice(remote[:1024]))
            proto, port = rng.choice(PORTS + (("TCP", 22), ("UDP", 80)))
            probes.append({"src": _ip, "dst": dst,
                           "proto": 6 if proto == "TCP" else 17,
                           "sport": 40000, "dport": port, "rx_if": idx})
            want.append(k8s_egress_allowed(policies, pid.namespace, dst,
                                           proto, port))
    want = np.array(want)
    assert want.any() and not want.all()
    pkts = make_packet_vector(probes, n=len(probes))
    t = dp.tables
    for name, fn in (("dense", acl_classify_local),
                     ("bv", acl_classify_local_bv),
                     ("pallas", local_pallas_interpret)):
        got = np.asarray(fn(t, pkts).permit)
        bad = np.nonzero(got != want)[0]
        assert not len(bad), (name, [probes[i] for i in bad[:5]])
    # and through the whole step, on the served rung and on dense
    for knob in ("bv", "dense"):
        node = dp if knob == "bv" else _egress_node(seed, "dense")[0]
        disp = np.asarray(node.process(pkts).disp)
        np.testing.assert_array_equal(disp != int(Disposition.DROP), want)


# --- rung selection and the memory cap -------------------------------------


def _local_only(rules, **kw):
    from vpp_tpu.ir.rule import Action, ContivRule, Protocol

    cfg = dict(max_tables=2, max_rules=64, max_global_rules=32,
               max_ifaces=8, fib_slots=16, sess_slots=64,
               classifier="auto", classifier_bv_min_rules=32)
    cfg.update(kw)
    dp = Dataplane(DataplaneConfig(**cfg))
    dp.mxu_threshold = 1 << 30
    idx = dp.add_pod_interface(PodID("ns", "p"))
    slot = dp.alloc_table_slot("T")
    dp.builder.set_local_table(slot, [
        ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                   dest_port=8000 + i) for i in range(rules - 1)
    ] + [ContivRule(action=Action.DENY, protocol=Protocol.TCP)])
    dp.assign_pod_table(PodID("ns", "p"), "T")
    dp.swap()
    return dp, idx


def test_auto_selects_bv_from_the_largest_local_table():
    dp, _ = _local_only(48)
    assert dp.builder.glb_nrules == 0
    assert dp.classifier_impl == "bv"
    dp, _ = _local_only(8)
    assert dp.classifier_impl == "dense"
    # the ladder itself: a local table at the threshold engages BV, and
    # pallas where the backend carries it; MXU never serves local tables
    assert select_impl("auto", True, True, 0, 32, 16, local_nrules=32) == "bv"
    assert select_impl("auto", True, True, 0, 32, 16, pallas_ok=True,
                       local_nrules=40) == "pallas"
    assert select_impl("auto", False, True, 0, 32, 16,
                       local_nrules=40) == "dense"
    assert select_impl("auto", True, True, 20, 32, 16) == "mxu"


def test_cluster_agrees_on_the_local_rung():
    from vpp_tpu.ir.rule import Action, ContivRule, Protocol
    from vpp_tpu.parallel.cluster import ClusterDataplane
    from vpp_tpu.parallel.mesh import cluster_mesh

    cfg = DataplaneConfig(max_tables=2, max_rules=64, max_global_rules=64,
                          max_ifaces=8, fib_slots=16, sess_slots=64,
                          classifier="auto", classifier_bv_min_rules=32)
    clus = ClusterDataplane(cluster_mesh(2, 1), cfg)
    clus.mxu_threshold = 1 << 30
    # only the second node stages a large local table: the one jitted
    # program of the mesh still has to serve it on the BV rung
    node = clus.node(1)
    node.add_pod_interface(PodID("ns", "p"))
    slot = node.alloc_table_slot("T")
    node.builder.set_local_table(slot, [
        ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                   dest_port=8000 + i) for i in range(40)])
    node.assign_pod_table(PodID("ns", "p"), "T")
    clus.swap()
    assert clus.classifier_impl == "bv"


def test_memory_cap_counts_the_local_planes():
    base = dict(max_global_rules=128, max_rules=1024, max_tables=16,
                classifier="auto")
    want = bv_global_bytes(128) + 16 * bv_global_bytes(1024)
    assert bv_config_bytes(DataplaneConfig(**base)) == want
    cap_mb = -(-want // (1 << 20))
    assert bv_enabled_for(DataplaneConfig(**base, classifier_bv_mem_mb=cap_mb))
    # the global structure alone fits the cap; with the local planes
    # counted the config does not
    assert bv_global_bytes(128) < (cap_mb - 1) * (1 << 20)
    assert not bv_enabled_for(
        DataplaneConfig(**base, classifier_bv_mem_mb=cap_mb - 1))
    # explicit knobs allocate regardless
    assert bv_enabled_for(DataplaneConfig(**dict(base, classifier="bv"),
                                          classifier_bv_mem_mb=0))


# --- the local_table_pkts counter ------------------------------------------


def _packed(rx_ifs, valid):
    n = len(rx_ifs)
    flat = np.zeros((5, n), np.int32)
    cols = {c: np.zeros(n, np.int32) for c in
            ("src_ip", "dst_ip", "sport", "dport", "pkt_len", "proto",
             "ttl", "flags")}
    cols["rx_if"] = np.asarray(rx_ifs, np.int32)
    cols["flags"] = np.asarray(valid, np.int32)
    pack_packet_columns(flat.view(np.uint32), cols, n)
    return flat


def test_local_table_pkts_counts_valid_packets_on_tabled_interfaces():
    table = np.array([-1, -1, 0, 3, -1], np.int32)
    flat = _packed([2, 3, 4, 2, 1, 3, 99], [1, 1, 1, 0, 1, 1, 1])
    # rx 2 and 3 have tables; the invalid slot and if 4 / 1 do not;
    # an out-of-range interface reads the last entry (-1)
    assert local_table_pkts(flat, table) == 3
    assert local_table_pkts(np.stack([flat, flat]), table) == 6
    assert local_table_pkts(jnp.asarray(flat), table) == 0


def test_local_table_pkts_folds_into_pump_stats():
    from wire import make_frame

    from vpp_tpu.io import DataplanePump, IORingPair
    from vpp_tpu.native.pktio import PacketCodec

    dp, idx = _local_only(8)
    other = dp.add_pod_interface(PodID("open", "q"))
    dp.builder.add_route("10.1.1.2/32", other, Disposition.LOCAL)
    dp.swap()
    flat = _packed([idx] * 5 + [other] * 3, [1] * 8)
    before = dp.host_counters["local_table_pkts"]
    dp.process_packed(flat)
    assert dp.host_counters["local_table_pkts"] - before == 5

    rings = IORingPair(n_slots=32)
    pump = DataplanePump(dp, rings, max_batch=VEC)
    pump.warm()
    pump.start()
    codec = PacketCodec()
    scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
    try:
        for rx, n in ((idx, 6), (other, 4)):
            frames = [make_frame("10.1.1.9", "10.1.1.2", proto=6,
                                 sport=30000 + j, dport=8000)
                      for j in range(n)]
            cols, got = codec.parse(frames, rx, scratch)
            assert rings.rx.push(cols, got, payload=scratch)
        deadline = time.monotonic() + 120
        out = 0
        while out < 2 and time.monotonic() < deadline:
            if rings.tx.peek() is None:
                time.sleep(0.002)
                continue
            rings.tx.release()
            out += 1
    finally:
        assert pump.stop(join_timeout=60)
        rings.close()
    assert pump.stats["pkts"] == 10
    assert pump.stats["local_table_pkts"] == 6
