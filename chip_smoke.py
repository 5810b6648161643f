#!/usr/bin/env python
"""Chip smoke: the node data plane end to end on one TPU chip.

Runs in ONE process (a chip belongs to one process at a time) and
prints one line per phase, then the result line the driver reads:

A  the agent path at its default configuration: ContivAgent fed by a
   KSR + KVStore pair (two pods, one NetworkPolicy, one ClusterIP
   Service), 256-packet frames through DataplanePump in dispatch mode;
B  one node at a size its users run: 10,240 global rules shaped like
   the reference's gen-policy.py, 100 NAT backends, 1 << 20 session
   slots and a 5,000-node FIB; the Pallas rungs against the jnp rungs
   on the same frames, and the pure-Python rule/route oracles;
C  every Pallas kernel with ``interpret=False`` at the Phase B widths
   against its jnp reference.

``--mesh`` (a four-chip host) runs only the mesh phase: MeshRuntime
over four chips, then two nodes x two rule shards on the MXU
classifier, each held bit-exact against standalone Dataplanes on one
chip.

Times printed here are a smoke reading, not a benchmark. With no TPU
the script exits non-zero, names the platform it found and prints no
result line.
"""

from __future__ import annotations

import argparse
import copy
import ipaddress
import json
import struct
import sys
import time
import traceback

import numpy as np

N_RULES = 10240
N_BACKENDS = 100
SESS_SLOTS = 1 << 20
N_NODES = 5000          # Kubernetes "Considerations for large clusters"
LOCAL_NODE = 1
N_PODS = 110            # kubelet's default max pods per node
STEP_PKTS = 65536
VIP = "10.96.0.10"
FRAME = 256


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


def tree_diff(a, b) -> list:
    """Leaves of ``a`` and ``b`` that differ: (path, mismatches, first
    indices, a's values there, b's values there)."""
    import jax

    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, tb = jax.tree_util.tree_flatten_with_path(b)
    if ta != tb:
        return [("structure", str(ta), str(tb))]
    out = []
    for (path, x), (_, y) in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape:
            out.append((jax.tree_util.keystr(path), "shape", x.shape,
                        y.shape))
            continue
        bad = np.nonzero(np.ravel(x != y))[0]
        if len(bad):
            out.append((jax.tree_util.keystr(path), len(bad),
                        bad[:4].tolist(), np.ravel(x)[bad[:4]].tolist(),
                        np.ravel(y)[bad[:4]].tolist()))
    return out


def check_equal(a, b, msg: str) -> None:
    diff = tree_diff(a, b)
    check(not diff, f"{msg}: {diff}")


# --- Phase A: the agent path -------------------------------------------


def tcp_frame(src: str, dst: str, sport: int, dport: int) -> bytes:
    """Ethernet + IPv4 + TCP SYN with valid checksums."""
    eth = b"\x02\x00\x00\x00\x00\x02\x02\x00\x00\x00\x00\x01\x08\x00"
    src_b = ipaddress.ip_address(src).packed
    dst_b = ipaddress.ip_address(dst).packed

    def csum(data: bytes) -> int:
        s = sum(struct.unpack(f"!{len(data) // 2}H", data))
        while s >> 16:
            s = (s & 0xFFFF) + (s >> 16)
        return ~s & 0xFFFF

    l4 = struct.pack("!HHIIBBHHH", sport, dport, 1, 0, 5 << 4, 0x02,
                     8192, 0, 0) + b"x" * 32
    ck = csum(src_b + dst_b + struct.pack("!BBH", 0, 6, len(l4)) + l4)
    l4 = l4[:16] + struct.pack("!H", ck or 0xFFFF) + l4[18:]
    hdr = struct.pack("!BBHHHBBH4s4s", 0x45, 0, 20 + len(l4), 1, 0x4000,
                      64, 6, 0, src_b, dst_b)
    hdr = hdr[:10] + struct.pack("!H", csum(hdr)) + hdr[12:]
    return eth + hdr + l4


def phase_a(on_tpu: bool, n_frames: int = 8) -> dict:
    from vpp_tpu.cmd import AgentConfig, ContivAgent
    from vpp_tpu.cmd.config import IOConfig
    from vpp_tpu.cmd.ksr_main import KsrAgent
    from vpp_tpu.cni.model import CNIRequest
    from vpp_tpu.ksr import model as m
    from vpp_tpu.kvstore.store import KVStore
    from vpp_tpu.native.pktio import PacketCodec
    from vpp_tpu.pipeline.vector import VEC, Disposition, ip4

    store = KVStore()
    ksr = KsrAgent(store=store, serve_http=False)
    ksr.start()
    # the default agent config; io.enabled gives it the in-process
    # rings and the dispatch-mode pump it serves in deployment
    agent = ContivAgent(
        AgentConfig(serve_http=False, io=IOConfig(enabled=True)),
        store=store)
    t0 = time.perf_counter()
    agent.start()
    boot_s = time.perf_counter() - t0
    try:
        ips = {}
        for name in ("client", "server"):
            reply = agent.cni_server.add(CNIRequest(
                container_id=f"c-{name}",
                extra_args={"K8S_POD_NAME": name,
                            "K8S_POD_NAMESPACE": "default"}))
            check(reply.result == 0, f"CNI add {name}: {reply}")
            ip = reply.interfaces[0].ip_addresses[0].address.split("/")[0]
            ips[name] = ip
            ksr.sources[m.Pod.TYPE].add(f"default/{name}", m.Pod(
                name=name, namespace="default", labels={"app": name},
                ip_address=ip))
        ksr.sources[m.Namespace.TYPE].add(
            "default", m.Namespace(name="default", labels={}))
        # the server admits only TCP/80 from the client: 9999 is denied
        ksr.sources[m.Policy.TYPE].add("default/server", m.Policy(
            name="server", namespace="default",
            pods=m.LabelSelector(match_labels={"app": "server"}),
            policy_type=m.POLICY_INGRESS,
            ingress_rules=[m.PolicyRule(
                ports=[m.PolicyPort(protocol="TCP", port=80)],
                peers=[m.PolicyPeer(pods=m.LabelSelector(
                    match_labels={"app": "client"}))])]))
        vip = "10.96.0.50"
        ksr.sources[m.Service.TYPE].add("default/web", m.Service(
            name="web", namespace="default", cluster_ip=vip,
            ports=[m.ServicePort(name="http", protocol="TCP", port=80,
                                 target_port="http")]))
        ksr.sources[m.Endpoints.TYPE].add("default/web", m.Endpoints(
            name="web", namespace="default",
            subsets=[m.EndpointSubset(
                addresses=[m.EndpointAddress(
                    ip=ips["server"], node_name=agent.config.node_name)],
                ports=[m.EndpointPort(name="http", port=80,
                                      protocol="TCP")])]))

        dp = agent.dataplane
        client_if = dp.pod_if[("default", "client")]
        server_if = dp.pod_if[("default", "server")]
        rings = agent.io_rings
        codec = PacketCodec(snap=rings.rx.snap)
        scratch = np.zeros((VEC, rings.rx.snap), np.uint8)
        # per packet j: 0,1 permitted (server:80), 2 denied
        # (server:9999), 3 VIP:80 (DNAT to server:80)
        kind = np.arange(FRAME) % 4
        dsts = [(ips["server"], 80), (ips["server"], 80),
                (ips["server"], 9999), (vip, 80)]
        t0 = time.perf_counter()
        for k in range(n_frames):
            frames = [tcp_frame(ips["client"], dsts[kind[j]][0],
                                20000 + k * FRAME + j, dsts[kind[j]][1])
                      for j in range(FRAME)]
            cols, n = codec.parse(frames, client_if, scratch)
            check(n == FRAME, f"codec parsed {n} of {FRAME}")
            while not rings.rx.push(cols, n, payload=scratch):
                time.sleep(0.001)
        got = []
        deadline = time.monotonic() + 900
        while len(got) < n_frames and time.monotonic() < deadline:
            f = rings.tx.peek()
            if f is None:
                time.sleep(0.002)
                continue
            got.append({c: f.cols[c][:f.n].copy()
                        for c in ("disp", "rx_if", "dst_ip", "dport",
                                  "sport")})
            rings.tx.release()
        serve_s = time.perf_counter() - t0
        check(len(got) == n_frames,
              f"pump delivered {len(got)} of {n_frames} frames")
        for k, fr in enumerate(got):
            check(len(fr["disp"]) == FRAME, f"frame {k}: {len(fr['disp'])}")
            order = fr["sport"] - 20000 - k * FRAME
            check((order == np.arange(FRAME)).all(), f"frame {k} order")
            ok = kind < 2
            check((fr["disp"][ok] == int(Disposition.LOCAL)).all()
                  and (fr["rx_if"][ok] == server_if).all(),
                  f"frame {k}: permitted flows not delivered")
            check((fr["disp"][kind == 2] == int(Disposition.DROP)).all(),
                  f"frame {k}: the denied port was not dropped")
            v = kind == 3
            check((fr["disp"][v] == int(Disposition.LOCAL)).all()
                  and (fr["dst_ip"][v] == ip4(ips["server"])).all()
                  and (fr["dport"][v] == 80).all()
                  and (fr["rx_if"][v] == server_if).all(),
                  f"frame {k}: VIP traffic not DNAT'd to the backend")
        snap = dp.kernel_snapshot()
        if on_tpu:
            check(snap["session"]["impl"] == "pallas",
                  f"phase A session rung {snap['session']}")
        return {"frames": n_frames, "pkts": n_frames * FRAME,
                "boot_s": boot_s, "serve_s": serve_s,
                "kernels": {k: snap[k]["impl"]
                            for k in ("classifier", "fib", "session")},
                "pump": agent.io_pump.mode}
    finally:
        agent.close()
        ksr.close()


# --- Phase B: one node at size ------------------------------------------


def node_subnet(i: int) -> str:
    """Node i's /24 pod subnet (Contiv's podSubnetOneNodePrefixLen)."""
    return f"10.{1 + (i >> 8)}.{i & 255}.0/24"


def build_rules(n_rules: int):
    """Policy rule set shaped like tests/policy/perf/gen-policy.py:
    CIDR-block x port permits with interleaved deny excepts, then a
    terminal deny-all (the renderer-cache table form)."""
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


def build_node(knobs: dict, n_rules: int = N_RULES,
               n_nodes: int = N_NODES, sess_slots: int = SESS_SLOTS):
    """One node of a ``n_nodes`` cluster: local /32 pods, a /24 per
    peer node toward the uplink, a default route, a gen-policy-shaped
    global table and one VIP with ``N_BACKENDS`` weighted backends."""
    from vpp_tpu.ir.rule import Action, ContivRule, Protocol
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import Disposition, ip4

    config = DataplaneConfig(
        max_tables=2, max_rules=16, max_global_rules=n_rules,
        max_ifaces=N_PODS + 16, fib_slots=8192, sess_slots=sess_slots,
        nat_mappings=4, nat_backends=N_BACKENDS, **knobs)
    dp = Dataplane(config)
    uplink = dp.add_uplink()
    peers = np.array([i for i in range(n_nodes) if i != LOCAL_NODE],
                     np.int32)
    nets = np.array([int(ipaddress.ip_network(node_subnet(int(i)))
                         .network_address) for i in peers], np.uint32)
    dp.builder.add_routes_np(
        nets, np.full(len(peers), 24), np.full(len(peers), uplink),
        np.full(len(peers), int(Disposition.REMOTE)), node_id=peers)
    pods = []
    for k in range(N_PODS):
        idx = dp.add_pod_interface(("default", f"pod-{k}"))
        dp.builder.add_route(f"10.1.1.{k + 2}/32", idx, Disposition.LOCAL)
        pods.append(idx)
    dp.builder.add_route("0.0.0.0/0", uplink, Disposition.REMOTE)
    rules = [ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                        dest_port=80)] + build_rules(n_rules - 1)
    dp.builder.set_global_table(rules)
    dp.builder.set_nat_mapping(
        0, ext_ip=ip4(VIP), ext_port=80, proto=6,
        backends=[(ip4("10.1.1.2") + i, 80, 1 + (i % 2))
                  for i in range(N_BACKENDS)],
        boff=0)
    dp.swap()
    return dp, uplink, rules, pods


def node_traffic(n: int, uplink: int, seed: int, n_nodes: int = N_NODES):
    """Uplink TCP from the rule-space CIDR blocks: 70% to local pods,
    15% to peer nodes' pods, 15% to the VIP."""
    import jax.numpy as jnp

    from vpp_tpu.pipeline.vector import FLAG_VALID, PacketVector, ip4

    rng = np.random.default_rng(seed)
    block = rng.integers(0, 1000, n)
    src = ((172 << 24) | ((16 + block // 256) << 16)
           | ((block % 256) << 8) | rng.integers(1, 255, n))
    kind = rng.random(n)
    local = ip4("10.1.1.2") + rng.integers(0, N_PODS, n)
    peer = rng.integers(0, n_nodes - 1, n)
    peer = peer + (peer >= LOCAL_NODE)
    remote = ((10 << 24) | ((1 + (peer >> 8)) << 16) | ((peer & 255) << 8)
              | rng.integers(1, 255, n))
    dst = np.where(kind < 0.7, local, np.where(kind < 0.85, remote,
                                               ip4(VIP)))
    dport = np.where(kind < 0.85, 8000 + rng.integers(0, 20, n), 80)
    i32 = lambda a: jnp.asarray(np.asarray(a, np.int32))  # noqa: E731
    return PacketVector(
        src_ip=jnp.asarray(src.astype(np.uint32)),
        dst_ip=jnp.asarray(dst.astype(np.uint32)),
        proto=i32(np.full(n, 6)),
        sport=i32(rng.integers(1024, 65535, n)),
        dport=i32(dport),
        ttl=i32(np.full(n, 64)),
        pkt_len=i32(np.full(n, 512)),
        rx_if=i32(np.full(n, uplink)),
        flags=i32(np.full(n, FLAG_VALID)),
    )


def step_outputs(res) -> dict:
    """The per-packet results held bit-exact across rungs: disposition,
    drop reason, rewritten headers and the forwarding decision."""
    import jax

    return jax.device_get({
        "disp": res.disp, "drop_cause": res.drop_cause,
        "pkts": res.pkts, "tx_if": res.tx_if, "node_id": res.node_id,
        "next_hop": res.next_hop})


def run_steps(dp, batches, now0: int = 1):
    """Every batch once (the first call compiles), then the list again
    timed; returns (outputs per call, compile s, steady s per step)."""
    import jax

    outs = []
    t0 = time.perf_counter()
    res = dp.process(batches[0], now=now0)
    jax.block_until_ready(res.disp)
    compile_s = time.perf_counter() - t0
    outs.append(step_outputs(res))
    for k, b in enumerate(batches[1:], 1):
        outs.append(step_outputs(dp.process(b, now=now0 + k)))
    timed = []
    for k, b in enumerate(batches, len(batches)):
        t0 = time.perf_counter()
        res = dp.process(b, now=now0 + k)
        jax.block_until_ready(res.disp)
        timed.append(time.perf_counter() - t0)
        outs.append(step_outputs(res))
    return outs, compile_s, float(np.median(timed))


def oracle_check(out: dict, pkts, rules, pods, n_check: int) -> int:
    """The first batch (fresh sessions) against the rule oracle
    (ir/rule.rule_matches, first match wins) and the route table:
    non-VIP packets only, where the verdict is the ACL's alone."""
    from vpp_tpu.ir.rule import Action, Protocol, rule_matches
    from vpp_tpu.pipeline.graph import DROP_ACL
    from vpp_tpu.pipeline.vector import Disposition, ip4

    src = np.asarray(pkts.src_ip)
    dst = np.asarray(pkts.dst_ip)
    sport = np.asarray(pkts.sport)
    dport = np.asarray(pkts.dport)
    cand = np.nonzero(dst != ip4(VIP))[0][:n_check]
    for i in cand:
        s = str(ipaddress.ip_address(int(src[i])))
        d = str(ipaddress.ip_address(int(dst[i])))
        act = next(r.action for r in rules if rule_matches(
            r, s, d, Protocol.TCP, int(sport[i]), int(dport[i])))
        cause = int(out["drop_cause"][i])
        disp = int(out["disp"][i])
        if act == Action.DENY:
            check(disp == int(Disposition.DROP) and cause == DROP_ACL,
                  f"pkt {i} {s}->{d}:{dport[i]}: oracle denies, "
                  f"disp {disp} cause {cause}")
            continue
        check(cause != DROP_ACL, f"pkt {i}: oracle permits, ACL dropped")
        net = ipaddress.ip_address(d)
        if net in ipaddress.ip_network("10.1.1.0/24"):
            k = int(dst[i]) - ip4("10.1.1.2")
            check(disp == int(Disposition.LOCAL)
                  and int(out["tx_if"][i]) == pods[k],
                  f"pkt {i} -> {d}: disp {disp} tx_if {out['tx_if'][i]}")
        else:
            node = ((int(dst[i]) >> 16 & 255) - 1) * 256 \
                + (int(dst[i]) >> 8 & 255)
            check(disp == int(Disposition.REMOTE)
                  and int(out["node_id"][i]) == node,
                  f"pkt {i} -> {d}: disp {disp} node "
                  f"{out['node_id'][i]} want {node}")
    return len(cand)


def phase_b(on_tpu: bool, seed: int, keep: dict, n_pkts: int = STEP_PKTS,
            n_rules: int = N_RULES, n_nodes: int = N_NODES,
            sess_slots: int = SESS_SLOTS, n_batches: int = 3,
            n_check: int = 300) -> dict:
    """``keep`` receives the Pallas-rung node and its frames as soon as
    they exist, so Phase C runs even when this phase fails."""
    pal, uplink, rules, pods = build_node({}, n_rules, n_nodes, sess_slots)
    batches = [node_traffic(n_pkts, uplink, seed + k, n_nodes)
               for k in range(n_batches)]
    keep.update(dp=pal, batches=batches)
    snap = pal.kernel_snapshot()
    if on_tpu:
        check(snap["classifier"]["impl"] == "pallas"
              and snap["fib"]["impl"] == "pallas",
              f"phase B rungs {snap}")
    ref, _, _, _ = build_node(
        {"classifier": "bv", "fib_impl": "lpm", "session_impl": "gather"},
        n_rules, n_nodes, sess_slots)
    out_p, compile_p, step_p = run_steps(pal, batches)
    out_r, compile_r, step_r = run_steps(ref, batches)
    for k, (a, b) in enumerate(zip(out_p, out_r)):
        check_equal(a, b,
                    f"phase B call {k}: pallas rungs differ from jnp rungs")
    checked = oracle_check(out_p[0], batches[0], rules, pods, n_check)
    return {"kernels": {k: snap[k]["impl"]
                        for k in ("classifier", "fib", "session")},
            "kernels_ref": {k: ref.kernel_snapshot()[k]["impl"]
                            for k in ("classifier", "fib", "session")},
            "calls_bitexact": len(out_p), "oracle_pkts": checked,
            "pkts_per_step": n_pkts,
            "smoke_compile_s": {"pallas": compile_p, "jnp": compile_r},
            "smoke_step_s": {"pallas": step_p, "jnp": step_r}}


# --- Phase C: every Pallas kernel against its reference -----------------


def phase_c(seed: int, dp, pkts, interpret: bool = False,
            sess_sizes=(1 << 15, 1 << 18)) -> dict:
    import jax
    import jax.numpy as jnp

    from vpp_tpu.ops.acl_bv import (
        BV_ENC_MISS,
        _first_set_bit,
        bv_first_match,
        bv_first_match_fused,
        bv_first_set,
    )
    from vpp_tpu.ops.acl_mxu import (
        PLANES,
        mxu_first_match,
        mxu_first_match_reference,
    )
    from vpp_tpu.ops.lpm import (
        _lpm_stack,
        lpm_fused_lookup,
        lpm_fused_reference,
    )
    from vpp_tpu.ops.session import _probe_ways_reference, sess_probe_ways

    rng = np.random.default_rng(seed)
    p = int(pkts.src_ip.shape[0])
    done = {}
    t = dp.tables

    # bv_first_set at [P, W]: five sparse random bitmap rows
    w = int(t.glb_bv_src.shape[1])
    rows = [rng.integers(0, 1 << 32, (p, w), dtype=np.uint32)
            for _ in range(5)]
    for r in rows[1:]:
        r &= rng.integers(0, 1 << 32, (p, w), dtype=np.uint32)
    enc = np.asarray(bv_first_set(*map(jnp.asarray, rows),
                                  interpret=interpret))
    m, r = _first_set_bit(jnp.asarray(
        rows[0] & rows[1] & rows[2] & rows[3] & rows[4]))
    check(np.array_equal(enc != BV_ENC_MISS, np.asarray(m))
          and np.array_equal(np.where(enc != BV_ENC_MISS, enc, -1),
                             np.asarray(r)), "bv_first_set")
    done["bv_first_set"] = [p, w]

    # bv_first_match_fused on the staged Phase B planes
    args = (t.glb_bv_bnd_src, t.glb_bv_bnd_dst, t.glb_bv_bnd_sport,
            t.glb_bv_bnd_dport, t.glb_bv_nbnd, t.glb_bv_src, t.glb_bv_dst,
            t.glb_bv_sport, t.glb_bv_dport, t.glb_bv_proto, pkts)
    check_equal(bv_first_match_fused(*args, interpret=interpret),
                bv_first_match(*args), "bv_first_match_fused")
    done["bv_first_match_fused"] = [p, w]

    # mxu_first_match: 0/1 bit planes, small integer coefficients —
    # every partial sum is exact in f32, so any order agrees
    n_rules = int(t.glb_action.shape[0])
    bits = jnp.asarray(rng.integers(0, 2, (p, PLANES)), jnp.bfloat16)
    coeff = jnp.asarray(rng.integers(-1, 2, (PLANES, n_rules)),
                        jnp.bfloat16)
    k = jnp.asarray(rng.integers(-2, 3, n_rules), jnp.float32)
    check(np.array_equal(
        np.asarray(mxu_first_match(bits, coeff, k, interpret=interpret)),
        np.asarray(mxu_first_match_reference(bits, coeff, k))),
        "mxu_first_match")
    done["mxu_first_match"] = [p, n_rules]

    # sess_probe_ways: planted hits, expired plants and misses
    for slots in sess_sizes:
        ways = 4
        nb = slots // ways
        valid = (rng.random((nb, ways)) < 0.5).astype(np.int32)
        cols = [rng.integers(0, 1 << 32, (nb, ways), dtype=np.uint32)
                for _ in range(3)]
        proto = rng.integers(0, 256, (nb, ways)).astype(np.uint32)
        tm = rng.integers(0, 1000, (nb, ways)).astype(np.int32)
        b = rng.integers(0, nb, p).astype(np.int32)
        key = [rng.integers(0, 1 << 32, p, dtype=np.uint32)
               for _ in range(3)]
        key.append(rng.integers(0, 256, p).astype(np.uint32))
        plant = np.arange(0, p, 4)
        way = rng.integers(0, ways, len(plant))
        valid[b[plant], way] = 1
        for c, kk in zip(cols, key[:3]):
            c[b[plant], way] = kk[plant]
        proto[b[plant], way] = key[3][plant]
        tm[b[plant], way] = np.where(plant % 8 == 0, 100, 950)
        a = (b, *key, valid, *cols, proto, tm)
        a = tuple(jnp.asarray(x) for x in a)
        got = sess_probe_ways(*a, 1000, 200, interpret=interpret)
        want = _probe_ways_reference(*a, 1000, 200)
        check(bool(np.asarray(want[0]).any()), "session plants missed")
        check_equal(got, want, f"sess_probe_ways at {slots} slots")
        done[f"sess_probe_ways[{slots}]"] = [p, nb, ways]

    # lpm_fused_lookup on the staged Phase B planes
    stack = _lpm_stack(t)
    check(stack is not None, "phase B staged no LPM plane")
    got = lpm_fused_lookup(pkts.dst_ip, *stack, interpret=interpret)
    want = lpm_fused_reference(pkts.dst_ip, *stack)
    check_equal(got, want, "lpm_fused_lookup")
    check(int(np.sum(np.asarray(stack[1]) > 0)) >= 3,
          "fewer than 3 populated prefix lengths")
    done["lpm_fused_lookup"] = [p] + list(stack[2].shape)
    jax.block_until_ready(got)
    return done


# --- mesh phase (four chips) ---------------------------------------------


def _standalone(cluster, i: int):
    """A standalone single-chip Dataplane staged from cluster node i's
    builder (a copy: the device-array cache stays with the cluster)."""
    from vpp_tpu.pipeline.dataplane import Dataplane

    node = cluster.node(i)
    b = node.builder
    cache, b._dev_cache = b._dev_cache, {}
    try:
        nb = copy.deepcopy(b)
    finally:
        b._dev_cache = cache
    dp = Dataplane(node.config)
    dp.builder = nb
    dp.swap()
    return dp


def _host(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), tree)


def mesh_vs_standalone(runtime, frames, now: int) -> dict:
    """One cluster step against standalone Dataplanes replaying it:
    pass 1 on every sender, the fabric rows in all_to_all order
    (source-major, ``B`` = packets per node, zero padding), pass 2 at
    each destination on its uplink. Returns what was compared."""
    import jax

    from vpp_tpu.pipeline.vector import (
        FLAG_VALID,
        Disposition,
        PacketVector,
    )

    cluster = runtime.cluster
    n = len(runtime.agents)
    res = runtime.step(frames, now=now)
    jax.block_until_ready(res)
    devs = {s.device for s in res.delivered.disp.addressable_shards}
    check(len(devs) == n * cluster.mesh.shape["rule"],
          f"cluster result spans {len(devs)} devices")
    dps = [_standalone(cluster, i) for i in range(n)]
    host = jax.device_get(frames)
    pass1 = [dps[i].process(_host(jax.tree.map(lambda a: a[i], host)),
                            now=now) for i in range(n)]
    p1 = [jax.device_get(r) for r in pass1]
    bsz = int(host.src_ip.shape[1])
    for j in range(n):
        cols = {f: [] for f in PacketVector._fields}
        for i in range(n):
            sel = np.nonzero((p1[i].disp == int(Disposition.REMOTE))
                             & (p1[i].node_id == j))[0][:bsz]
            for f in PacketVector._fields:
                row = np.zeros(bsz, np.asarray(getattr(host, f)).dtype)
                if f == "flags":
                    row[:len(sel)] = FLAG_VALID
                else:
                    row[:len(sel)] = np.asarray(getattr(p1[i].pkts, f))[sel]
                cols[f].append(row)
        flat = {f: np.concatenate(v) for f, v in cols.items()}
        flat["rx_if"][:] = runtime.agents[j].uplink_if
        r2 = dps[j].process(_host(PacketVector(**flat)), now=now)
        want_local = (p1[j].pkts, p1[j].disp, p1[j].tx_if, p1[j].node_id,
                      p1[j].next_hop, p1[j].drop_cause)
        want_deliv = jax.device_get(
            (r2.pkts, r2.disp, r2.tx_if, r2.node_id, r2.next_hop,
             r2.drop_cause))
        got = jax.device_get(res)
        pick = lambda t: jax.tree.map(lambda a: a[j], t)  # noqa: E731
        check_equal(pick(tuple(got.local)), want_local,
                    f"node {j}: cluster pass 1 differs from standalone")
        check_equal(pick(tuple(got.delivered)), want_deliv,
                    f"node {j}: cluster delivery differs from standalone")
    local = int(Disposition.LOCAL)
    return {"pkts": n * bsz,
            "fabric_sent": int(np.sum(np.asarray(got.fabric_sent))),
            "local_pass1": int(np.sum(np.asarray(got.local.disp) == local)),
            "delivered": int(np.sum(np.asarray(got.delivered.disp)
                                    == local)),
            "devices": len(devs)}


def _boot_mesh(n_nodes: int, rule_shards: int):
    from vpp_tpu.cmd.config import AgentConfig
    from vpp_tpu.cmd.ksr_main import KsrAgent
    from vpp_tpu.cni.model import CNIRequest
    from vpp_tpu.ksr import model as m
    from vpp_tpu.kvstore.store import KVStore
    from vpp_tpu.parallel.runtime import MeshRuntime
    from vpp_tpu.pipeline.tables import DataplaneConfig

    store = KVStore()
    ksr = KsrAgent(store=store, serve_http=False)
    ksr.start()
    config = AgentConfig(
        node_name="mesh", serve_http=False,
        dataplane=DataplaneConfig(
            max_tables=4, max_rules=16, max_global_rules=1024,
            max_ifaces=16, fib_slots=64, nat_mappings=8, nat_backends=32))
    runtime = MeshRuntime(n_nodes, config, rule_shards=rule_shards,
                          store=store)
    runtime.start()
    pods = []
    for i, agent in enumerate(runtime.agents):
        for p in range(2):
            reply = agent.cni_server.add(CNIRequest(
                container_id=f"c-{i}-{p}",
                extra_args={"K8S_POD_NAME": f"pod-{i}-{p}",
                            "K8S_POD_NAMESPACE": "default"}))
            check(reply.result == 0, f"CNI add on node {i}")
            pods.append((i, f"pod-{i}-{p}", reply.interfaces[0]
                         .ip_addresses[0].address.split("/")[0]))
    ksr.sources[m.Service.TYPE].add("default/vip", m.Service(
        name="vip", namespace="default", cluster_ip=VIP,
        ports=[m.ServicePort(name="http", protocol="TCP", port=80,
                             target_port="http")]))
    ksr.sources[m.Endpoints.TYPE].add("default/vip", m.Endpoints(
        name="vip", namespace="default",
        subsets=[m.EndpointSubset(
            addresses=[m.EndpointAddress(
                ip=pods[0][2],
                node_name=runtime.agents[0].config.node_name)],
            ports=[m.EndpointPort(name="http", port=80,
                                  protocol="TCP")])]))
    return ksr, runtime, pods


def _mesh_frames(runtime, pods, seed: int, n: int = FRAME):
    """Every node: its first pod sends to every pod of the cluster
    (same node, peers over the fabric) and to the VIP."""
    rng = np.random.default_rng(seed)
    per_node = []
    for i, agent in enumerate(runtime.agents):
        src = next(p for p in pods if p[0] == i)
        rx_if = agent.dataplane.pod_if[("default", src[1])]
        dsts = [p[2] for p in pods] + [VIP]
        per_node.append([
            {"src": src[2], "dst": dsts[k % len(dsts)], "proto": 6,
             "sport": int(rng.integers(1024, 65535)),
             "dport": 80 if k % 3 else int(rng.integers(1, 65535)),
             "rx_if": rx_if}
            for k in range(n)])
    return runtime.make_frames(per_node, n=n)


def phase_mesh(seed: int) -> dict:
    import jax

    from vpp_tpu.ir.rule import Action, ContivRule, Protocol

    out = {}
    ksr, runtime, pods = _boot_mesh(4, 1)
    try:
        ids = {d.id for d in runtime.mesh.devices.flat}
        check(len(ids) == 4, f"mesh spans devices {sorted(ids)}")
        frames = _mesh_frames(runtime, pods, seed)
        out["nodes4"] = mesh_vs_standalone(runtime, frames, now=5)
        out["nodes4_devices"] = sorted(ids)
        out["nodes4_classifier"] = runtime.cluster.classifier_impl
    finally:
        runtime.close()
        ksr.close()

    ksr, runtime, pods = _boot_mesh(2, 2)
    try:
        cluster = runtime.cluster
        check(not cluster._use_mxu, "MXU selected before the big table")
        big = [ContivRule(action=Action.PERMIT, protocol=Protocol.TCP,
                          dest_port=1000 + i)
               for i in range(cluster.mxu_threshold + 8)]
        big.append(ContivRule(action=Action.PERMIT))
        node0 = runtime.agents[0].dataplane
        with node0._lock:
            node0.builder.set_global_table(big)
        node0.swap()
        check(cluster._use_mxu, "the big table did not select the MXU rung")
        frames = _mesh_frames(runtime, pods, seed + 1)
        out["nodes2x2"] = mesh_vs_standalone(runtime, frames, now=7)
        out["nodes2x2_devices"] = sorted(
            d.id for d in runtime.mesh.devices.flat)
        out["nodes2x2_classifier"] = cluster.classifier_impl
    finally:
        runtime.close()
        ksr.close()
    jax.effects_barrier()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four-chip host: run only the mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from vpp_tpu.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: run from the repository root ({e})",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found platform {platform!r} "
              f"({len(devs)} device(s)); this smoke needs a TPU",
              file=sys.stderr)
        return 2
    kind = devs[0].device_kind
    say(f"device: {platform} {kind} x{len(devs)}; compile cache "
        f"{cache_dir}")
    t_all = time.perf_counter()
    failed = []

    def phase(label: str, fn) -> None:
        try:
            say(label, json.dumps(fn()))
        except Exception as e:  # noqa: BLE001 — every phase reports
            failed.append(label)
            traceback.print_exc()
            say(f"{label} FAILED: {type(e).__name__}: {e}")

    if args.mesh:
        check(len(devs) >= 4, f"--mesh needs 4 chips, found {len(devs)}")
        phase("mesh:", lambda: phase_mesh(args.seed))
    else:
        phase("phase A (agent path, default config):",
              lambda: phase_a(on_tpu=True))
        keep = {}
        phase("phase B (one node at size; times are a smoke reading, "
              "not a benchmark):",
              lambda: phase_b(on_tpu=True, seed=args.seed, keep=keep))
        if keep:
            phase("phase C (Pallas kernels, interpret=False, bit-exact):",
                  lambda: phase_c(args.seed, keep["dp"],
                                  keep["batches"][0]))
        else:
            failed.append("phase C (phase B built no node)")
    say(f"smoke seconds: {time.perf_counter() - t_all:.1f}")
    if failed:
        say(f"chip_smoke: failed phases: {failed}")
        return 1

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
