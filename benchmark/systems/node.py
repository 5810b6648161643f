"""One Kubernetes worker node's data plane, built from a configuration
file: the program's ``Dataplane`` with its tables staged through the
program's own TableBuilder, and the address plan (``world``) the traffic
generator and the reference share.

The policy is the gen-policy.py shape the program's own benchmark used
(CIDR block x port permits with a deny every ``deny_every``-th rule,
then a terminal deny), the FIB holds a /24 per peer node toward the
uplink plus a /32 per local pod and a default route, and one ClusterIP
VIP maps to weighted local backends.
"""

from __future__ import annotations

import ipaddress
from typing import Dict

import numpy as np


def ip(s: str) -> int:
    return int(ipaddress.ip_address(s))


def policy_rules(p: Dict):
    """The global rule list, in the program's rule type."""
    from vpp_tpu.ir.rule import Action, ContivRule, Protocol

    rules = []
    first = p.get("first")
    if first:
        rules.append(ContivRule(action=Action[first["action"].upper()],
                                protocol=Protocol[first["proto"].upper()],
                                dest_port=int(first["dport"])))
    n_gen = int(p["rules"]) - len(rules) - 1
    base = ip(p["block_base"])
    for i in range(n_gen):
        block = i % int(p["blocks"])
        port = int(p["port_base"]) + (i // int(p["blocks"])) % int(p["ports"])
        net = ipaddress.ip_network((base + (block << 8), 24))
        deny = i % int(p["deny_every"]) == int(p["deny_every"]) - 1
        rules.append(ContivRule(action=Action.DENY if deny else Action.PERMIT,
                                src_network=net, protocol=Protocol.TCP,
                                dest_port=port))
    rules.append(ContivRule(action=Action.DENY))
    return rules


def io_config(cfg: Dict):
    """The agent's IOConfig with the configuration's overrides (none in
    a configuration that serves at the agent defaults)."""
    from vpp_tpu.cmd.config import IOConfig

    return IOConfig(enabled=True, **cfg.get("io", {}))


def build(cfg: Dict):
    """-> (dataplane, world). The world is the node's wiring and address
    plan: interface indices the program assigned, pod and VIP addresses."""
    from vpp_tpu.pipeline.dataplane import Dataplane
    from vpp_tpu.pipeline.tables import DataplaneConfig
    from vpp_tpu.pipeline.vector import Disposition

    dp = Dataplane(DataplaneConfig(**cfg["dataplane"]))
    uplink = dp.add_uplink()
    local = int(cfg["local_node"])
    node_net = ip(cfg["node_net"])
    peers = np.array([i for i in range(int(cfg["cluster_nodes"]))
                      if i != local], np.int32)
    if len(peers):
        vtep = ip(cfg["vtep_net"])
        dp.builder.add_routes_np(
            (node_net + (peers.astype(np.int64) << 8)).astype(np.uint32),
            np.full(len(peers), 24), np.full(len(peers), uplink),
            np.full(len(peers), int(Disposition.REMOTE)),
            next_hop=(vtep + 1 + peers.astype(np.int64)).astype(np.uint32),
            node_id=peers)
    pod_base = node_net + (local << 8) + int(cfg["pod_host_base"])
    pod_ip, pod_if = [], []
    for k in range(int(cfg["pods"])):
        idx = dp.add_pod_interface(("default", f"pod-{k}"))
        addr = pod_base + k
        dp.builder.add_route(f"{ipaddress.ip_address(addr)}/32", idx,
                             Disposition.LOCAL)
        pod_ip.append(addr)
        pod_if.append(idx)
    dp.builder.add_route("0.0.0.0/0", uplink, Disposition.REMOTE)
    if cfg.get("policy"):
        dp.builder.set_global_table(policy_rules(cfg["policy"]))
    vip = cfg.get("vip")
    if vip:
        w = vip["weights"]
        dp.builder.set_nat_mapping(
            0, ext_ip=ip(vip["ip"]), ext_port=int(vip["port"]),
            proto=6 if vip["proto"] == "tcp" else 17,
            backends=[(pod_ip[i % len(pod_ip)], int(vip["port"]),
                       int(w[i % len(w)]))
                      for i in range(int(vip["backends"]))],
            boff=0)
    dp.swap()
    world = {
        "uplink_if": int(uplink),
        # the pod gateway address ICMP errors originate from
        "gateway": node_net + (local << 8) + 1,
        "pod_ip": pod_ip,
        "pod_if": pod_if,
        "peer_nodes": peers.tolist(),
        "node_net_base": node_net,
        "vip": (ip(vip["ip"]), int(vip["port"])) if vip else (0, 0),
    }
    if cfg.get("policy"):
        world["outside_blocks"] = {"base": ip(cfg["policy"]["block_base"]),
                                   "count": int(cfg["policy"]["blocks"]),
                                   "hosts": int(cfg["outside_hosts"])}
    return dp, world


def rungs(dp) -> Dict[str, str]:
    """Which kernel rung each ladder serves (classifier, fib, session)."""
    snap = dp.kernel_snapshot()
    return {k: snap[k]["impl"] for k in ("classifier", "fib", "session")}


def staged_shapes(dp) -> Dict[str, list]:
    """Shapes the per-layer byte counts are computed from."""
    t = dp.tables
    return {"glb_bv_src": list(t.glb_bv_src.shape),
            "sess_valid": list(t.sess_valid.shape)}
