"""A Kubernetes worker node whose namespaces carry egress NetworkPolicies,
rendered through the program's policy path.

The node itself is ``node.py``'s (uplink, a /24 route per peer node, a
/32 per local pod, a default route). Its pods are then spread over the
configuration's namespaces, and the Kubernetes objects (namespaces,
pods, one ``Egress`` NetworkPolicy per policy namespace in
gen-policy.py's shape: ipBlocks with excepts x TCP ports) reach the
program the way an agent's start-up sees them: one datasync resync of
the ``PolicyCache``, through ``PolicyProcessor`` -> ``PolicyConfigurator``
-> ``TpuRenderer`` -> TableBuilder and one epoch swap. Each policy
namespace ends up with its own local table, picked per packet by the
sending pod's interface.

The configuration states a render budget: if the render has not
returned within ``render_budget_s`` seconds, the process exits non-zero
with that message instead of serving late.
"""

from __future__ import annotations

import ipaddress
import os
import sys
import threading
import time
import weakref
from pathlib import Path
from typing import Dict, List

_HERE = Path(__file__).resolve().parent
# dataplane -> the PolicyConfigurator that rendered into it
_CONFIGURATORS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _node():
    from benchmark.spec import load_module

    return load_module(_HERE / "node.py")


def io_config(cfg: Dict):
    return _node().io_config(cfg)


def pod_namespaces(cfg: Dict) -> List[str]:
    """Namespace of each pod, by pod index: the first ``isolated_pods``
    round-robin over the policy namespaces, the rest unisolated."""
    ns = cfg["namespaces"]
    n = int(ns["policy"])
    iso = int(ns["isolated_pods"])
    return [f"ns{k % n}" if k < iso else ns["unisolated"]
            for k in range(int(cfg["pods"]))]


def policy_blocks(cfg: Dict, j: int) -> List[tuple]:
    """(block CIDR, [except CIDRs]) of namespace ``j``'s policy: the /24
    pod subnets of consecutive peer nodes, each less the /N excepts at
    the stated host offsets."""
    e = cfg["egress_policy"]
    base = int(ipaddress.ip_address(cfg["node_net"]))
    out = []
    for b in range(int(e["blocks"])):
        node = int(e["first_peer_node"]) + int(e["node_stride"]) * j + b
        net = base + (node << 8)
        excepts = [f"{ipaddress.ip_address(net + off)}/{e['except_plen']}"
                   for off in e["except_offsets"]]
        out.append((f"{ipaddress.ip_address(net)}/24", excepts))
    return out


def policy_peer_nodes(cfg: Dict) -> List[int]:
    """Every peer node whose pod subnet some policy names."""
    e = cfg["egress_policy"]
    first, stride = int(e["first_peer_node"]), int(e["node_stride"])
    n = int(cfg["namespaces"]["policy"])
    return sorted({first + stride * j + b for j in range(n)
                   for b in range(int(e["blocks"]))})


def k8s_objects(cfg: Dict, pod_ip: List[int]):
    """(namespaces, pods, policies) as the Kubernetes state reflector
    hands them to the agent."""
    from vpp_tpu.ksr import model as m

    e = cfg["egress_policy"]
    names = pod_namespaces(cfg)
    n = int(cfg["namespaces"]["policy"])
    namespaces = [m.Namespace(name=f"ns{j}", labels={"tenant": f"ns{j}"})
                  for j in range(n)]
    namespaces.append(m.Namespace(name=cfg["namespaces"]["unisolated"]))
    pods = [m.Pod(name=f"pod-{k}", namespace=ns, labels={"app": "client"},
                  ip_address=str(ipaddress.ip_address(pod_ip[k])))
            for k, ns in enumerate(names)]
    ports = [m.PolicyPort(protocol=e["proto"].upper(), port=p)
             for p in range(int(e["port_base"]),
                            int(e["port_base"]) + int(e["ports"]))]
    policies = [
        m.Policy(
            name="egress", namespace=f"ns{j}", pods=m.LabelSelector(),
            policy_type=m.POLICY_EGRESS,
            egress_rules=[m.PolicyRule(ports=ports, peers=[
                m.PolicyPeer(ip_block=m.IPBlock(cidr=cidr,
                                                except_cidrs=excepts))
                for cidr, excepts in policy_blocks(cfg, j)])])
        for j in range(n)]
    return namespaces, pods, policies


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build(cfg: Dict):
    """-> (dataplane, world): node.py's node, its pods re-homed into
    their namespaces, and the namespaces' policies rendered."""
    from vpp_tpu.ir.rule import PodID
    from vpp_tpu.policy import PolicyCache, PolicyConfigurator, PolicyProcessor
    from vpp_tpu.renderer.tpu import TpuRenderer

    dp, world = _node().build(dict(cfg, policy=None, vip=None))
    names = pod_namespaces(cfg)
    for k, ns in enumerate(names):
        old = PodID("default", f"pod-{k}")
        new = PodID(ns, f"pod-{k}")
        if new != old:
            idx = dp.pod_if[old]
            dp.del_pod_interface(old)
            if dp.add_pod_interface(new) != idx:
                raise RuntimeError(f"pod {k} changed interface")
    cache = PolicyCache()
    configurator = PolicyConfigurator(cache)
    configurator.register_renderer(TpuRenderer(dp))
    PolicyProcessor(cache, configurator)
    namespaces, pods, policies = k8s_objects(cfg, world["pod_ip"])

    budget = float(cfg["render_budget_s"])

    def expired():
        _say(f"policy render exceeded its budget of {budget:.0f} s: the "
             f"node's policy did not take effect in time")
        os._exit(3)

    watchdog = threading.Timer(budget, expired)
    watchdog.daemon = True
    t0 = time.perf_counter()
    watchdog.start()
    try:
        cache.resync(pods, policies, namespaces)
    finally:
        watchdog.cancel()
    wall = time.perf_counter() - t0
    _CONFIGURATORS[dp] = configurator
    slots = len(dp.table_slots)
    _say(f"policy render: {len(policies)} policies over {len(pods)} pods "
         f"in {wall:.3f} s, {slots} local tables of "
         f"{sorted(set(int(n) for n in dp.builder.acl_nrules if n))} rules")
    world["peer_nodes"] = policy_peer_nodes(cfg)
    return dp, world


def rungs(dp) -> Dict[str, object]:
    """Kernel rungs of each ladder, and the last policy commit's render
    seconds (the configurator's counter; absent on a program without
    it)."""
    out: Dict[str, object] = dict(_node().rungs(dp))
    ms = getattr(_CONFIGURATORS.get(dp), "render_ms", None)
    if ms is not None:
        out["policy_render_s"] = float(ms) / 1e3
    return out


def staged_shapes(dp) -> Dict[str, list]:
    """Shapes the per-layer byte counts are computed from."""
    t = dp.tables
    return {"acl_bv_src": list(t.acl_bv_src.shape),
            "sess_valid": list(t.sess_valid.shape)}
