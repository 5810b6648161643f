"""Plain reference of a node whose namespaces carry egress NetworkPolicies,
for the configurations whose ``system`` is ``nsegress``. It imports
nothing of the program: the policies are read from the configuration's
own numbers, never from the renderer's rule lists, and forwarding reuses
``node_reference``'s routes and longest-prefix match.

Per packet arriving at the node from a local pod:

1. ip4-input: the TTL is decremented (RFC 1812).
2. Egress policy (Kubernetes NetworkPolicy semantics): a pod selected by
   an egress policy may send only what some rule of its policies
   allows. Each namespace's one policy selects all of its pods and has
   one rule: the union of its ipBlocks, each block minus its excepts,
   on the policy's TCP ports. A pod of the unisolated namespace is not
   restricted.
3. FIB: longest prefix match on the destination, as in
   ``node_reference``.

``control`` ``no_deny`` ignores the policies (every packet allowed).
"""

from __future__ import annotations

import ipaddress
from pathlib import Path
from typing import Dict, Optional

import numpy as np

_HERE = Path(__file__).resolve().parent
PROTO = {"tcp": 6, "udp": 17}


def _node_reference():
    from benchmark.spec import load_module

    return load_module(_HERE / "node_reference.py")


def pod_namespace_index(cfg: Dict) -> np.ndarray:
    """Policy namespace of each pod by pod index, -1 for unisolated."""
    ns = cfg["namespaces"]
    k = np.arange(int(cfg["pods"]))
    return np.where(k < int(ns["isolated_pods"]), k % int(ns["policy"]), -1)


def allowed(cfg: Dict, ns: np.ndarray, dst: np.ndarray, proto: np.ndarray,
            dport: np.ndarray) -> np.ndarray:
    """Whether a pod of policy namespace ``ns`` (-1: unisolated) may send
    each packet: in some block of its policy (the /24 of peer node
    first + stride * ns + b, b < blocks) and in none of that block's
    excepts, TCP to one of the policy's ports."""
    e = cfg["egress_policy"]
    base = int(ipaddress.ip_address(cfg["node_net"]))
    dst = dst.astype(np.int64)
    # the node whose /24 holds dst, and dst's host offset inside it
    node = (dst - base) >> 8
    host = (dst - base) & 0xFF
    first = int(e["first_peer_node"]) + int(e["node_stride"]) * ns
    in_block = (((dst - base) >= 0) & (node >= first)
                & (node < first + int(e["blocks"])))
    size = 1 << (32 - int(e["except_plen"]))
    in_except = np.zeros(len(dst), bool)
    for off in e["except_offsets"]:
        in_except |= (host >= int(off)) & (host < int(off) + size)
    port_ok = ((dport >= int(e["port_base"]))
               & (dport < int(e["port_base"]) + int(e["ports"])))
    ok = in_block & ~in_except & (proto == PROTO[e["proto"]]) & port_ok
    return (ns < 0) | ok


class Reference:
    def __init__(self, cfg: Dict, world: Dict, control: Optional[str] = None):
        self.cfg = cfg
        self.world = world
        self.control = control
        nr = _node_reference()
        self.lpm = nr.lpm
        self.routes = nr.routes(cfg, world)
        self.DROP = nr.DROP
        self.pod_ns = pod_namespace_index(cfg)
        self.vip = None

    def backend_weights(self):
        return None

    def expected(self, f: Dict[str, np.ndarray],
                 served_dst: Optional[np.ndarray] = None) -> Dict:
        """Expected tx-ring columns for packets with header fields ``f``
        (the generator's, with ``src_pod``: every packet comes from a
        local pod)."""
        src = f["src_ip"].astype(np.int64)
        dst = f["dst_ip"].astype(np.int64)
        proto = f["proto"].astype(np.int64)
        sport = f["sport"].astype(np.int64)
        dport = f["dport"].astype(np.int64)
        ttl = f["ttl"].astype(np.int64) - 1
        ns = self.pod_ns[f["src_pod"].astype(np.int64)]
        permit = allowed(self.cfg, ns, dst, proto, dport)
        if self.control == "no_deny":
            permit = np.ones_like(permit)
        disp, tx_if, nh = self.lpm(self.routes, dst)
        disp = np.where(permit, disp, self.DROP)
        tx_if = np.where(permit, tx_if, -1)
        nh = np.where(permit, nh, 0)
        return {"src_ip": src, "dst_ip": dst, "proto": proto,
                "sport": sport, "dport": dport, "ttl": ttl,
                "disp": disp, "rx_if": tx_if, "next_hop": nh}
