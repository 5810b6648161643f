"""Plain reference of a Kubernetes node's forwarding semantics, for the
configurations whose ``system`` is ``node``. It imports nothing of the
program: the policy, the routes and the service are rebuilt here from
the configuration's own numbers, and each packet is evaluated one rule
and one route at a time in the order the semantics state.

Per packet arriving at the node, in the order the packets arrive:

1. ip4-input: the TTL is decremented (RFC 1812).
2. NAT44 reverse: a backend's reply to a flow that was DNAT'd and
   forwarded has its source rewritten back to the VIP's address and
   port (the NAT session of that flow).
3. NAT44 DNAT: a packet to the VIP's address, port and protocol is
   rewritten to one of the VIP's backends (address and port), the same
   backend for every packet of a flow.
4. Policy: on the uplink the global table applies, first match wins,
   a terminal deny closes it; a packet from a local pod meets no table.
5. FIB: longest prefix match on the rewritten destination. A local /32
   delivers to the pod's interface; a peer node's /24 leaves on the
   uplink toward that node's VXLAN endpoint; the default route leaves
   on the uplink with no next hop.

The reference keeps the NAT sessions and each flow's backend without
bound: the check asks it only about flows recent enough that the
program's table must still hold them (``benchmark/check.py``). Which
backend a new flow gets is left open by the semantics; the weights
(``backend_weights``) say how often each is due.

``control`` breaks one guarantee the configuration states, for the
control run: ``no_deny`` ignores every deny rule (policy not enforced),
``no_ttl`` forwards without the TTL decrement.
"""

from __future__ import annotations

import ipaddress
from typing import Dict, Optional

import numpy as np

DROP, LOCAL, REMOTE = 0, 1, 2
PROTO = {"tcp": 6, "udp": 17, "any": -1}


def _ip(s: str) -> int:
    return int(ipaddress.ip_address(s))


def rule_table(cfg: Dict) -> Dict[str, np.ndarray]:
    """The global policy as rows: src net/mask, proto, dport, permit."""
    p = cfg.get("policy")
    rows = []
    if p:
        first = p.get("first")
        if first:
            rows.append((0, 0, PROTO[first["proto"]], int(first["dport"]),
                         first["action"] == "permit"))
        base = _ip(p["block_base"])
        for i in range(int(p["rules"]) - len(rows) - 1):
            block = i % int(p["blocks"])
            port = int(p["port_base"]) + (i // int(p["blocks"])) \
                % int(p["ports"])
            deny = i % int(p["deny_every"]) == int(p["deny_every"]) - 1
            rows.append((base + (block << 8), 0xFFFFFF00, 6, port, not deny))
        rows.append((0, 0, -1, 0, False))
    if not rows:
        return {}
    a = np.array(rows, np.int64)
    return {"net": a[:, 0], "mask": a[:, 1], "proto": a[:, 2],
            "dport": a[:, 3], "permit": a[:, 4].astype(bool)}


def first_match(rules: Dict[str, np.ndarray], src, proto, dport,
                chunk: int = 512) -> np.ndarray:
    """Index of the first rule each packet matches (-1: none)."""
    out = np.full(len(src), -1, np.int64)
    for s in range(0, len(src), chunk):
        sl = slice(s, s + chunk)
        m = (((src[sl, None] & rules["mask"][None]) == rules["net"][None])
             & ((rules["proto"][None] < 0)
                | (proto[sl, None] == rules["proto"][None]))
             & ((rules["dport"][None] == 0)
                | (dport[sl, None] == rules["dport"][None])))
        hit = m.any(axis=1)
        out[sl] = np.where(hit, m.argmax(axis=1), -1)
    return out


def routes(cfg: Dict, world: Dict) -> list:
    """(net, plen, disp, tx_if, next_hop) of every route, unordered."""
    out = [(0, 0, REMOTE, world["uplink_if"], 0)]
    local = int(cfg["local_node"])
    node_net = _ip(cfg["node_net"])
    if int(cfg["cluster_nodes"]) > 1:
        vtep = _ip(cfg["vtep_net"])
        for i in range(int(cfg["cluster_nodes"])):
            if i != local:
                out.append((node_net + (i << 8), 24, REMOTE,
                            world["uplink_if"], vtep + 1 + i))
    for addr, idx in zip(world["pod_ip"], world["pod_if"]):
        out.append((int(addr), 32, LOCAL, int(idx), 0))
    return out


def lpm(route_list: list, dst: np.ndarray) -> tuple:
    """(disp, tx_if, next_hop) of the longest prefix matching each dst."""
    n = len(dst)
    disp = np.full(n, DROP, np.int64)
    tx_if = np.full(n, -1, np.int64)
    nh = np.zeros(n, np.int64)
    done = np.zeros(n, bool)
    for plen in sorted({r[1] for r in route_list}, reverse=True):
        mask = ((0xFFFFFFFF << (32 - plen)) & 0xFFFFFFFF) if plen else 0
        rs = sorted((r for r in route_list if r[1] == plen),
                    key=lambda r: r[0])
        nets = np.array([r[0] for r in rs], np.int64)
        key = dst & mask
        j = np.searchsorted(nets, key).clip(0, len(nets) - 1)
        hit = ~done & (nets[j] == key)
        for col, arr in ((2, disp), (3, tx_if), (4, nh)):
            vals = np.array([r[col] for r in rs], np.int64)
            arr[hit] = vals[j[hit]]
        done |= hit
    return disp, tx_if, nh


class Reference:
    def __init__(self, cfg: Dict, world: Dict, control: Optional[str] = None):
        self.cfg = cfg
        self.world = world
        self.control = control
        self.rules = rule_table(cfg)
        self.routes = routes(cfg, world)
        vip = cfg.get("vip")
        self.vip = None
        if vip:
            n = int(vip["backends"])
            pods = world["pod_ip"]
            self.vip = (_ip(vip["ip"]), int(vip["port"]), PROTO[vip["proto"]],
                        np.array([pods[i % len(pods)] for i in range(n)],
                                 np.int64), int(vip["port"]))
        # reply 5-tuple -> (VIP, port); forward 5-tuple -> backend
        self.nat: Dict[tuple, tuple] = {}
        self.flow_backend: Dict[tuple, int] = {}

    def backend_weights(self) -> Optional[np.ndarray]:
        """Weight of each VIP backend, in the backends' order."""
        if self.vip is None:
            return None
        w = self.cfg["vip"]["weights"]
        return np.array([w[i % len(w)] for i in range(len(self.vip[3]))],
                        np.float64)

    def expected(self, f: Dict[str, np.ndarray],
                 served_dst: Optional[np.ndarray] = None) -> Dict:
        """Expected tx-ring columns for packets with header fields ``f``,
        which arrive after every packet of earlier calls. A new VIP
        flow's backend is the one the program chose when that choice is
        a backend of the VIP (the semantics leave the choice open);
        otherwise the first backend, which then fails the check."""
        src = f["src_ip"].astype(np.int64)
        dst = f["dst_ip"].astype(np.int64)
        proto = f["proto"].astype(np.int64)
        sport = f["sport"].astype(np.int64)
        dport = f["dport"].astype(np.int64)
        ttl = f["ttl"].astype(np.int64)
        ttl = ttl if self.control == "no_ttl" else ttl - 1
        n = len(src)
        to_vip = np.zeros(n, bool)
        if self.vip is not None:
            vip_ip, vip_port, vip_proto, backends, bport = self.vip
            back = [self.nat.get(t) for t in
                    zip(src.tolist(), sport.tolist(), dst.tolist(),
                        dport.tolist(), proto.tolist())]
            rev = np.array([b is not None for b in back], bool)
            if rev.any():
                src = src.copy()
                sport = sport.copy()
                src[rev] = [b[0] for b in back if b is not None]
                sport[rev] = [b[1] for b in back if b is not None]
            to_vip = ~rev & (dst == vip_ip) & (dport == vip_port) \
                & (proto == vip_proto)
            pick = np.full(n, backends[0])
            if served_dst is not None:
                ok = np.isin(served_dst.astype(np.int64), backends)
                pick = np.where(ok, served_dst.astype(np.int64), pick)
            keys = list(zip(src.tolist(), sport.tolist(), dst.tolist(),
                            dport.tolist(), proto.tolist()))
            for i in np.nonzero(to_vip)[0]:
                pick[i] = self.flow_backend.get(keys[i], pick[i])
            dst = np.where(to_vip, pick, dst)
            dport = np.where(to_vip, bport, dport)
        permit = np.ones(len(src), bool)
        if self.rules:
            on_uplink = f["rx_if"] == self.world["uplink_if"]
            idx = first_match(self.rules, src, proto, dport)
            permit_rule = np.where(idx >= 0, self.rules["permit"][idx], False)
            if self.control == "no_deny":
                permit_rule = np.ones_like(permit_rule)
            permit = ~on_uplink | permit_rule
        disp, tx_if, nh = lpm(self.routes, dst)
        disp = np.where(permit, disp, DROP)
        tx_if = np.where(permit, tx_if, -1)
        nh = np.where(permit, nh, 0)
        for i in np.nonzero(to_vip & (disp != DROP))[0]:
            fwd = keys[i]
            self.flow_backend[fwd] = int(dst[i])
            self.nat[(int(dst[i]), int(dport[i]), fwd[0], fwd[1],
                      fwd[4])] = (fwd[2], fwd[3])
        return {"src_ip": src, "dst_ip": dst, "proto": proto,
                "sport": sport, "dport": dport, "ttl": ttl,
                "disp": disp, "rx_if": tx_if, "next_hop": nh}
