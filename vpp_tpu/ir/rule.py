"""ContivRule: the canonical 5-tuple policy rule with a total order.

This is the most basic policy rule definition that every renderer (and the
TPU data plane) must support, together with the total order used to keep
rule tables sorted most-specific-first.

Reference semantics: plugins/policy/renderer/api.go:65-136 (ContivRule,
Compare) and plugins/policy/utils/utils.go (CompareIPNets, ComparePorts).
Re-designed for Python: networks are ``ipaddress.IPv4Network`` /
``IPv6Network`` instances or ``None`` for "match all".
"""

from __future__ import annotations

import enum
import functools
import ipaddress
from dataclasses import dataclass
from typing import List, NamedTuple, Optional, Union

IPNetwork = Union[ipaddress.IPv4Network, ipaddress.IPv6Network]

# Port number 0 stands for "any port".
ANY_PORT = 0


class PodID(NamedTuple):
    """Identifier of a pod: (namespace, name).

    Reference: plugins/ksr/model/pod/keyer.go (podmodel.ID).
    """

    namespace: str
    name: str

    def __str__(self) -> str:  # "<ns>/<name>" form used in ETCD keys and logs
        return f"{self.namespace}/{self.name}"

    @classmethod
    def parse(cls, s: str) -> "PodID":
        ns, _, name = s.partition("/")
        return cls(ns, name)


class Action(enum.IntEnum):
    """Rule action. Reference: renderer/api.go:139-147."""

    DENY = 0
    PERMIT = 1


class Protocol(enum.IntEnum):
    """L4 protocol of a rule. Reference: renderer/api.go:161-169.

    The reference's renderer layer only distinguishes TCP/UDP (ICMP and
    OTHER are handled by explicit appended rules in the ACL renderer); we
    additionally carry ANY/ICMP through the IR so the TPU tables can encode
    them natively rather than via renderer-specific appendices.
    """

    TCP = 0
    UDP = 1
    ICMP = 2
    ANY = 3

    @property
    def ip_proto(self) -> int:
        """IANA protocol number (ANY has none; returns -1)."""
        return {Protocol.TCP: 6, Protocol.UDP: 17, Protocol.ICMP: 1}.get(self, -1)


@dataclass(frozen=True)
class ContivRule:
    """An n-tuple rule: action + L3 src/dst networks + L4 protocol/ports.

    ``src_network``/``dest_network`` of ``None`` and port ``0`` mean
    "match all". Instances are immutable and hashable so they can be used
    directly as dict keys (the renderer cache dedups tables by rule lists).

    Reference: plugins/policy/renderer/api.go:65-77.
    """

    action: Action
    src_network: Optional[IPNetwork] = None
    dest_network: Optional[IPNetwork] = None
    protocol: Protocol = Protocol.TCP
    src_port: int = ANY_PORT
    dest_port: int = ANY_PORT

    def __str__(self) -> str:
        src = str(self.src_network) if self.src_network is not None else "ANY"
        dst = str(self.dest_network) if self.dest_network is not None else "ANY"
        sp = str(self.src_port) if self.src_port else "ANY"
        dp = str(self.dest_port) if self.dest_port else "ANY"
        return (
            f"Rule <{self.action.name} {src}[{self.protocol.name}:{sp}]"
            f" -> {dst}[{self.protocol.name}:{dp}]>"
        )

    # Total order (see compare_rules); enables `sorted(rules)`.
    def __lt__(self, other: "ContivRule") -> bool:
        return self.sort_key < other.sort_key

    @functools.cached_property
    def sort_key(self) -> tuple:
        """The key of the rules' total order: if a matches a subset of
        b's traffic, ``a.sort_key < b.sort_key``; equal keys are equal
        rules. Order of significance: protocol, src net, dst net, src
        port, dst port, action (reference: renderer/api.go:110-136).
        Computed once per rule, so sorting and bisecting a 10k-rule
        table compares tuples in C."""
        return (int(self.protocol), _net_key(self.src_network),
                _net_key(self.dest_network), _port_key(self.src_port),
                _port_key(self.dest_port), int(self.action))


def _net_key(net: Optional[IPNetwork]) -> tuple:
    # a ⊂ b ⇒ a first: IPv4 before IPv6, longer prefix first, then by
    # address (a total order over disjoint subnets); None (0/0) last
    if net is None:
        return (2, 0, 0)
    return (0 if net.version == 4 else 1, -net.prefixlen,
            int(net.network_address))


def _port_key(port: int) -> tuple:
    # 0 (= all ports) after every specific port
    return (1, 0) if port == ANY_PORT else (0, port)


def compare_ints(a, b) -> int:
    return (a > b) - (a < b)


def compare_ports(a: int, b: int) -> int:
    """Port order: 0 (= all ports) is *higher* than any specific port.

    Reference: plugins/policy/utils/utils.go ComparePorts.
    """
    return compare_ints(_port_key(a), _port_key(b))


def compare_ip_nets(a: Optional[IPNetwork], b: Optional[IPNetwork]) -> int:
    """Network order such that a ⊂ b ⇒ a < b; None (= 0/0) is the maximum.

    Reference: plugins/policy/utils/utils.go CompareIPNets.
    """
    return compare_ints(_net_key(a), _net_key(b))


def compare_rules(a: ContivRule, b: ContivRule) -> int:
    """Total order over rules (``ContivRule.sort_key``): if a matches a
    subset of b's traffic, a < b."""
    return compare_ints(a.sort_key, b.sort_key)


def compare_rule_lists(a: List[ContivRule], b: List[ContivRule]) -> int:
    """Lexicographic order over sorted rule lists (used for table dedup):
    one list comparison, which skips shared rule objects by identity and
    orders the first differing pair by ``sort_key``."""
    return compare_ints(a, b)


def allow_all_tcp() -> ContivRule:
    """PERMIT ANY->ANY TCP. Reference: cache_impl.go allowAllTCP."""
    return ContivRule(action=Action.PERMIT, protocol=Protocol.TCP)


def allow_all_udp() -> ContivRule:
    """PERMIT ANY->ANY UDP. Reference: cache_impl.go allowAllUDP."""
    return ContivRule(action=Action.PERMIT, protocol=Protocol.UDP)


def one_host_subnet(addr: str) -> IPNetwork:
    """The /32 (or /128) subnet containing only the given host address.

    Reference: plugins/policy/utils/utils.go GetOneHostSubnet.
    """
    ip = ipaddress.ip_address(addr)
    return ipaddress.ip_network(f"{ip}/{ip.max_prefixlen}")


def rule_matches(
    rule: ContivRule,
    src_ip: str,
    dst_ip: str,
    protocol: Protocol,
    src_port: int,
    dst_port: int,
) -> bool:
    """Pure-Python oracle: does the rule match the given 5-tuple?

    Used by tests and the mock classification engine to cross-check the
    TPU kernels (the reference's analog is mock/aclengine).
    """
    if rule.protocol != Protocol.ANY and protocol != rule.protocol:
        return False
    if rule.src_network is not None and ipaddress.ip_address(src_ip) not in rule.src_network:
        return False
    if rule.dest_network is not None and ipaddress.ip_address(dst_ip) not in rule.dest_network:
        return False
    if rule.src_port != ANY_PORT and src_port != rule.src_port:
        return False
    if rule.dest_port != ANY_PORT and dst_port != rule.dest_port:
        return False
    return True
