"""RendererCache: shared cache computing minimal table diffs for renderers.

The cache folds each pod's ingress+egress ContivRules into a single chosen
orientation, groups identical per-pod rule sets into shared *local tables*,
maintains one node-*global table*, and lets a renderer transaction compute
the minimal set of table changes (`get_changes`) needed to reach the new
configuration.

Orientation semantics (from the vswitch point of view):
- INGRESS: tables match traffic *arriving* from interfaces into the vswitch
  (local table rules have src addr/port wildcarded).
- EGRESS: tables match traffic *leaving* the vswitch through interfaces
  (local table rules have dst addr/port wildcarded).

Reference: plugins/policy/renderer/cache/{cache_api.go,cache_impl.go,
local_tables.go,ports.go} — semantics reproduced, implementation re-done
in Python (sorted lists + dict indexes instead of Go slices/maps).
"""

from __future__ import annotations

import bisect
import enum
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

from vpp_tpu.ir.rule import (
    ANY_PORT,
    Action,
    ContivRule,
    IPNetwork,
    PodID,
    Protocol,
    allow_all_tcp,
    allow_all_udp,
    compare_rule_lists,
)
from vpp_tpu.ir.table import GLOBAL_TABLE_ID, ContivRuleTable, TableType, sorted_unique
from vpp_tpu.renderer.api import PodConfig


class Orientation(enum.IntEnum):
    INGRESS = 0
    EGRESS = 1


@dataclass
class TxnChange:
    """One table-level change computed by a transaction.

    ``previous_pods`` is the set of pods previously assigned to the table
    (empty for the global table or a newly added local table).
    """

    table: ContivRuleTable
    previous_pods: Set[PodID] = field(default_factory=set)

    def __str__(self) -> str:
        prev = ", ".join(sorted(str(p) for p in self.previous_pods))
        return f"Change <table: {self.table}, prevPods: [{prev}]>"


# --- Port-set algebra (reference: renderer/cache/ports.go) -----------------

ANY_PORTS = frozenset({ANY_PORT})


def _ports_is_subset(p: Set[int], p2: Set[int]) -> bool:
    if ANY_PORT in p2:
        return True
    if ANY_PORT in p:
        return False
    return all(port in p2 for port in p)


def _ports_intersection(p: Set[int], p2: Set[int]) -> Set[int]:
    if ANY_PORT in p:
        return set(p2)
    if ANY_PORT in p2:
        return set(p)
    return {port for port in p if port in p2}


# --- Local-table collection (reference: renderer/cache/local_tables.go) ----


class LocalTables:
    """Collection of local tables ordered by rule lists, with ID/pod indexes.

    A pod is assigned to at most one table at any time.
    """

    def __init__(self) -> None:
        self.tables: List[ContivRuleTable] = []
        self.by_id: Dict[str, ContivRuleTable] = {}
        self.by_pod: Dict[PodID, ContivRuleTable] = {}

    def __iter__(self):
        return iter(list(self.tables))

    def _lookup_idx_by_rules(self, rules: List[ContivRule]) -> int:
        lo, hi = 0, len(self.tables)
        while lo < hi:
            mid = (lo + hi) // 2
            if compare_rule_lists(self.tables[mid].rules, rules) < 0:
                lo = mid + 1
            else:
                hi = mid
        return lo

    def insert(self, table: ContivRuleTable) -> bool:
        if table.id in self.by_id:
            return False
        idx = self._lookup_idx_by_rules(table.rules)
        self.tables.insert(idx, table)
        self.by_id[table.id] = table
        for pod in list(table.pods):
            self.unassign_pod(None, pod)
            self.by_pod[pod] = table
        return True

    def remove(self, table: ContivRuleTable) -> bool:
        if table.id not in self.by_id:
            return False
        self.tables.remove(self.by_id[table.id])
        del self.by_id[table.id]
        for pod in table.pods:
            self.by_pod.pop(pod, None)
        return True

    def assign_pod(self, table: ContivRuleTable, pod: PodID) -> None:
        self.unassign_pod(None, pod)
        table.pods.add(pod)
        self.by_pod[pod] = table

    def unassign_pod(self, table: Optional[ContivRuleTable], pod: PodID) -> None:
        if table is not None:
            table.pods.discard(pod)
        assigned = self.by_pod.get(pod)
        if assigned is not None and (table is None or table is assigned):
            assigned.pods.discard(pod)
            del self.by_pod[pod]

    def lookup_by_id(self, table_id: str) -> Optional[ContivRuleTable]:
        return self.by_id.get(table_id)

    def lookup_by_rules(self, rules: List[ContivRule]) -> Optional[ContivRuleTable]:
        idx = self._lookup_idx_by_rules(rules)
        if idx < len(self.tables) and compare_rule_lists(rules, self.tables[idx].rules) == 0:
            return self.tables[idx]
        return None

    def lookup_by_pod(self, pod: PodID) -> Optional[ContivRuleTable]:
        return self.by_pod.get(pod)

    def get_isolated_pods(self) -> Set[PodID]:
        return {pod for pod, table in self.by_pod.items() if table.num_of_rules > 0}


# --- The cache itself -------------------------------------------------------


class RendererCache:
    """See module docstring. Reference: renderer/cache/cache_impl.go."""

    def __init__(self, orientation: Orientation = Orientation.INGRESS):
        self.orientation = orientation
        self._next_table_id = 0
        self.flush()

    def flush(self) -> None:
        self.config: Dict[PodID, PodConfig] = {}
        self.local_tables = LocalTables()
        self.global_table = ContivRuleTable(GLOBAL_TABLE_ID)

    def new_txn(self) -> "RendererCacheTxn":
        return RendererCacheTxn(self)

    def resync(self, tables: Iterable[ContivRuleTable]) -> None:
        """Replace cache content with dumped tables (e.g. from the device).

        Only the set of tracked pods can be reconstructed, not per-pod rule
        configs — follow a resync with a txn updating still-present pods and
        removing the rest.
        """
        config: Dict[PodID, PodConfig] = {}
        allocated: Set[str] = set()
        local = LocalTables()
        global_table = ContivRuleTable(GLOBAL_TABLE_ID)

        for table in tables:
            if table is None:
                continue
            # Copy: the cache must own its tables — later commits mutate pod
            # assignments in place and must not corrupt the caller's dump
            # (or another cache still holding the same objects).
            table = table.copy()
            if table.type == TableType.GLOBAL:
                global_table = table
                continue
            if not table.pods:
                continue
            if table.id in allocated:
                raise ValueError(f"duplicate ContivRuleTable ID: {table.id}")
            allocated.add(table.id)
            for pod in table.pods:
                if pod in config:
                    raise ValueError(f"pod assigned to multiple local tables: {pod}")
                config[pod] = PodConfig()
            local.insert(table)

        self.config = config
        self.local_tables = local
        self.global_table = global_table
        # Never reuse an ID from the dump: bump the generator counter past
        # any counter-shaped IDs (arbitrary foreign IDs cannot collide with
        # the "T%08d" namespace).
        for table_id in allocated:
            if table_id.startswith("T") and table_id[1:].isdigit():
                self._next_table_id = max(self._next_table_id, int(table_id[1:]) + 1)

    # View
    def get_pod_config(self, pod: PodID) -> Optional[PodConfig]:
        return self.config.get(pod)

    def get_all_pods(self) -> Set[PodID]:
        return set(self.config.keys())

    def get_isolated_pods(self) -> Set[PodID]:
        return self.local_tables.get_isolated_pods()

    def get_local_table_by_pod(self, pod: PodID) -> Optional[ContivRuleTable]:
        table = self.local_tables.lookup_by_pod(pod)
        if table is not None and table.num_of_rules == 0:
            return None
        return table

    def get_global_table(self) -> ContivRuleTable:
        return self.global_table

    def _generate_table_id(self) -> str:
        # Monotonic counter: IDs are never reused, so no tracking set is
        # needed (an abandoned transaction merely skips a few IDs).
        table_id = f"T{self._next_table_id:08d}"
        self._next_table_id += 1
        return table_id


class RendererCacheTxn:
    """Transaction over RendererCache; computes tables lazily on demand."""

    def __init__(self, cache: RendererCache):
        self.cache = cache
        self.config: Dict[PodID, PodConfig] = {}
        self.local_tables = LocalTables()
        self.global_table: Optional[ContivRuleTable] = None
        self._up_to_date = False

    # -- updates
    def update(self, pod: PodID, pod_config: PodConfig) -> None:
        self.config[pod] = pod_config
        self._up_to_date = False

    def get_updated_pods(self) -> Set[PodID]:
        return set(self.config.keys())

    def get_removed_pods(self) -> Set[PodID]:
        return {pod for pod, cfg in self.config.items() if cfg.removed}

    # -- view (as-if-committed)
    def get_pod_config(self, pod: PodID) -> Optional[PodConfig]:
        if pod in self.config:
            return self.config[pod]
        return self.cache.get_pod_config(pod)

    def get_all_pods(self) -> Set[PodID]:
        pods = self.cache.get_all_pods()
        for pod, cfg in self.config.items():
            if cfg.removed:
                pods.discard(pod)
            else:
                pods.add(pod)
        return pods

    def get_isolated_pods(self) -> Set[PodID]:
        # After _refresh_tables every tracked pod has an assignment in the
        # txn's table collection, so the txn view is authoritative.
        if not self._up_to_date:
            self._refresh_tables()
        return self.local_tables.get_isolated_pods()

    def get_local_table_by_pod(self, pod: PodID) -> Optional[ContivRuleTable]:
        if not self._up_to_date:
            self._refresh_tables()
        table = self.local_tables.lookup_by_pod(pod)
        if table is None:
            table = self.cache.local_tables.lookup_by_pod(pod)
        if table is not None and table.num_of_rules == 0:
            return None
        return table

    def get_global_table(self) -> ContivRuleTable:
        if not self._up_to_date:
            self._refresh_tables()
        return self.global_table if self.global_table is not None else self.cache.global_table

    # -- diff + commit
    def get_changes(self) -> List[TxnChange]:
        if not self._up_to_date:
            self._refresh_tables()
        changes: List[TxnChange] = []
        for txn_table in self.local_tables:
            orig = self.cache.local_tables.lookup_by_id(txn_table.id)
            if txn_table.num_of_rules == 0:
                continue
            if not txn_table.pods and orig is None:
                continue  # added and removed within the same txn
            if orig is not None and txn_table.pods == orig.pods:
                continue  # unchanged
            changes.append(
                TxnChange(
                    table=txn_table,
                    previous_pods=set(orig.pods) if orig is not None else set(),
                )
            )
        if self.global_table is not None and compare_rule_lists(
            self.global_table.rules, self.cache.global_table.rules
        ):
            changes.append(TxnChange(table=self.global_table))
        return changes

    def commit(self) -> None:
        if not self._up_to_date:
            self._refresh_tables()
        for txn_table in self.local_tables:
            orig = self.cache.local_tables.lookup_by_id(txn_table.id)
            if orig is not None:
                if not txn_table.pods:
                    self.cache.local_tables.remove(orig)
                elif txn_table.pods != orig.pods:
                    for pod in set(orig.pods):
                        if pod not in txn_table.pods:
                            self.cache.local_tables.unassign_pod(orig, pod)
                    for pod in set(txn_table.pods):
                        if pod not in orig.pods:
                            self.cache.local_tables.assign_pod(orig, pod)
                    orig.private = txn_table.private
            else:
                # Rule-less tables (unisolated/removed pods) are never
                # installed; they only exist to carry assignment changes.
                if txn_table.pods and txn_table.num_of_rules > 0:
                    self.cache.local_tables.insert(txn_table)
        if self.global_table is not None and compare_rule_lists(
            self.global_table.rules, self.cache.global_table.rules
        ):
            self.cache.global_table = self.global_table
        for pod, cfg in self.config.items():
            if cfg.removed:
                self.cache.config.pop(pod, None)
                self.cache.local_tables.unassign_pod(None, pod)
            else:
                self.cache.config[pod] = cfg
        # Prune local tables left with no assigned pods.
        for table in list(self.cache.local_tables):
            if not table.pods:
                self.cache.local_tables.remove(table)

    # -- table building (reference: cache_impl.go refreshTables et al.)
    def _refresh_tables(self) -> None:
        fold = _Fold(self)
        for pod in self.get_all_pods() | self.get_removed_pods():
            pod_cfg = self.get_pod_config(pod)
            if pod_cfg is None:
                continue
            new_table = self._build_local_table(pod, pod_cfg, fold)

            # Pull the pod's original table into the txn if not already there.
            orig = self.cache.local_tables.lookup_by_pod(pod)
            if orig is not None and self.local_tables.lookup_by_id(orig.id) is None:
                self.local_tables.insert(orig.copy())

            # Shared with another table already in the txn?
            txn_table = self.local_tables.lookup_by_rules(new_table.rules)
            if txn_table is not None:
                self.local_tables.assign_pod(txn_table, pod)
                continue

            # Shared with a cache table not yet copied into the txn?
            cache_table = self.cache.local_tables.lookup_by_rules(new_table.rules)
            if cache_table is not None:
                updated = cache_table.copy()
                updated.pods.add(pod)
                self.local_tables.insert(updated)
                self.local_tables.assign_pod(updated, pod)
                continue

            self.local_tables.insert(new_table)
            self.local_tables.assign_pod(new_table, pod)

        self._rebuild_global_table()
        self._up_to_date = True

    def _build_local_table(
        self, dst_pod: PodID, dst_cfg: PodConfig, fold: "_Fold"
    ) -> ContivRuleTable:
        table = ContivRuleTable(self.cache._generate_table_id(), TableType.LOCAL)
        table.pods.add(dst_pod)
        if dst_cfg.removed:
            return table
        table.rules = list(fold.table_rules(dst_cfg))
        return table

    def _rebuild_global_table(self) -> None:
        self.global_table = ContivRuleTable(GLOBAL_TABLE_ID)
        egress_oriented = self.cache.orientation == Orientation.EGRESS
        for pod in self.get_all_pods():
            cfg = self.get_pod_config(pod)
            if cfg is None:
                continue
            rules = cfg.ingress if egress_oriented else cfg.egress
            for rule in rules:
                if egress_oriented:
                    rule = ContivRule(
                        action=rule.action,
                        src_network=cfg.pod_ip,
                        dest_network=rule.dest_network,
                        protocol=rule.protocol,
                        src_port=rule.src_port,
                        dest_port=rule.dest_port,
                    )
                else:
                    rule = ContivRule(
                        action=rule.action,
                        src_network=rule.src_network,
                        dest_network=cfg.pod_ip,
                        protocol=rule.protocol,
                        src_port=rule.src_port,
                        dest_port=rule.dest_port,
                    )
                self.global_table.insert_rule(rule)
        if self.global_table.num_of_rules > 0:
            self.global_table.insert_rule(allow_all_tcp())
            self.global_table.insert_rule(allow_all_udp())


# --- The fold (reference: cache_impl.go installLocalRules) ------------------


def _ip_key(net: IPNetwork) -> Tuple[int, int]:
    return net.version, int(net.network_address)


class _PortIndex:
    """The allowed destination (TCP, UDP) ports of ONE rule list at every
    local pod address (reference: ports.go getAllowedEgressPorts /
    getAllowedIngressPorts): a list with no deny allows all ports; else
    a permit counts where its peer network holds the address, and ANY
    counts for both protocols (ICMP for neither: the tables enforce it
    directly). Computed once per list: the rules with no peer network
    apply everywhere, and each distinct peer network adds its ports to
    the pod addresses it covers (found by bisecting the sorted
    addresses), so the whole list costs O(R log P) instead of O(R) per
    (pod, pod) pair. ``net_attr`` names the peer field (``src_network``
    for egress rules, ``dest_network`` for ingress rules)."""

    def __init__(self, rules: List[ContivRule], net_attr: str,
                 addrs: Dict[int, List[int]]):
        self.has_deny = any(r.action == Action.DENY for r in rules)
        if not self.has_deny:
            return
        tcp: Set[int] = set()
        udp: Set[int] = set()
        by_net: Dict[IPNetwork, Tuple[Set[int], Set[int]]] = {}
        for rule in rules:
            if rule.action == Action.DENY:
                continue
            net = getattr(rule, net_attr)
            t, u = (tcp, udp) if net is None else by_net.setdefault(
                net, (set(), set()))
            if rule.protocol in (Protocol.TCP, Protocol.ANY):
                t.add(rule.dest_port)
            if rule.protocol in (Protocol.UDP, Protocol.ANY):
                u.add(rule.dest_port)
        self.base = (tcp, udp)
        # a pod with no address (None) meets every rule
        self.unaddressed = (
            tcp.union(*(t for t, _ in by_net.values())),
            udp.union(*(u for _, u in by_net.values())),
        )
        self.extra: Dict[Tuple[int, int], Tuple[Set[int], Set[int]]] = {}
        for net, (t, u) in by_net.items():
            ints = addrs.get(net.version, [])
            lo = bisect.bisect_left(ints, int(net.network_address))
            hi = bisect.bisect_right(ints, int(net.broadcast_address))
            for a in ints[lo:hi]:
                et, eu = self.extra.setdefault((net.version, a),
                                               (set(tcp), set(udp)))
                et |= t
                eu |= u

    def ports(self, ip: Optional[IPNetwork]) -> Tuple[Set[int], Set[int]]:
        """Allowed (TCP, UDP) destination ports at pod address ``ip``;
        the caller must not mutate them."""
        if not self.has_deny:
            return ANY_PORTS, ANY_PORTS
        if ip is None:
            return self.unaddressed
        return self.extra.get(_ip_key(ip), self.base)


class _Fold:
    """One txn's view of the node for ``_build_local_table``: a pod's
    table is its own rules plus, for every pod whose opposite-direction
    rules restrict the traffic, the pinned ports of the pair (the
    ingress∧egress semantic in one orientation). Port indexes are built
    once per distinct rule list and tables once per distinct (own rules,
    pinned ports): the cost is O(R + pods) per distinct pod
    configuration, not O(pods² × R)."""

    def __init__(self, txn: "RendererCacheTxn"):
        self.egress_oriented = txn.cache.orientation == Orientation.EGRESS
        # own rules are in the cache orientation; their peer is the
        # destination for INGRESS, the source for EGRESS
        self.own_attr = "src_network" if self.egress_oriented else "dest_network"
        self.other_attr = "dest_network" if self.egress_oriented else "src_network"
        pods = [(pod, txn.get_pod_config(pod)) for pod in txn.get_all_pods()]
        pods = [(pod, cfg) for pod, cfg in pods if cfg is not None]
        addrs: Dict[int, Set[int]] = {}
        for _, cfg in pods:
            if cfg.pod_ip is not None:
                addrs.setdefault(cfg.pod_ip.version, set()).add(
                    int(cfg.pod_ip.network_address))
        self.addrs = {v: sorted(a) for v, a in addrs.items()}
        # key -> (the rule list, its index): holding the list keeps the
        # ids in the key its own for the life of the txn
        self._indexes: Dict[tuple, Tuple[List[ContivRule], _PortIndex]] = {}
        self._tables: Dict[tuple, List[ContivRule]] = {}
        # the pods whose opposite-direction rules deny something: only
        # they pin ports into other pods' tables (no deny = all ports)
        self.folders = []
        for _, cfg in pods:
            idx = self._index(self._other(cfg), self.other_attr)
            if idx.has_deny:
                self.folders.append((cfg.pod_ip, idx))

    def _own(self, cfg: PodConfig) -> List[ContivRule]:
        return cfg.egress if self.egress_oriented else cfg.ingress

    def _other(self, cfg: PodConfig) -> List[ContivRule]:
        return cfg.ingress if self.egress_oriented else cfg.egress

    def _index(self, rules: List[ContivRule], net_attr: str) -> _PortIndex:
        # pods sharing a policy set share the rule objects: key by them
        key = (net_attr, tuple(map(id, rules)))
        if key not in self._indexes:
            self._indexes[key] = (rules, _PortIndex(rules, net_attr, self.addrs))
        return self._indexes[key][1]

    def table_rules(self, dst_cfg: PodConfig) -> List[ContivRule]:
        """The rule list of a pod's local table (not removed)."""
        own = self._own(dst_cfg)
        own_idx = self._index(own, self.own_attr)
        pins = []
        for src_ip, src_idx in self.folders:
            src_tcp, src_udp = src_idx.ports(dst_cfg.pod_ip)
            dst_tcp, dst_udp = own_idx.ports(src_ip)
            if not _ports_is_subset(dst_tcp, src_tcp):
                pins.append((src_ip, Protocol.TCP,
                             frozenset(_ports_intersection(dst_tcp, src_tcp))))
            if not _ports_is_subset(dst_udp, src_udp):
                pins.append((src_ip, Protocol.UDP,
                             frozenset(_ports_intersection(dst_udp, src_udp))))
        key = (tuple(map(id, own)), tuple(pins))
        rules = self._tables.get(key)
        if rules is None:
            rules = self._tables[key] = self._fold_rules(own, pins)
        return rules

    def _fold_rules(self, own: List[ContivRule], pins: list) -> List[ContivRule]:
        # Pinning an address removes the one-host rules at it (_pinned),
        # those of an earlier pin of the address and protocol included:
        # of those pins only the last one's rules stay.
        last = {(proto, _ip_key(ip)): n
                for n, (ip, proto, _) in enumerate(pins) if ip is not None}
        live = [(ip, proto, ports) for n, (ip, proto, ports) in enumerate(pins)
                if ip is None or ip.prefixlen != ip.max_prefixlen
                or last[(proto, _ip_key(ip))] == n]
        drop = set(last)
        table = ContivRuleTable("", TableType.LOCAL)
        table.rules = sorted_unique(
            [r for r in own if not self._pinned(r, drop)]
            + [rule for ip, proto, ports in live
               for rule in self._pin_rules(ip, proto, ports)])

        # Explicitly allow traffic not matched by any rule. A rule counts as
        # "total" for its protocol only if every match dimension is
        # wildcarded (the reference omits the src_port check because its
        # configurator never emits src-port rules; our IR allows them, so
        # check it — otherwise a src-port-specific permit would suppress
        # the allow-all append and default-deny everything else).
        if table.rules:
            all_tcp = any(
                r.dest_port == ANY_PORT and r.src_port == ANY_PORT
                and r.src_network is None and r.dest_network is None
                and r.protocol == Protocol.TCP
                for r in table.rules
            )
            all_udp = any(
                r.dest_port == ANY_PORT and r.src_port == ANY_PORT
                and r.src_network is None and r.dest_network is None
                and r.protocol == Protocol.UDP
                for r in table.rules
            )
            if not all_tcp:
                table.insert_rule(allow_all_tcp())
            if not all_udp:
                table.insert_rule(allow_all_udp())
        return table.rules

    def _pinned(self, rule: ContivRule, drop: Set[tuple]) -> bool:
        """Whether a rule is one of the subtrees rooted at a pinned
        pod's one-host subnet (for the pinned protocol)."""
        net = rule.src_network if self.egress_oriented else rule.dest_network
        if net is None or net.prefixlen != net.max_prefixlen:
            return False
        return (rule.protocol, _ip_key(net)) in drop

    def _pin_rules(self, src_pod_ip: Optional[IPNetwork], protocol: Protocol,
                   allowed_ports) -> List[ContivRule]:
        """An explicit permit per allowed port, then deny-the-rest."""
        peer = "src_network" if self.egress_oriented else "dest_network"
        out = [
            ContivRule(action=Action.PERMIT, protocol=protocol,
                       src_port=ANY_PORT, dest_port=port,
                       **{peer: src_pod_ip})
            for port in allowed_ports
        ]
        out.append(ContivRule(action=Action.DENY, protocol=protocol,
                              src_port=ANY_PORT, dest_port=ANY_PORT,
                              **{peer: src_pod_ip}))
        return out
