"""The policy path's rule expansion and table fold against the quadratic
algorithms they replaced, kept here as the oracle.

The configurator dedupes each generated rule against a set of the rules
kept so far, and the renderer cache builds one table per distinct pod
configuration from per-rule-list port indexes. The oracle below is the
earlier code: a rule is kept if no kept rule compares equal to it, and
each pod's table is built alone, scanning every rule of its list once
per (pod, pod) pair. On seeded random namespaces, pods and policies
(ingress and egress, pod and namespace selectors, ipBlocks with
excepts, ports or none) both must give the same rule lists, the same
tables (ids, rules in order, pods) and the same pod assignment after
every commit.
"""

import bisect
import functools
import ipaddress
import random

import pytest

from vpp_tpu.ir.rule import (
    ANY_PORT,
    Action,
    ContivRule,
    PodID,
    Protocol,
    allow_all_tcp,
    allow_all_udp,
    compare_ints,
    compare_ip_nets,
    compare_ports,
    compare_rule_lists,
    compare_rules,
)
from vpp_tpu.ir.table import ContivRuleTable, TableType, sorted_unique
from vpp_tpu.ksr import model as m
from vpp_tpu.policy import PolicyCache, PolicyConfigurator, PolicyProcessor
from vpp_tpu.policy.config import MatchType, PolicyType
from vpp_tpu.policy.configurator import subtract_subnet
from vpp_tpu.renderer.api import PodConfig, PolicyRendererAPI, RendererTxn
from vpp_tpu.renderer.cache import (
    Orientation,
    RendererCache,
    RendererCacheTxn,
    _ports_intersection,
    _ports_is_subset,
)

# --- the oracle: the earlier algorithms ------------------------------------


def old_compare_ports(a, b) -> int:
    if a == b:
        return 0
    if a == ANY_PORT:
        return 1
    if b == ANY_PORT:
        return -1
    return compare_ints(a, b)


def old_compare_ip_nets(a, b) -> int:
    if a is None:
        return 0 if b is None else 1
    if b is None:
        return -1
    a4, b4 = a.version == 4, b.version == 4
    if a4 != b4:
        return -1 if a4 else 1
    common = min(a.prefixlen, b.prefixlen)
    a_net = int(a.network_address) >> (a.max_prefixlen - common) if common else 0
    b_net = int(b.network_address) >> (b.max_prefixlen - common) if common else 0
    if a_net == b_net:
        return compare_ints(b.prefixlen, a.prefixlen)
    mask_order = compare_ints(b.prefixlen, a.prefixlen)
    if mask_order != 0:
        return mask_order
    return compare_ints(int(a.network_address), int(b.network_address))


def old_compare_rules(a, b) -> int:
    for cmp in (
        compare_ints(int(a.protocol), int(b.protocol)),
        old_compare_ip_nets(a.src_network, b.src_network),
        old_compare_ip_nets(a.dest_network, b.dest_network),
        old_compare_ports(a.src_port, b.src_port),
        old_compare_ports(a.dest_port, b.dest_port),
    ):
        if cmp != 0:
            return cmp
    return compare_ints(int(a.action), int(b.action))


_CMP = functools.cmp_to_key(old_compare_rules)


def old_insert(table: ContivRuleTable, rule: ContivRule) -> None:
    idx = bisect.bisect_left(table.rules, _CMP(rule), key=_CMP)
    if idx < len(table.rules) and old_compare_rules(table.rules[idx], rule) == 0:
        return
    table.rules.insert(idx, rule)


def old_compare_rule_lists(a, b) -> int:
    for ra, rb in zip(a, b):
        cmp = old_compare_rules(ra, rb)
        if cmp != 0:
            return cmp
    return compare_ints(len(a), len(b))


def old_allowed_egress_ports(src_ip, egress):
    tcp, udp, has_deny = set(), set(), False
    for rule in egress:
        if rule.action == Action.DENY:
            has_deny = True
            continue
        if (rule.src_network is not None and src_ip is not None
                and src_ip.network_address not in rule.src_network):
            continue
        if rule.protocol in (Protocol.TCP, Protocol.ANY):
            tcp.add(rule.dest_port)
        if rule.protocol in (Protocol.UDP, Protocol.ANY):
            udp.add(rule.dest_port)
    if not has_deny:
        return {ANY_PORT}, {ANY_PORT}
    return tcp, udp


def old_allowed_ingress_ports(dst_ip, ingress):
    tcp, udp, has_deny = set(), set(), False
    for rule in ingress:
        if rule.action == Action.DENY:
            has_deny = True
            continue
        if (rule.dest_network is not None and dst_ip is not None
                and dst_ip.network_address not in rule.dest_network):
            continue
        if rule.protocol in (Protocol.TCP, Protocol.ANY):
            tcp.add(rule.dest_port)
        if rule.protocol in (Protocol.UDP, Protocol.ANY):
            udp.add(rule.dest_port)
    if not has_deny:
        return {ANY_PORT}, {ANY_PORT}
    return tcp, udp


class OldCacheTxn(RendererCacheTxn):
    """Each pod's table built alone, the fold scanning every rule per
    (dst pod, src pod) pair."""

    def _refresh_tables(self) -> None:
        for pod in self.get_all_pods() | self.get_removed_pods():
            pod_cfg = self.get_pod_config(pod)
            if pod_cfg is None:
                continue
            new_table = self._old_build(pod, pod_cfg)
            orig = self.cache.local_tables.lookup_by_pod(pod)
            if orig is not None and self.local_tables.lookup_by_id(orig.id) is None:
                self.local_tables.insert(orig.copy())
            txn_table = self.local_tables.lookup_by_rules(new_table.rules)
            if txn_table is not None:
                self.local_tables.assign_pod(txn_table, pod)
                continue
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

    def _old_build(self, dst_pod, dst_cfg):
        table = ContivRuleTable(self.cache._generate_table_id(), TableType.LOCAL)
        table.pods.add(dst_pod)
        if dst_cfg.removed:
            return table
        egress = self.cache.orientation == Orientation.EGRESS
        for rule in (dst_cfg.egress if egress else dst_cfg.ingress):
            old_insert(table, rule)
        for src_pod in self.get_all_pods():
            src_cfg = self.get_pod_config(src_pod)
            if src_cfg is not None:
                self._old_install(table, dst_cfg, src_cfg)
        if table.rules:
            def total(proto):
                return any(
                    r.dest_port == ANY_PORT and r.src_port == ANY_PORT
                    and r.src_network is None and r.dest_network is None
                    and r.protocol == proto for r in table.rules)
            if not total(Protocol.TCP):
                old_insert(table, allow_all_tcp())
            if not total(Protocol.UDP):
                old_insert(table, allow_all_udp())
        return table

    def _old_install(self, dst_table, dst_cfg, src_cfg):
        if self.cache.orientation == Orientation.EGRESS:
            src_tcp, src_udp = old_allowed_ingress_ports(dst_cfg.pod_ip, src_cfg.ingress)
            dst_tcp, dst_udp = old_allowed_egress_ports(src_cfg.pod_ip, dst_cfg.egress)
        else:
            src_tcp, src_udp = old_allowed_egress_ports(dst_cfg.pod_ip, src_cfg.egress)
            dst_tcp, dst_udp = old_allowed_ingress_ports(src_cfg.pod_ip, dst_cfg.ingress)
        for dst, src, proto in ((dst_tcp, src_tcp, Protocol.TCP),
                                (dst_udp, src_udp, Protocol.UDP)):
            if not _ports_is_subset(dst, src):
                self._old_pin(dst_table, src_cfg.pod_ip,
                              _ports_intersection(dst, src), proto)

    def _old_pin(self, dst_table, src_pod_ip, allowed_ports, protocol):
        egress = self.cache.orientation == Orientation.EGRESS

        def against_src_pod(rule):
            if rule.protocol != protocol:
                return False
            net = rule.src_network if egress else rule.dest_network
            if net is None or src_pod_ip is None:
                return False
            return (net.prefixlen == net.max_prefixlen
                    and net.network_address == src_pod_ip.network_address)

        dst_table.rules = [r for r in dst_table.rules if not against_src_pod(r)]
        peer = "src_network" if egress else "dest_network"
        for port in allowed_ports:
            old_insert(dst_table, ContivRule(
                action=Action.PERMIT, protocol=protocol, src_port=ANY_PORT,
                dest_port=port, **{peer: src_pod_ip}))
        old_insert(dst_table, ContivRule(
            action=Action.DENY, protocol=protocol, src_port=ANY_PORT,
            dest_port=ANY_PORT, **{peer: src_pod_ip}))


class OldCache(RendererCache):
    def new_txn(self):
        return OldCacheTxn(self)


def old_generate_rules(configurator, direction, policies):
    """The configurator's expansion with the pairwise dedupe."""
    rules = []
    has_policy = all_allowed = False

    def append(*new):
        for rule in new:
            if not any(old_compare_rules(rule, r) == 0 for r in rules):
                rules.append(rule)

    def permit(protocol, peer_net=None, dest_port=ANY_PORT):
        kw = dict(action=Action.PERMIT, protocol=protocol,
                  src_port=ANY_PORT, dest_port=dest_port)
        if peer_net is not None:
            kw["src_network" if direction == MatchType.INGRESS
               else "dest_network"] = peer_net
        return ContivRule(**kw)

    for policy in policies:
        if ((policy.type == PolicyType.INGRESS and direction == MatchType.EGRESS)
                or (policy.type == PolicyType.EGRESS
                    and direction == MatchType.INGRESS)):
            continue
        has_policy = True
        for match in policy.matches:
            if match.type != direction:
                continue
            nets = []
            for peer in match.pods or []:
                data = configurator.cache.lookup_pod(peer)
                if data is not None and data.ip_address:
                    nets.append(ipaddress.ip_network(f"{data.ip_address}/32"))
            for block in match.ip_blocks or []:
                subnets = [block.network]
                for exc in block.except_nets:
                    subnets = [s for sub in subnets
                               for s in subtract_subnet(sub, exc)]
                nets.extend(subnets)
            if match.pods is None and match.ip_blocks is None:
                if not match.ports:
                    append(permit(Protocol.TCP), permit(Protocol.UDP))
                    all_allowed = True
                else:
                    for port in match.ports:
                        append(permit(port.protocol.rule_protocol,
                                      dest_port=port.number))
                continue
            for net in nets:
                if not match.ports:
                    append(permit(Protocol.TCP, net), permit(Protocol.UDP, net))
                else:
                    for port in match.ports:
                        append(permit(port.protocol.rule_protocol, net,
                                      dest_port=port.number))
    if has_policy and not all_allowed:
        append(ContivRule(action=Action.DENY, protocol=Protocol.TCP),
               ContivRule(action=Action.DENY, protocol=Protocol.UDP))
    return rules


# --- a renderer that runs both caches side by side ------------------------


def _tables(cache):
    return [(t.id, list(t.rules), sorted(t.pods)) for t in cache.local_tables]


class _TwinTxn(RendererTxn):
    def __init__(self, twin, resync):
        self.twin = twin
        if resync:
            twin.new.flush()
            twin.old.flush()
        self.txns = (twin.new.new_txn(), twin.old.new_txn())

    def render(self, pod, pod_ip, ingress, egress, removed=False):
        for txn in self.txns:
            txn.update(pod, PodConfig(pod_ip=pod_ip, ingress=list(ingress),
                                      egress=list(egress), removed=removed))
        return self

    def commit(self):
        new, old = self.txns
        cn, co = new.get_changes(), old.get_changes()
        assert [(c.table.id, c.table.rules, c.previous_pods) for c in cn] == \
            [(c.table.id, c.table.rules, c.previous_pods) for c in co]
        for pod in new.get_all_pods():
            tn = new.get_local_table_by_pod(pod)
            to = old.get_local_table_by_pod(pod)
            assert (tn and tn.id, tn and tn.rules) == (to and to.id, to and to.rules)
        new.commit()
        old.commit()
        assert _tables(self.twin.new) == _tables(self.twin.old)
        assert self.twin.new.global_table.rules == self.twin.old.global_table.rules
        self.twin.commits += 1


class TwinRenderer(PolicyRendererAPI):
    def __init__(self, orientation):
        self.new = RendererCache(orientation)
        self.old = OldCache(orientation)
        self.commits = 0

    def new_txn(self, resync=False):
        return _TwinTxn(self, resync)


class CheckedConfigurator(PolicyConfigurator):
    """Every expansion also through the pairwise-dedupe oracle."""

    def new_txn(self, resync=False):
        txn = super().new_txn(resync)
        gen = txn._generate_rules

        def checked(direction, policies):
            got = gen(direction, policies)
            assert got == old_generate_rules(self, direction, policies)
            return got

        txn._generate_rules = checked
        return txn


# --- seeded random policy sets ---------------------------------------------

LABELS = ("web", "db", "cache")
PORTS = (("TCP", 80), ("TCP", 443), ("UDP", 53), ("TCP", 8080))


def _random_peer(rng, n_ns):
    kind = rng.random()
    if kind < 0.35:
        return m.PolicyPeer(pods=m.LabelSelector(
            match_labels={"app": rng.choice(LABELS)}))
    if kind < 0.5:
        return m.PolicyPeer(namespaces=m.LabelSelector(
            match_labels={"team": f"t{rng.randrange(n_ns)}"}))
    base = rng.choice(("10.1.1.0", "10.1.2.0", "10.1.0.0"))
    plen = rng.choice((24, 26, 28))
    net = ipaddress.ip_network(f"{base}/{plen}")
    excepts = []
    for _ in range(rng.randint(0, 3)):
        sub = list(net.subnets(new_prefix=min(32, plen + rng.choice((2, 4, 6)))))
        excepts.append(str(rng.choice(sub)))
    return m.PolicyPeer(ip_block=m.IPBlock(cidr=str(net),
                                           except_cidrs=sorted(set(excepts))))


def _random_rules(rng, n_ns):
    return [
        m.PolicyRule(
            ports=[m.PolicyPort(protocol=p, port=n)
                   for p, n in rng.sample(PORTS, rng.randint(1, 2))]
            if rng.random() < 0.7 else [],
            peers=[_random_peer(rng, n_ns) for _ in range(rng.randint(1, 2))]
            if rng.random() < 0.85 else [],
        )
        for _ in range(rng.randint(0, 2))
    ]


def _random_policy(rng, i, namespaces):
    ptype = rng.choice((m.POLICY_INGRESS, m.POLICY_EGRESS, m.POLICY_BOTH))
    return m.Policy(
        name=f"pol{i}", namespace=rng.choice(namespaces),
        pods=m.LabelSelector(match_labels={"app": rng.choice(LABELS)})
        if rng.random() < 0.7 else m.LabelSelector(),
        policy_type=ptype,
        ingress_rules=_random_rules(rng, len(namespaces))
        if ptype != m.POLICY_EGRESS else [],
        egress_rules=_random_rules(rng, len(namespaces))
        if ptype != m.POLICY_INGRESS else [],
    )


@pytest.mark.parametrize("orientation", list(Orientation))
@pytest.mark.parametrize("seed", [3, 11, 29, 47])
def test_render_matches_quadratic_oracle(seed, orientation):
    rng = random.Random(seed)
    namespaces = [f"ns{j}" for j in range(rng.randint(2, 3))]
    cache = PolicyCache()
    configurator = CheckedConfigurator(cache)
    twin = TwinRenderer(orientation)
    configurator.register_renderer(twin)
    PolicyProcessor(cache, configurator)
    for j, ns in enumerate(namespaces):
        cache.update_namespace(m.Namespace(name=ns, labels={"team": f"t{j}"}))
    pods = []
    for k in range(rng.randint(6, 10)):
        pod = m.Pod(name=f"p{k}", namespace=rng.choice(namespaces),
                    labels={"app": rng.choice(LABELS)},
                    ip_address=f"10.1.{1 + k % 2}.{2 + 7 * k}")
        pods.append(pod)
        cache.update_pod(pod)
    policies = [_random_policy(rng, i, namespaces)
                for i in range(rng.randint(3, 6))]
    for pol in policies:
        cache.update_policy(pol)
    # churn: a pod leaves, a policy goes, then a full resync
    gone = rng.choice(pods)
    cache.delete_pod(PodID(gone.namespace, gone.name))
    cache.delete_policy(policies[0].namespace, policies[0].name)
    cache.resync(
        [p for p in pods if p is not gone], policies[1:],
        [cache.lookup_namespace(ns) for ns in namespaces])
    assert twin.commits >= len(policies)
    assert any(t.rules for t in twin.new.local_tables)


def _random_rule(rng):
    def net():
        if rng.random() < 0.3:
            return None
        if rng.random() < 0.1:
            return ipaddress.ip_network(f"fd00::{rng.randrange(4)}/128")
        plen = rng.choice((0, 8, 24, 28, 32))
        addr = rng.choice((0x0A010100, 0x0A010200, 0x0A000000, 0))
        return ipaddress.ip_network((addr, plen), strict=False)

    return ContivRule(
        action=rng.choice(list(Action)), src_network=net(),
        dest_network=net(), protocol=rng.choice(list(Protocol)),
        src_port=rng.choice((0, 0, 80)), dest_port=rng.choice((0, 80, 443)))


@pytest.mark.parametrize("seed", [5, 6])
def test_sort_key_is_compare_rules(seed):
    """The tuple key orders rules exactly as the earlier comparators did,
    and the one-sort table build equals rule-by-rule inserts."""
    rng = random.Random(seed)
    rules = [_random_rule(rng) for _ in range(300)]
    for a, b in zip(rules, rules[1:] + rules[:1]):
        want = old_compare_rules(a, b)
        assert compare_rules(a, b) == want, (a, b)
        assert compare_ints(a.sort_key, b.sort_key) == want, (a, b)
        assert (a < b) == (want < 0)
        assert (a == b) == (want == 0)
        for x, y in ((a.src_network, b.src_network),
                     (a.dest_network, b.dest_network)):
            assert compare_ip_nets(x, y) == old_compare_ip_nets(x, y)
        assert compare_ports(a.dest_port, b.dest_port) == \
            old_compare_ports(a.dest_port, b.dest_port)
    table = ContivRuleTable("t")
    for r in rules:
        old_insert(table, r)
    assert sorted_unique(rules) == table.rules
    fresh = ContivRuleTable("u")
    for r in rules:
        fresh.insert_rule(r)
    assert fresh.rules == table.rules
    lists = [sorted_unique(rng.sample(rules, rng.randint(0, 20)))
             for _ in range(40)]
    lists += [list(x) for x in lists[:10]]
    for a in lists:
        for b in lists[::7]:
            assert compare_rule_lists(a, b) == old_compare_rule_lists(a, b)


_POD_NETS = ("10.1.1.2/32", "10.1.1.9/32", "10.1.2.0/31", "10.1.2.0/32",
             "10.1.1.0/24")


def _pod_rule(rng, nets):
    """A permit or deny whose peer is one of ``nets`` (or anything)."""
    net = rng.choice(nets + (None,))
    return ContivRule(
        action=Action.PERMIT if rng.random() < 0.8 else Action.DENY,
        protocol=rng.choice((Protocol.TCP, Protocol.UDP, Protocol.ANY)),
        dest_port=rng.choice((0, 80, 443)),
        **{rng.choice(("src_network", "dest_network")):
           None if net is None else ipaddress.ip_network(net)})


@pytest.mark.parametrize("orientation", list(Orientation))
@pytest.mark.parametrize("seed", [7, 13, 31])
def test_fold_matches_oracle_on_shared_addresses(seed, orientation):
    """Pods that share an address, have none, or hold a subnet wider than
    one host: a later pin of an address and protocol replaces the
    one-host rules an earlier one put in, and the one-sort fold must
    still give the oracle's tables."""
    rng = random.Random(seed)
    twin = TwinRenderer(orientation)
    addrs = [ipaddress.ip_network(n) for n in _POD_NETS[:4]] + [None]
    for commit in range(4):
        txn = twin.new_txn(resync=commit == 0)
        for k in range(rng.randint(5, 9)):
            def rules():
                out = [_pod_rule(rng, _POD_NETS) for _ in range(rng.randint(0, 5))]
                if out and rng.random() < 0.8:
                    out += [ContivRule(action=Action.DENY, protocol=Protocol.TCP),
                            ContivRule(action=Action.DENY, protocol=Protocol.UDP)]
                return out
            txn.render(PodID("ns", f"p{k}"), rng.choice(addrs), rules(), rules(),
                       removed=rng.random() < 0.1)
        txn.commit()
    assert twin.commits == 4
    assert any(t.rules for t in twin.new.local_tables)
