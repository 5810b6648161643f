"""Agent flavor tests: full DI wiring, the KSR→store→agent dataflow
spine, CNI integration, config loading, and graceful shutdown.

Reference model: the control-plane dataflow of SURVEY.md §1 — K8s API →
KSR reflectors → data store → agent watchers → policy/service plugins →
renderers → data plane — exercised end to end in-process with a shared
in-memory store standing in for ETCD.
"""

import textwrap

import pytest

from vpp_tpu.cmd import AgentConfig, ContivAgent, load_config
from vpp_tpu.cmd.ksr_main import KsrAgent
from vpp_tpu.cni.model import CNIRequest
from vpp_tpu.ksr import model as m
from vpp_tpu.kvstore.store import KVStore
from vpp_tpu.pipeline.vector import Disposition, make_packet_vector


def boot(node_name="node-a"):
    store = KVStore()
    ksr = KsrAgent(store=store, serve_http=False)
    ksr.start()
    agent = ContivAgent(
        AgentConfig(node_name=node_name, serve_http=False), store=store
    )
    agent.start()
    return store, ksr, agent


def add_pod(agent, cid, name, ns="default"):
    reply = agent.cni_server.add(CNIRequest(
        container_id=cid,
        extra_args={"K8S_POD_NAME": name, "K8S_POD_NAMESPACE": ns},
    ))
    assert reply.result == 0
    return reply.interfaces[0].ip_addresses[0].address.split("/")[0]


def send(agent, src_pod, src_ip, dst_ip, dport, proto=6, sport=44444):
    pkts = make_packet_vector([
        {"src": src_ip, "dst": dst_ip, "proto": proto, "sport": sport,
         "dport": dport, "rx_if": agent.dataplane.pod_if[src_pod]}
    ])
    res = agent.dataplane.process(pkts)
    return Disposition(int(res.disp[0])), res


def reflect_pod(ksr, name, ip, labels, ns="default"):
    ksr.sources[m.Pod.TYPE].add(
        f"{ns}/{name}",
        m.Pod(name=name, namespace=ns, labels=labels, ip_address=ip),
    )


def test_agent_boots_and_allocates_node_id():
    store, ksr, agent = boot()
    assert agent.node_id == 1
    assert agent.statuscheck.liveness()["ready"] is True
    agent.close()


def test_full_spine_policy_enforcement():
    """KSR reflects pods+policy → agent watch bridge → renderers → verdicts."""
    store, ksr, agent = boot()
    ip_web = add_pod(agent, "c-web", "web")
    ip_db = add_pod(agent, "c-db", "db")
    ip_cli = add_pod(agent, "c-cli", "client")

    # KSR side: reflect the pods (as the k8s API would show them)
    reflect_pod(ksr, "web", ip_web, {"app": "web"})
    reflect_pod(ksr, "db", ip_db, {"app": "db"})
    reflect_pod(ksr, "client", ip_cli, {"app": "client"})
    ksr.sources[m.Namespace.TYPE].add(
        "default", m.Namespace(name="default", labels={})
    )

    # no policy yet: everything flows
    disp, _ = send(agent, ("default", "client"), ip_cli, ip_db, 5432)
    assert disp == Disposition.LOCAL

    # reflect a NetworkPolicy: db accepts only web on TCP:5432
    ksr.sources[m.Policy.TYPE].add("default/db-policy", m.Policy(
        name="db-policy", namespace="default",
        pods=m.LabelSelector(match_labels={"app": "db"}),
        policy_type=m.POLICY_INGRESS,
        ingress_rules=[m.PolicyRule(
            ports=[m.PolicyPort(protocol="TCP", port=5432)],
            peers=[m.PolicyPeer(
                pods=m.LabelSelector(match_labels={"app": "web"}))],
        )],
    ))

    disp, _ = send(agent, ("default", "client"), ip_cli, ip_db, 5432)
    assert disp == Disposition.DROP, "client is not app=web"
    disp, _ = send(agent, ("default", "web"), ip_web, ip_db, 5432)
    assert disp == Disposition.LOCAL, "web may reach db:5432"
    disp, _ = send(agent, ("default", "web"), ip_web, ip_db, 9999)
    assert disp == Disposition.DROP, "wrong port"

    # policy deleted → open again
    ksr.sources[m.Policy.TYPE].delete("default/db-policy")
    disp, _ = send(agent, ("default", "client"), ip_cli, ip_db, 5432)
    assert disp == Disposition.LOCAL
    agent.close()


def test_full_spine_service_nat():
    store, ksr, agent = boot()
    ip_cli = add_pod(agent, "c-cli", "client")
    ip_be = add_pod(agent, "c-be", "backend")

    ksr.sources[m.Service.TYPE].add("default/web", m.Service(
        name="web", namespace="default", cluster_ip="10.96.0.50",
        ports=[m.ServicePort(name="http", protocol="TCP", port=80,
                             target_port="http")],
    ))
    ksr.sources[m.Endpoints.TYPE].add("default/web", m.Endpoints(
        name="web", namespace="default",
        subsets=[m.EndpointSubset(
            addresses=[m.EndpointAddress(ip=ip_be, node_name="node-a")],
            ports=[m.EndpointPort(name="http", port=8080, protocol="TCP")],
        )],
    ))

    disp, res = send(agent, ("default", "client"), ip_cli, "10.96.0.50", 80)
    assert disp == Disposition.LOCAL
    assert int(res.pkts.dport[0]) == 8080, "DNAT to target port"
    agent.close()


def test_vpptcp_renderer_gets_policies_too():
    store, ksr, agent = boot()
    ip_web = add_pod(agent, "c-web", "web")
    ip_db = add_pod(agent, "c-db", "db")
    reflect_pod(ksr, "web", ip_web, {"app": "web"})
    reflect_pod(ksr, "db", ip_db, {"app": "db"})
    ksr.sources[m.Namespace.TYPE].add(
        "default", m.Namespace(name="default", labels={})
    )
    ksr.sources[m.Policy.TYPE].add("default/db-policy", m.Policy(
        name="db-policy", namespace="default",
        pods=m.LabelSelector(match_labels={"app": "db"}),
        policy_type=m.POLICY_INGRESS,
        ingress_rules=[m.PolicyRule(
            ports=[m.PolicyPort(protocol="TCP", port=5432)],
            peers=[m.PolicyPeer(
                pods=m.LabelSelector(match_labels={"app": "web"}))],
        )],
    ))
    assert agent.session_engine.num_rules > 0, "session rules installed"
    agent.close()


def test_agent_restart_resyncs_pods():
    store = KVStore()
    agent = ContivAgent(AgentConfig(node_name="n1", serve_http=False), store=store)
    agent.start()
    ip = add_pod(agent, "c1", "p1")
    agent.close()

    agent2 = ContivAgent(AgentConfig(node_name="n1", serve_http=False), store=store)
    agent2.start()
    assert ("default", "p1") in agent2.dataplane.pod_if
    assert agent2.node_id == 1, "same node keeps its ID"
    agent2.close()


def test_two_agents_get_distinct_node_ids_and_subnets():
    store = KVStore()
    a = ContivAgent(AgentConfig(node_name="n1", serve_http=False), store=store)
    b = ContivAgent(AgentConfig(node_name="n2", serve_http=False), store=store)
    assert (a.node_id, b.node_id) == (1, 2)
    assert a.ipam.pod_network != b.ipam.pod_network
    a.close(); b.close()


def test_agent_resyncs_preexisting_ksr_state():
    """KSR reflected objects BEFORE the agent started: the first resync
    must replay them into the policy cache and service processor."""
    store = KVStore()
    ksr = KsrAgent(store=store, serve_http=False)
    ksr.start()
    # reflect everything while no agent exists
    reflect_pod(ksr, "web", "10.1.1.10", {"app": "web"})
    reflect_pod(ksr, "db", "10.1.1.11", {"app": "db"})
    ksr.sources[m.Namespace.TYPE].add(
        "default", m.Namespace(name="default", labels={})
    )
    ksr.sources[m.Policy.TYPE].add("default/db-policy", m.Policy(
        name="db-policy", namespace="default",
        pods=m.LabelSelector(match_labels={"app": "db"}),
        policy_type=m.POLICY_INGRESS,
        ingress_rules=[m.PolicyRule(
            ports=[m.PolicyPort(protocol="TCP", port=5432)],
            peers=[m.PolicyPeer(
                pods=m.LabelSelector(match_labels={"app": "web"}))],
        )],
    ))

    agent = ContivAgent(AgentConfig(node_name="late", serve_http=False),
                        store=store)
    agent.start()
    # resync picked up the reflected objects
    assert agent.policy_cache.lookup_pod(("default", "web")) is not None
    ip_web = add_pod(agent, "c-web", "web")
    ip_db = add_pod(agent, "c-db", "db")
    # kubelet assigned real IPs; KSR re-reflects them
    reflect_pod(ksr, "web", ip_web, {"app": "web"})
    reflect_pod(ksr, "db", ip_db, {"app": "db"})
    # the pre-existing policy must be enforced
    disp, _ = send(agent, ("default", "web"), ip_web, ip_db, 9999)
    assert disp == Disposition.DROP, "pre-existing policy enforced"
    disp, _ = send(agent, ("default", "web"), ip_web, ip_db, 5432)
    assert disp == Disposition.LOCAL
    agent.close()


def test_node_events_install_and_remove_peer_routes():
    """Two agents on one store: each learns the other's subnets and
    routes them REMOTE via the peer VTEP (node_events.go analog)."""
    store = KVStore()
    a = ContivAgent(AgentConfig(node_name="n1", serve_http=False), store=store)
    a.start()
    b = ContivAgent(AgentConfig(node_name="n2", serve_http=False), store=store)
    b.start()

    ip_a = add_pod(a, "c1", "p1")
    # a pod on node A sending to node B's pod subnet → REMOTE toward B
    dst_b = str(b.ipam.pod_gateway_ip() + 5)
    disp, res = send(a, ("default", "p1"), ip_a, dst_b, 80)
    assert disp == Disposition.REMOTE
    assert int(res.node_id[0]) == b.node_id
    outer = a.dataplane.encap_remote(res)
    assert bool(outer.valid[0])
    assert int(outer.dst_ip[0]) == int(a.ipam.vxlan_ip_address(b.node_id))

    # B also learned A (it listed existing nodes at startup)
    ip_b = add_pod(b, "c2", "p2")
    disp_b, res_b = send(b, ("default", "p2"), ip_b, ip_a, 80)
    assert disp_b == Disposition.REMOTE
    assert int(res_b.node_id[0]) == a.node_id

    # node removal deletes the routes
    b.node_allocator.release()
    disp, _ = send(a, ("default", "p1"), ip_a, dst_b, 80)
    assert disp == Disposition.DROP
    a.close(); b.close()


def test_node_crash_lease_expiry_removes_peer_routes():
    """A node that dies WITHOUT cleanup (kill -9, partition) must lose
    its routes on peers once its liveness lease expires — the etcd-lease
    liveness mechanism (VERDICT r2 Next #8). Clean release() is covered
    above; this is the crash path: no delete is ever issued."""
    store = KVStore()
    a = ContivAgent(AgentConfig(node_name="n1", serve_http=False), store=store)
    a.start()
    b = ContivAgent(AgentConfig(node_name="n2", serve_http=False), store=store)
    b.node_allocator.liveness_ttl_s = 0.3
    b.start()

    ip_a = add_pod(a, "c1", "p1")
    dst_b = str(b.ipam.pod_gateway_ip() + 5)
    disp, _ = send(a, ("default", "p1"), ip_a, dst_b, 80)
    assert disp == Disposition.REMOTE

    # B "crashes": stop its maintenance loop (keepalives) without any
    # cleanup; its allocatedIDs claim stays (ID reuse on restart), but
    # the liveness key must expire
    b._closed.set()
    import time

    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        store.sweep_leases()
        disp, _ = send(a, ("default", "p1"), ip_a, dst_b, 80)
        if disp == Disposition.DROP:
            break
        time.sleep(0.1)
    assert disp == Disposition.DROP
    # the ID claim survives (restarting node-b reuses its ID)
    assert store.get("allocatedIDs/" + str(b.node_id)) is not None
    a.close(); b.close()


def test_config_yaml_roundtrip(tmp_path):
    cfg_file = tmp_path / "contiv.yaml"
    cfg_file.write_text(textwrap.dedent("""
        node_name: worker-7
        stats_port: 19999
        dataplane:
          max_tables: 8
          sess_slots: 512
        ipam:
          pod_subnet_cidr: 10.128.0.0/14
    """))
    cfg = load_config(str(cfg_file))
    assert cfg.node_name == "worker-7"
    assert cfg.stats_port == 19999
    assert cfg.dataplane.max_tables == 8
    assert cfg.ipam.pod_subnet_cidr == "10.128.0.0/14"
    # defaults survive partial files
    assert cfg.health_port == 9191

    bad = tmp_path / "bad.yaml"
    bad.write_text("nonsense_key: 1\n")
    with pytest.raises(ValueError, match="nonsense_key"):
        load_config(str(bad))


@pytest.mark.parametrize("raw,match", [
    ({"tuned_profile": "tuned/cpu.json"},
     r"unknown config keys: \['tuned_profile'\]"),
    ({"io": {"no_such_io_knob": 1}},
     r"unknown config keys in 'io': \['no_such_io_knob'\]"),
])
def test_from_dict_refuses_unknown_keys(raw, match):
    """A key no config section declares is refused at load, at top
    level and inside a section, never silently ignored."""
    with pytest.raises(ValueError, match=match):
        AgentConfig.from_dict(raw)


def test_maintenance_tick_ages_sessions_and_publishes():
    store, ksr, agent = boot()
    ip1 = add_pod(agent, "c1", "p1")
    ip2 = add_pod(agent, "c2", "p2")
    disp, res = send(agent, ("default", "p1"), ip1, ip2, 80)
    assert disp == Disposition.LOCAL
    import numpy as np
    assert int(np.asarray(agent.dataplane.tables.sess_valid).sum()) == 1
    agent.stats.update(res.stats)

    agent.session_max_age = 0  # everything idle > 0 frames expires
    agent.dataplane._now += 5
    agent.maintenance_tick()
    assert int(np.asarray(agent.dataplane.tables.sess_valid).sum()) == 0
    assert agent.stats.node_gauges["vpp_tpu_node_rx_packets"].get() == 1
    assert agent.statuscheck.liveness()["ready"] is True
    agent.close()


def test_close_is_idempotent_and_stops_watches():
    store, ksr, agent = boot()
    agent.close()
    agent.close()  # second close is a no-op
    # events after close must not reach the plugins
    ksr.sources[m.Pod.TYPE].add(
        "default/late",
        m.Pod(name="late", namespace="default", labels={}, ip_address="10.0.0.9"),
    )
    assert agent.policy_cache.lookup_pod(("default", "late")) is None


def test_cli_socket_serves_debug_commands(tmp_path):
    """A running agent answers vppctl-style commands over its CLI
    socket — the operator path `vpp-tpu-ctl "show interface"`."""
    from vpp_tpu.cmd.ctl import run_line

    store = KVStore()
    cfg = AgentConfig(
        node_name="n1", serve_http=True,
        stats_port=0, health_port=0,
        cni_socket=str(tmp_path / "cni.sock"),
        cli_socket=str(tmp_path / "cli.sock"),
    )
    agent = ContivAgent(cfg, store=store)
    agent.start()
    try:
        out = run_line(cfg.cli_socket, "show interface", timeout=10)
        assert "uplink" in out
        out = run_line(cfg.cli_socket, "show fib", timeout=10)
        assert "0.0.0.0/0" in out
        out = run_line(cfg.cli_socket, "help", timeout=10)
        assert "test connectivity" in out
        # unknown commands degrade to a message over the wire
        out = run_line(cfg.cli_socket, "bogus words", timeout=10)
        assert "unknown command" in out
        # the vppctl trace workflow: arm over the socket, traffic
        # through the dataplane, render the captured path
        out = run_line(cfg.cli_socket, "trace add 4", timeout=10)
        assert "tracing the next 4" in out
        from vpp_tpu.pipeline.vector import make_packet_vector

        agent.dataplane.process(make_packet_vector([
            {"src": "10.9.9.9", "dst": "10.9.9.10", "proto": 17,
             "sport": 1, "dport": 2, "rx_if": agent.uplink_if}
        ]))
        out = run_line(cfg.cli_socket, "show trace", timeout=10)
        # src shows post-SNAT (cluster egress rewrites to the node IP)
        assert "10.9.9.10" in out and "ip4-input" in out
        assert "cleared" in run_line(cfg.cli_socket, "trace clear", 10)
    finally:
        agent.close()
